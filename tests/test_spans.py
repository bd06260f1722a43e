"""The span rings and the loop-lag mark (obs/spans.py) and what the engine
and the worker write into them: CPU, tiny model."""

import asyncio
import dataclasses
import json
import logging
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from llmq_tpu.engine.engine import (
    AsyncEngine,
    EngineConfig,
    EngineCore,
    scopes_from_hlo_text,
)
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.scheduler import Sequence
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.cache import cache_layout
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.obs import get_registry
from llmq_tpu.obs import spans as spans_mod
from llmq_tpu.obs.spans import LoopLag, SpanRing, merge_dumps, self_times_ns
from llmq_tpu.parallel import make_mesh

CFG = ModelConfig.tiny(vocab_size=304)
PARAMS = init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def make_core(**engine) -> EngineCore:
    defaults = dict(
        max_num_seqs=4, max_model_len=64, page_size=8, num_pages=40,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    defaults.update(engine)
    return EngineCore(
        CFG, PARAMS, ByteTokenizer(), mesh=make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**defaults),
    )


def greedy(max_tokens=6):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True)


def serve(engine, n, prefix="r", max_tokens=6):
    async def main():
        return await asyncio.gather(
            *[
                engine.generate(
                    rid=f"{prefix}{i}", prompt=f"request {i}", params=greedy(max_tokens)
                )
                for i in range(n)
            ]
        )

    return asyncio.run(main())


def names(dump):
    return [s["name"] for s in dump["spans"]]


def profile_options():
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


# --- the ring itself ----------------------------------------------------------


def test_a_ring_that_was_never_on_holds_nothing():
    ring = SpanRing("t")
    dump = ring.dump()
    assert not ring.on and dump["spans"] == [] and dump["requests"] == {}
    assert dump["counters"] == {"t.spans_written": 0, "t.spans_overwritten": 0}
    assert not ring._buf  # nothing allocated until it is switched on


def test_span_fields_cause_and_order():
    ring = SpanRing("t")
    ring.set(True)
    outer = ring.begin("outer", a=1)
    inner = ring.begin("inner")
    assert ring.inside("inner") and not ring.inside("outer")
    assert ring.end(b=2) == inner
    assert ring.end(c=3) == outer and ring.end() == 0
    late = ring.add("held", 5, 9, cause=outer, free=1)
    dump = ring.dump()
    by_name = {s["name"]: s for s in dump["spans"]}
    assert by_name["inner"]["cause"] == by_name["outer"]["id"] == outer
    assert by_name["outer"]["cause"] == 0
    assert (by_name["outer"]["a"], by_name["outer"]["c"], by_name["inner"]["b"]) == (1, 3, 2)
    assert by_name["held"]["id"] == late and by_name["held"]["free"] == 1
    assert by_name["outer"]["t0_ns"] <= by_name["inner"]["t0_ns"]
    assert by_name["inner"]["t1_ns"] <= by_name["outer"]["t1_ns"]
    assert [s["t0_ns"] for s in dump["spans"]] == sorted(s["t0_ns"] for s in dump["spans"])
    assert all(s["ring"] == "t" for s in dump["spans"])


def test_cause_ids_run_from_dispatch_to_fetch_to_emit():
    """What the engine does around one dispatch, on the ring alone."""
    ring = SpanRing("t")
    ring.set(True)
    turn = ring.begin("turn", 0)
    dispatch = ring.begin("prefill_dispatch", rows=1)
    ring.end_dispatch(7)
    ring.begin("fetch", ring.cause_of(7), seq=7)
    emit = ring.then("emit")
    ring.end()
    ring.close_all()
    by_name = {s["name"]: s for s in ring.dump()["spans"]}
    assert by_name["prefill_dispatch"]["cause"] == turn
    assert by_name["prefill_dispatch"]["seq"] == 7
    assert by_name["fetch"]["cause"] == dispatch
    assert by_name["emit"]["cause"] == by_name["fetch"]["id"] and by_name["emit"]["id"] == emit
    assert by_name["fetch"]["t1_ns"] <= by_name["emit"]["t0_ns"]
    assert ring.cause_of(7) == 0  # taken once


def test_ring_wraps_and_counts_what_it_overwrote():
    ring = SpanRing("t", capacity=8)
    ring.set(True)
    for i in range(20):
        ring.add("n", i, i + 1, i=i)
    dump = ring.dump()
    assert [s["i"] for s in dump["spans"]] == list(range(12, 20))
    assert dump["counters"]["t.spans_written"] == 20
    assert dump["counters"]["t.spans_overwritten"] == 12


def test_requests_are_bounded_and_merged_by_rid():
    ring = SpanRing("t", max_requests=3)
    ring.set(True)
    for i in range(5):
        ring.note_request(f"r{i}", enqueued=float(i))
    ring.note_request("r4", admitted=9.0)
    assert list(ring.requests) == ["r2", "r3", "r4"]
    other = SpanRing("u")
    other.set(True)
    other.note_request("r4", claimed=3.5)
    merged = merge_dumps([ring.dump(), other.dump()])
    assert merged["requests"]["r4"] == {"enqueued": 4.0, "admitted": 9.0, "claimed": 3.5}


def test_switching_off_closes_what_was_open():
    ring = SpanRing("t")
    ring.set(True)
    ring.begin("turn")
    ring.begin("admit")
    ring.end_dispatch(3)
    ring.begin("admit")
    ring.set(False)
    assert names(ring.dump()) == ["turn", "admit", "admit"]
    assert not ring._stack and not ring._dispatch_span and not ring.on


def test_follows_the_profiler_unless_switched_on_by_hand():
    ring = SpanRing("t")
    ring.follow_profiler(True)
    assert ring.on and ring.profiled
    ring.follow_profiler(False)
    assert not ring.on and not ring.profiled
    assert names(ring.dump()) == ["profile"]
    ring.set(True)
    ring.follow_profiler(True)
    ring.follow_profiler(False)
    assert ring.on  # by hand: the profiler's going changes nothing
    ring.set(False)
    assert not ring.on


def test_self_time_is_span_minus_children():
    def s(i, name, t0, t1, ring="e"):
        return {"id": i, "name": name, "t0_ns": t0, "t1_ns": t1, "ring": ring}

    rows = [
        s(1, "turn", 0, 100),
        s(2, "admit", 10, 60),
        s(3, "prefill_dispatch", 20, 50),
        s(4, "decode_dispatch", 70, 90),
        s(5, "admit_hold", 55, 130),  # overlaps the turn's end: nobody's child
        s(6, "loop_tick", 0, 100, ring="worker"),
    ]
    own = self_times_ns(rows)
    assert own[1] == 100 - 50 - 20
    assert own[2] == 50 - 30 and own[3] == 30 and own[4] == 20
    assert own[5] == 75 and own[6] == 100


def test_spans_are_annotations_in_a_profile(tmp_path):
    """While the ring is on, a span is a TraceAnnotation too: it lands on
    the host plane of a profile with both clocks."""
    from jax.profiler import ProfileData, TraceAnnotation

    ring = SpanRing("t")
    ring.set(True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=profile_options())
    try:
        assert TraceAnnotation.is_enabled()
        ring.begin("turn")
        time.sleep(0.002)
        ring.end()
    finally:
        jax.profiler.stop_trace()
    assert not TraceAnnotation.is_enabled()
    span = ring.dump()["spans"][0]
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    found = [
        dict(ev.stats)
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "llmq.turn"
    ]
    assert found and found[0]["span_id"] == span["id"]
    assert found[0]["t_mono_ns"] == span["t0_ns"]


# --- the engine thread ----------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    eng = AsyncEngine(make_core())
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def untraced(engine):
    before = {t.name for t in threading.enumerate()}
    outs = serve(engine, 3, prefix="off", max_tokens=12)
    return outs, before


def test_off_is_off(engine, untraced):
    """With the ring off the engine thread writes nothing, starts nothing
    and keeps nothing per token."""
    outs, threads_before = untraced
    time.sleep(1.1)  # 50 turns of the idle loop (20 ms each) on top of the serving
    ring = engine.core.spans
    assert not ring.on and not ring._buf and not ring._stack
    assert not ring.requests and not ring._dispatch_span
    assert engine.trace_dump()["counters"]["engine.spans_written"] == 0
    assert {t.name for t in threading.enumerate()} <= threads_before
    assert not any(
        word in t.name for t in threading.enumerate()
        for word in ("lag", "span", "sampler")
    )
    for out in outs:
        assert set(out.timing) == {
            "engine_submit", "enqueued", "admitted", "prefill_start",
            "first_token", "last_token", "finished", "preempt_count",
        }
        assert 0.0 < out.timing["engine_submit"] <= out.timing["enqueued"]


def test_no_sequence_holds_a_per_token_stamp_list():
    core = make_core()
    core.spans.set(True)  # not even while the ring is on
    seq = core.add_request("only", prompt="one request", params=greedy(12))
    while not seq.finish_reason:
        core.step()
    assert len(seq.output_ids) == 12
    for field in dataclasses.fields(seq):
        value = getattr(seq, field.name)
        if isinstance(value, list) and len(value) >= 12:
            assert not all(isinstance(v, float) for v in value), field.name


@pytest.fixture(scope="module")
def traced(engine, untraced):
    engine.set_tracing(True)
    outs = serve(engine, 6, prefix="on")
    time.sleep(0.1)  # the turn that resolved the last request ends at the next one's top
    dump = engine.trace_dump()
    engine.set_tracing(False)
    return outs, dump


def test_a_served_request_yields_the_engine_spans(traced):
    _, dump = traced
    assert set(names(dump)) == {
        "turn", "intake", "admit", "prefill_dispatch", "decode_dispatch",
        "fetch", "emit", "resolve",
    }
    by_id = {s["id"]: s for s in dump["spans"]}
    for s in dump["spans"]:
        if s["name"] in ("intake", "admit", "decode_dispatch", "resolve"):
            assert by_id[s["cause"]]["name"] == "turn"
        if s["name"] == "turn":
            assert s["cause"] == 0 and {"pending", "waiting", "running"} <= set(s)
        if s["name"] == "prefill_dispatch":
            assert by_id[s["cause"]]["name"] == "admit"
            assert s["program"] == "prefill_step" and s["mode"] == "greedy"
            rows, bucket = s["variant"].split("x")
            assert s["rows"] == len(s["rids"]) <= int(rows) and bucket == "16"
        if s["name"] == "decode_dispatch":
            assert s["program"] == "decode_step" and s["variant"] == ""
        if s["name"] == "admit":
            assert s["rows"] >= 1 and "waiting" in s
        if s["name"] == "emit":
            assert by_id[s["cause"]]["name"] == "fetch"
    assert sum(s["n"] for s in dump["spans"] if s["name"] == "intake") == 6


def test_decode_dispatch_counts_the_live_pages(traced):
    """``live_pages``: what the decode kernel has to visit a layer. The
    prompts are 9 bytes and 6 tokens come out: every sequence sits in its
    second 8-token page for the whole run."""
    _, dump = traced
    decodes = [s for s in dump["spans"] if s["name"] == "decode_dispatch"]
    assert decodes
    for s in decodes:
        assert s["live_pages"] == 2 * s["rows"] > 0


def test_live_pages_follow_the_window_of_a_model_that_slides():
    core = make_core()
    seqs = [
        Sequence(rid=f"w{n}", prompt_ids=[1] * n, params=greedy())
        for n in (1, 8, 9, 40)
    ]
    lengths = [s.num_tokens for s in seqs]
    sizes = dict(
        page_size=core.cfg.page_size, max_model_len=core.cfg.max_model_len,
        max_num_seqs=core.cfg.max_num_seqs,
    )
    assert core.cache.live_pages(lengths) == 1 + 1 + 2 + 5
    slides = cache_layout(dataclasses.replace(CFG, sliding_window=10), **sizes)
    # ctx 40, window 10: positions 30..39, pages 3 and 4 of 0..4.
    assert slides.live_pages(lengths) == 1 + 1 + 2 + 2
    every_second = cache_layout(
        dataclasses.replace(CFG, sliding_window=10, sliding_window_pattern=2), **sizes
    )  # every second layer sees the whole context: count those
    assert every_second.live_pages(lengths) == 1 + 1 + 2 + 5


def test_the_benchmarks_span_stat_reads_live_pages(traced, monkeypatch):
    """``decode_live_pages_mean`` is ``span_stat`` over this field."""
    from types import SimpleNamespace

    from benchmark import span_join
    from benchmark.readers import span_stat

    _, dump = traced
    monkeypatch.setattr(span_join, "process_dump", lambda: dump)
    spec = json.loads(
        open("benchmark/layer_metrics/decode_live_pages_mean.json").read()
    )
    assert spec["reader"] == "span_stat"
    ctx = SimpleNamespace(
        records=SimpleNamespace(rows=[], t0=0.0, t1=float("inf")), trace=None
    )
    decodes = [s for s in dump["spans"] if s["name"] == "decode_dispatch"]
    assert span_stat.read(ctx, **spec["args"]) == pytest.approx(
        sum(s["live_pages"] for s in decodes) / len(decodes)
    )
    # A program without the field (the parent): nothing, and no error.
    for s in dump["spans"]:
        s.pop("live_pages", None)
    ctx = SimpleNamespace(records=ctx.records, trace=None)
    assert span_stat.read(ctx, **spec["args"]) is None


def test_every_fetch_is_caused_by_an_earlier_dispatch(traced):
    _, dump = traced
    by_id = {s["id"]: s for s in dump["spans"]}
    fetches = [s for s in dump["spans"] if s["name"] == "fetch" and s["cause"]]
    assert len(fetches) >= 6
    for f in fetches:
        cause = by_id[f["cause"]]
        assert cause["name"] in ("prefill_dispatch", "decode_dispatch")
        assert cause["seq"] == f["seq"] and cause["t1_ns"] <= f["t0_ns"]
    assert {f["kind"] for f in fetches} == {"prefill", "decode"}


def test_dump_joins_the_request_stamps_in_order(traced):
    outs, dump = traced
    order = ["engine_submit", "enqueued", "admitted", "prefill_start", "first_token",
             "last_token", "finished"]
    for out in outs:
        kept = dump["requests"][out.rid]
        assert kept == out.timing
        assert [kept[k] for k in order] == sorted(kept[k] for k in order)
    # A worker's ring adds the claim; merged by rid it comes first.
    worker = SpanRing("worker")
    worker.set(True)
    first = outs[0]
    worker.note_request(first.rid, claimed=first.timing["engine_submit"] - 1e-3)
    row = merge_dumps([worker.dump(), dump])["requests"][first.rid]
    keys = ["claimed"] + order
    assert [row[k] for k in keys] == sorted(row[k] for k in keys)


def test_tokens_are_the_same_with_the_ring_on_and_off(untraced, traced, engine):
    on = serve(engine, 3, prefix="again", max_tokens=12)
    assert not engine.core.spans.on
    engine.set_tracing(True)
    try:
        again = serve(engine, 3, prefix="again-on", max_tokens=12)
    finally:
        engine.set_tracing(False)
    assert [o.token_ids for o in again] == [o.token_ids for o in on]
    assert [o.token_ids for o in on] == [o.token_ids for o in untraced[0]]


def test_dump_maps_instructions_to_scopes(traced):
    _, dump = traced
    decode = dump["scopes"]["decode_step"]["greedy/"]
    scopes = set(decode.values())
    assert {"llmq.decode_step", "llmq.qkv", "llmq.attn.xla", "llmq.kv_write",
            "llmq.mlp", "llmq.lm_head", "llmq.sample", "llmq.embed"} <= scopes
    prefill = dump["scopes"]["prefill_step"]
    assert set(prefill) <= {"greedy/1x16", "greedy/4x16"} and prefill
    assert "llmq.prefill_step" in set(next(iter(prefill.values())).values())
    json.dumps(dump)  # the whole dump is plain data


def test_engine_ring_follows_a_profile_and_marks_it(tmp_path):
    """No switch touched: the ring is on for as long as a profile of the
    process is taken, and a ``profile`` span marks the stretch."""
    eng = AsyncEngine(make_core())
    switched = []
    eng.on_tracing = switched.append
    try:
        serve(eng, 2, prefix="before")
        jax.profiler.start_trace(str(tmp_path), profiler_options=profile_options())
        try:
            serve(eng, 3, prefix="during")
        finally:
            jax.profiler.stop_trace()
        time.sleep(0.1)  # a turn of the idle loop notices the end
        serve(eng, 1, prefix="after")
        dump = eng.trace_dump()
    finally:
        eng.shutdown()
    assert switched == [True, False] and not eng.core.spans.on
    marks = [s for s in dump["spans"] if s["name"] == "profile"]
    assert len(marks) == 1
    rids = {r for s in dump["spans"] if s["name"] == "prefill_dispatch" for r in s["rids"]}
    assert rids == {"during0", "during1", "during2"}
    assert all(marks[0]["t0_ns"] <= s["t0_ns"] <= marks[0]["t1_ns"]
               for s in dump["spans"] if s["name"] == "prefill_dispatch")
    assert set(dump["requests"]) == {"during0", "during1", "during2"}


def test_scopes_from_hlo_text_names_the_gspmd_all_reduce():
    text = """
  %fusion.7 = bf16[8,64]{1,0} fusion(%p0), kind=kLoop, calls=%fc, metadata={op_name="jit(decode_step)/llmq.decode_step/while/body/llmq.mlp/dot_general" source_file="x.py"}
  %all-reduce.3 = bf16[8,64]{1,0} all-reduce(%fusion.7), metadata={op_name="jit(decode_step)/llmq.decode_step/while/body/llmq.o_proj/dot_general"}
  ROOT %copy.1 = bf16[8,64]{1,0} copy(%all-reduce.3), metadata={op_name="jit(decode_step)/jit(main)/add"}
"""
    assert scopes_from_hlo_text(text) == {
        "fusion.7": "llmq.mlp",
        "all-reduce.3": "llmq.tp.allreduce.o_proj",
    }


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_SOURCE_TABLES = re.compile(r"\nFileNames\n.*?\n\n\n", re.S)


def without_metadata(hlo_text: str) -> str:
    """A compiled module's text less what names where an instruction
    came from: its ``metadata={...}`` and the tables of files, functions
    and stack frames those point into."""
    return _METADATA.sub("", _SOURCE_TABLES.sub("\n", hlo_text))


def test_scopes_change_the_compiled_step_in_metadata_only(monkeypatch):
    """``jax.named_scope`` writes ``op_name`` metadata and nothing else:
    the compiled text of ``decode_step`` with the scopes and without them
    is the same once the metadata is cut out. (That is also why the
    compile cache, whose key leaves metadata out, hands back an entry
    written before the scopes existed: it carries none.)"""
    from jax._src import source_info_util as siu
    from jax.experimental.compilation_cache import compilation_cache

    # Not through the persistent cache, which an earlier test file of this
    # process may have switched on: it would hand the second compile the
    # first one's entry, metadata and all (that is the docstring's point).
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compiled_text() -> str:
        jax.clear_caches()
        core = make_core()
        seq = core.add_request("s", prompt="scoped or not", params=greedy(3))
        while not seq.finish_reason:
            core.step()
        prog = core._decode_jits["greedy"]
        return prog.lower(*prog.variants[""]).compile().as_text()

    scoped = compiled_text()
    scope = siu.ExtendNameStackContextManager
    real_enter = scope.__enter__

    def enter_unless_ours(self):
        if not self.name.startswith("llmq."):
            return real_enter(self)
        self.prev = siu._source_info_context.context  # what __exit__ restores

    monkeypatch.setattr(scope, "__enter__", enter_unless_ours)
    bare = compiled_text()
    monkeypatch.undo()
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    assert "llmq.mlp" in scoped and "llmq." not in bare
    assert without_metadata(scoped) == without_metadata(bare)
    assert "fusion" in without_metadata(bare)


def test_admit_hold_opens_and_closes_under_a_forced_hold():
    """Two running, one slot free, two waiting: the free slot is held for
    a full batch until ``admit_max_wait_s`` expires."""
    core = make_core(max_num_seqs=3, max_prefill_batch=2, admit_max_wait_s=0.01)
    core.spans.set(True)
    for i in range(2):
        core.add_request(f"a{i}", prompt=f"first {i}", params=greedy(55))
    for _ in range(4):
        core.step()
    for i in range(2):
        core.add_request(f"b{i}", prompt=f"second {i}", params=greedy(4))
    deadline = time.monotonic() + 20
    while "b0" not in core.scheduler.running and time.monotonic() < deadline:
        core.step()
    holds = [s for s in core.spans.dump()["spans"] if s["name"] == "admit_hold"]
    assert len(holds) == 1
    hold = holds[0]
    assert hold["expired"] == 1 and hold["free"] == 1 and hold["waiting"] == 2
    assert (hold["t1_ns"] - hold["t0_ns"]) / 1e9 >= 0.01
    assert core._defer_since is None


# --- the event loop ---------------------------------------------------------------


def test_a_blocked_loop_is_marked_logged_and_kept(caplog):
    ring = SpanRing("worker")
    lag = LoopLag(ring, period_s=0.02, late_s=0.01, stall_s=0.15)

    async def main():
        lag.start()
        await asyncio.sleep(0.07)
        quiet = lag.snapshot()
        ring.set(True)
        time.sleep(0.3)  # the loop stands still
        t_after = time.monotonic()
        await asyncio.sleep(0.07)
        lag.stop()
        ticks = lag.ticks
        await asyncio.sleep(0.06)
        assert lag.ticks == ticks  # cancelled: the chain has ended
        return quiet, t_after

    with caplog.at_level(logging.WARNING, logger="llmq_tpu.obs.spans"):
        quiet, t_after = asyncio.run(main())
    assert quiet["ticks"] >= 2 and quiet["max_ms"] < 150
    snap = lag.snapshot()
    stalls = [(t, ms) for t, ms in snap["late"] if ms >= 150]
    assert len(stalls) == 1 and snap["max_ms"] == stalls[0][1] >= 250
    assert abs(stalls[0][0] - t_after) < 0.05
    assert snap["late_total"] == len(snap["late"])
    warned = [r for r in caplog.records if "stood still" in r.getMessage()]
    assert len(warned) == 1
    ticks = [s for s in ring.dump()["spans"] if s["name"] == "loop_tick"]
    assert ticks and max(s["late_ms"] for s in ticks) == snap["max_ms"]
    assert spans_mod.dump_process()["loop_lag"]["ticks"] >= 1


def test_a_loop_that_stands_still_has_every_stack_written_meanwhile(tmp_path):
    sink = open(tmp_path / "stacks", "a", encoding="utf-8")
    lag = LoopLag(SpanRing("worker"), period_s=0.02, stall_s=0.1, stack_sink=sink)

    def the_call_that_holds_the_loop():
        time.sleep(0.3)
        # Written by faulthandler's own thread while this one slept.
        return (tmp_path / "stacks").read_text()

    async def main():
        lag.start()
        await asyncio.sleep(0.05)
        during = the_call_that_holds_the_loop()
        await asyncio.sleep(0.05)
        lag.stop()
        return during

    during = asyncio.run(main())
    assert "the_call_that_holds_the_loop" in during
    assert sink.closed and lag.stack_sink is None
    time.sleep(0.2)  # disarmed: nothing more is written
    assert (tmp_path / "stacks").read_text().count("the_call_that_holds") == during.count(
        "the_call_that_holds"
    )


async def test_worker_marks_loop_lag_and_stamps_the_claim(mem_url, tmp_path, monkeypatch):
    """Ring off: the worker's only addition to the loop is the lag mark's
    one timer, and LLMQ_TRACE_LOG stays the lifecycle sink it was: no
    ring on, no stacks file, no span dump. With LLMQ_SPANS both rings are
    on from the start, claimed <= engine_submit in one record, the
    heartbeat and /metrics carry the loop's lag, and the dump is written
    to that file at shutdown; LLMQ_STALL_STACKS alone opens the sink of
    the stacks."""
    from llmq_tpu.broker.manager import BrokerManager
    from llmq_tpu.core.config import Config
    from llmq_tpu.core.models import Job, Result
    from llmq_tpu.workers.tpu_worker import TPUWorker

    async def run_worker(queue):
        eng = AsyncEngine(make_core())
        worker = TPUWorker(
            queue, model="preset://tiny", config=Config(broker_url=mem_url),
            concurrency=4, engine_factory=lambda w: eng,
        )
        broker = BrokerManager(Config(broker_url=mem_url))
        await broker.connect()
        await broker.setup_queue_infrastructure(queue)
        task = asyncio.create_task(worker.run())
        results = []

        async def handler(message):
            results.append(Result.model_validate_json(message.body))
            await message.ack()

        try:
            while not worker.running:
                await asyncio.sleep(0.01)
            await broker.consume_results(f"{queue}.results", handler)
            for i in range(2):
                await broker.publish_job(
                    queue,
                    Job(id=f"j{i}", prompt="hello", temperature=0.0, max_tokens=4,
                        ignore_eos=True),
                )
            deadline = time.monotonic() + 60
            while len(results) < 2 and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.25)  # a few ticks of the lag mark
            loop = asyncio.get_running_loop()
            timers = [
                h for h in loop._scheduled
                if not h.cancelled()
                and getattr(h._callback, "__self__", None) is worker._loop_lag
            ]
            state = dict(
                timers=len(timers), tasks=len(asyncio.all_tasks()),
                on=(worker.spans.on, eng.core.spans.on),
                stats=worker._stats_with_robustness(),
                dump=worker.trace_dump(),
                gauge=get_registry().summary().get("llmq_loop_lag_max_ms"),
            )
        finally:
            worker.request_shutdown()
            await asyncio.wait_for(task, timeout=30)
            await broker.disconnect()
        assert worker._loop_lag._handle is None
        return state, results

    log = tmp_path / "trace.jsonl"
    monkeypatch.setenv("LLMQ_TRACE_LOG", str(log))
    off, results = await run_worker("spans-off-q")
    assert off["on"] == (False, False) and off["timers"] == 1
    assert sorted(p.name for p in tmp_path.iterdir()) == ["trace.jsonl"]
    lines = [json.loads(line) for line in log.read_text().splitlines()]
    assert lines and not [line for line in lines if line.get("event") == "span_dump"]
    assert off["dump"]["spans"] == [] and off["dump"]["requests"] == {}
    assert off["stats"]["loop_lag_max_ms"] >= 0 and off["gauge"] is not None
    events = [e["name"] for e in results[0].model_dump()["trace"]["events"]]
    assert events.index("claimed") < events.index("tokenized")

    monkeypatch.delenv("LLMQ_TRACE_LOG")
    monkeypatch.setenv("LLMQ_SPANS", str(tmp_path / "spans.json"))
    monkeypatch.setenv("LLMQ_STALL_STACKS", str(tmp_path / "stacks"))
    on, _ = await run_worker("spans-on-q")
    assert on["on"] == (True, True) and on["timers"] == 1
    assert on["tasks"] == off["tasks"]  # tracing starts no task of its own
    for rid in ("j0", "j1"):
        t = on["dump"]["requests"][rid]
        assert t["claimed"] <= t["engine_submit"] <= t["enqueued"] <= t["admitted"]
    assert {"turn", "loop_tick"} <= set(names(on["dump"]))
    dumped = json.loads((tmp_path / "spans.json").read_text())
    assert dumped["spans"] and dumped["worker_id"]
    assert (tmp_path / "stacks").exists()


def test_no_lag_mark_on_a_virtual_loop():
    """The fleet simulator's loop runs on virtual time: nothing is late
    there, and the mark must not add ten timers a virtual second."""
    from llmq_tpu.sim.vloop import run_virtual

    lag = LoopLag(SpanRing("worker"), period_s=0.02)

    async def main():
        lag.start()
        armed = lag._handle is not None
        await asyncio.sleep(1.0)
        lag.stop()
        return armed

    assert run_virtual(main()) is False and lag.ticks == 0
