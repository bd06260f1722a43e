"""The decode run-ahead's in-flight target (``engine.runahead_target``):
the rule as a pure function, and the engine that follows it. CPU, tiny
model; no time here is a device time."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from llmq_tpu.engine import engine as engine_mod
from llmq_tpu.engine.engine import EngineConfig, EngineCore, runahead_target
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.obs import spans as spans_mod
from llmq_tpu.parallel import make_mesh

ROOT = Path(__file__).resolve().parent.parent
CFG = ModelConfig.tiny(vocab_size=304)
PARAMS = init_params(CFG, jax.random.key(0), dtype=jnp.float32)
STEP = 0.01285  # chat's decode step on a v5e (ledger, PR 33), as an example


def make_core(**engine) -> EngineCore:
    defaults = dict(
        max_num_seqs=4, max_model_len=96, page_size=8, num_pages=40,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    defaults.update(engine)
    return EngineCore(
        CFG, PARAMS, ByteTokenizer(), mesh=make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**defaults),
    )


def greedy(max_tokens):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True)


def requests(n=7):
    """More requests than slots, prompts and outputs of several pages of 8:
    slots and pages are recycled while steps are in flight."""
    return [(f"r{i}", f"request number {i} " * (1 + i % 3), 20 + 5 * i) for i in range(n)]


def run(core, reqs, each_step=None):
    for rid, prompt, n in reqs:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = {}
    for _ in range(4000):
        for out in core.step():
            outs[out.rid] = out.token_ids
        if each_step is not None:
            each_step()
        if not core.has_work:
            break
    assert set(outs) == {r[0] for r in reqs}, "engine stalled"
    return outs


def pin_target(monkeypatch, depth):
    """The engine's target held at ``depth`` (under the cap) whatever this
    host's clocks read."""
    monkeypatch.setattr(
        engine_mod, "runahead_target",
        lambda turn_s, step_s, cap, *, pp=1, full=False: min(cap, depth),
    )


@pytest.mark.parametrize(
    "turn_s, step_s, cap, pp, want",
    [
        (0.001, STEP, 8, 1, 2),  # a turn far shorter than a step
        (0.0, STEP, 8, 1, 2),  # never below 2 while the cap allows it
        (STEP, STEP, 8, 1, 2),  # a turn of one step: one executing, one queued
        (1.01 * STEP, STEP, 8, 1, 3),
        (2.5 * STEP, STEP, 8, 1, 4),  # a turn of 2.5 steps
        (1.0, STEP, 8, 1, 8),  # never above the cap
        (2.5 * STEP, STEP, 3, 1, 3),
        (0.001, STEP, 1, 1, 1),  # a cap the OOM ladder halved to 1 holds
        (0.001, STEP, 0, 1, 0),  # no run-ahead at all, as configured
        (0.001, 0.0, 8, 1, 8),  # no step period read yet: the cap
        (0.001, STEP, 8, 2, 8),  # pipeline stages keep the cap
        (0.001, STEP, 8, 4, 8),
    ],
)
def test_the_rule(turn_s, step_s, cap, pp, want):
    assert runahead_target(turn_s, step_s, cap, pp=pp) == want
    # Every slot taken or requests waiting: the cap, whatever the clocks read.
    assert runahead_target(turn_s, step_s, cap, pp=pp, full=True) == cap


@pytest.mark.parametrize("turn_steps", [0.1, 0.9, 1.5, 2.5, 3.0, 6.2, 40.0])
@pytest.mark.parametrize("cap", [2, 4, 8])
def test_the_target_covers_the_turn(turn_steps, cap):
    """Under the cap, the steps in flight outlast the host's turn by at
    least one step period and at most two."""
    target = runahead_target(turn_steps * STEP, STEP, cap)
    assert 2 <= target <= cap
    if target < cap:
        assert 1.0 <= target - turn_steps < 2.0 or target == 2


@pytest.mark.parametrize("depth", [2, 3])
def test_greedy_tokens_do_not_depend_on_the_depth(monkeypatch, depth):
    deep = run(make_core(runahead=8), requests())
    by_cap = run(make_core(runahead=depth), requests())
    pin_target(monkeypatch, depth)
    by_target = run(make_core(runahead=8), requests())
    assert by_cap == deep
    assert by_target == deep


@pytest.mark.parametrize("depth", [2, 8])
def test_pages_cover_every_step_in_flight_at_a_page_boundary(monkeypatch, depth):
    """The page look-ahead counts the decode dispatches really in flight,
    so it follows a shallower queue: whenever a decode step is dispatched,
    every row it advances owns pages for every position the steps in
    flight can write (pages of 8, outputs of 20-50 tokens: each row
    crosses several boundaries), and the pool is whole again at the end
    (deferred frees follow the processed dispatches)."""
    pin_target(monkeypatch, depth)
    core = make_core(runahead=8)
    page = core.cfg.page_size
    push, seen = core._push_pending, []

    def checked_push(kind, out, snapshot, guard=None):
        push(kind, out, snapshot, guard)
        if kind != "decode":
            return
        for _row, seq in snapshot:
            horizon = len(seq.prompt_ids) + seq.params.max_tokens
            need = min(seq.num_tokens + core._pending_decodes, horizon)
            assert len(seq.pages) * page >= need, (seq.rid, need, len(seq.pages))
            seen.append(len(seq.pages))

    core._push_pending = checked_push
    free0 = core.scheduler.allocator.available
    outs = run(core, requests())
    assert max(seen) >= 5  # rows did cross page boundaries
    assert core.scheduler.allocator.available == free0
    assert outs == run(make_core(runahead=0), requests())  # no run-ahead at all


def test_the_queue_never_outgrows_the_target():
    core = make_core(runahead=8)
    dispatch, admit, prefill = core._dispatch_decode, core._try_admit, core._prefill_chunk
    met, in_round = [], [0]

    def checked_dispatch(finished):
        dispatch(finished)
        assert len(core._pending) <= core._ahead

    def counted_admit(finished):
        in_round[0] = 0
        return admit(finished)

    def checked_prefill(chunk, bucket):
        met.append((len(core._pending), core._ahead, in_round[0]))
        in_round[0] += 1
        prefill(chunk, bucket)

    core._dispatch_decode = checked_dispatch
    core._try_admit = counted_admit
    core._prefill_chunk = checked_prefill
    core.spans.set(True)
    targets = set()
    run(core, requests(3), each_step=lambda: targets.add(core._ahead))  # a slot stays free
    assert all(2 <= t <= 8 for t in targets) and not any(core._full)
    assert core.stats()["runahead_target"] == core._ahead
    # A step period was read, so the cap alone no longer decides.
    assert core._step_s > 0.0 and max(core._turn_worst) > 0.0
    spans = core.spans.dump()["spans"]
    decodes = [s for s in spans if s["name"] == "decode_dispatch"]
    prefills = [s for s in spans if s["name"] == "prefill_dispatch"]
    assert decodes and all(2 <= s["ahead"] <= 8 for s in decodes)
    # What a prefill met in the queue is what its span says: no more than
    # the target of that turn, and the prefills of other buckets that the
    # same admission sent before it.
    assert [s["pending"] for s in prefills] == [p for p, _, _ in met]
    assert all(p <= ahead + before for p, ahead, before in met)


def test_a_full_house_keeps_the_cap():
    """With every slot taken or requests waiting the queue stays as deep as
    the cap; once slots have been free for two buckets of turns the clocks
    decide again."""
    core = make_core(runahead=8)
    seen = set()
    run(core, requests(), each_step=lambda: seen.add(core._ahead))  # 7 on 4 slots
    assert seen == {8} and core._full[0]
    core._step_s, core._turn_worst[:] = 0.01, [0.001] * 4
    for n in range(2):
        core._turn_n = engine_mod._TURN_BUCKET - 1
        run(core, [(f"alone{n}", "one request, three free slots", 6)])
    assert core._full == [False, False]
    core._step_s, core._turn_worst[:] = 0.01, [0.001] * 4
    core.step()
    assert core._ahead == 2


def test_an_idle_engine_reads_no_turn():
    """The time an engine sat with nothing in flight is nobody's turn: the
    next request does not find the queue deepened by it."""
    core = make_core(runahead=8)
    run(core, requests(2))
    assert not core._pending and core._turn_from == 0.0
    worst = max(core._turn_worst)
    core._fetched = (core._fetched[0], core._fetched[1] - 60.0, core._fetched[2])
    run(core, [("late", "one more", 12)])
    assert max(core._turn_worst) < 30.0 and max(core._step_gaps) < 30.0
    assert worst > 0.0


def test_a_stall_that_comes_once_does_not_deepen_the_queue():
    """The host's turn is the longest that came twice in the record of the
    last 256-512 turns; the record forgets after two buckets."""
    core = make_core(runahead=8)
    run(core, requests(3))
    usual = sorted(core._turn_worst)[2]
    core._step_s, core._step_gaps = 0.01, [0.01]
    core._turn_worst[:] = [0.121, 0.004, 0.005, 0.003]  # one profile starts
    core.step()
    assert core._ahead == 2
    core._turn_worst[:] = [0.121, 0.004, 0.055, 0.003]  # the stall came again
    core.step()
    assert core._ahead == 7
    core._turn_n = engine_mod._TURN_BUCKET - 1
    run(core, [("a", "rotate the record", 6)])
    assert core._turn_worst[2:] != [0.055, 0.003] and core._turn_worst[2] >= 0.121
    core._turn_n = engine_mod._TURN_BUCKET - 1
    run(core, [("b", "and once more", 6)])
    assert max(core._turn_worst) < 0.1 and usual < 0.1


def test_the_oom_ladder_caps_the_target():
    core = make_core(runahead=8)
    run(core, requests(3))
    core._ahead = 3
    assert core.degrade_for_oom() == "shrink_runahead"
    assert core.cfg.runahead == 1  # half of what was in flight, not of 8
    run(core, [("after", "still serving", 12)])
    assert core._ahead == 1 and core.stats()["runahead_target"] == 1


def _spec(metric):
    spec = json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{metric}.json").read_text()
    )
    entry = next(
        m for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == metric
    )
    return spec, entry


def test_prefill_ahead_mean_reads_a_recorded_ring(monkeypatch):
    """The benchmark's reader on the traced chat run kept with its tests
    (the parent's engine on a v5e: 8 and more in front of every prefill)
    and on a ring this engine wrote."""
    from benchmark import span_join
    from benchmark.readers import span_stat

    spec, entry = _spec("prefill_ahead_mean")
    assert spec["reader"] == "span_stat"
    assert entry["moves"] == "ttft_p50_ms" and entry["layer"] == "engine dispatch"
    raw = json.loads(
        (ROOT / "benchmark/tests/data/spans_chat_v5e.json").read_text()
    )
    monkeypatch.setattr(span_join, "process_dump", lambda: raw)
    t0, t1 = raw["window"]
    ctx = SimpleNamespace(records=SimpleNamespace(rows=[], t0=t0, t1=t1), trace=None)
    assert 8.0 <= span_stat.read(ctx, **spec["args"]) <= 10.0

    pin_target(monkeypatch, 2)
    core = make_core(runahead=8)
    core.spans.set(True)
    run(core, requests())
    monkeypatch.setattr(span_join, "process_dump", spans_mod.dump_process)
    live = SimpleNamespace(
        records=SimpleNamespace(rows=[], t0=0.0, t1=float("inf")), trace=None
    )
    assert 0.0 <= span_stat.read(live, **spec["args"]) <= 2.0
