"""The span readers on a cut of one traced chat run on the chip
(``data/spans_chat_v5e.json``: the program's dump, the device events of
the first plane, the host plane's annotations), and on hand-worked rows."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import span_join, trace_reduce
from benchmark.readers import (
    loop_lag,
    scope_per_run,
    span_device,
    span_request,
    span_share,
    span_stat,
)

DATA = Path(__file__).parent / "data" / "spans_chat_v5e.json"


def _context(monkeypatch, dump, **kw):
    """A reader's context, with ``dump`` as what the process recorded."""
    monkeypatch.setattr(span_join, "process_dump", lambda: dump)
    return SimpleNamespace(**kw)


@pytest.fixture()
def ctx(monkeypatch):
    raw = json.loads(DATA.read_text())
    t0, t1 = raw["window"]
    return _context(
        monkeypatch,
        {k: raw[k] for k in ("spans", "requests", "counters", "scopes", "loop_lag")},
        records=SimpleNamespace(rows=raw["rows"], t0=t0, t1=t1),
        trace=trace_reduce.events_from_json(raw["events"]),
        raw=raw,
    )


def test_every_dispatch_finds_its_run_and_the_clocks_agree(ctx, capsys):
    j = span_join.load(ctx)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["line"] == "spans" and line["clock"]["pairs"] == len(j.pairs)
    assert j.shift is not None and len(j.pairs) >= 20
    for d, run in j.pairs:
        assert trace_reduce.program_name(run.name) == "jit_" + d["program"]
    # The anchored offset against the host plane's annotations of the
    # same profile (each one sample of monotonic - profiler): later than
    # their median by the shortest time from a program's end on the
    # device to its result on the host (2.0 ms here).
    deltas = sorted(e["t_mono_ns"] - e["start_ns"] for e in ctx.raw["host"]
                    if e.get("t_mono_ns"))
    fitted = deltas[len(deltas) // 2]
    assert max(abs(d - fitted) for d in deltas) / 1e6 < 0.2
    assert 0.0 < (j.clock["offset_ns"] - fitted) / 1e6 < 3.0
    # So the smallest fetch lag reads 0: a lag is "above the smallest".
    assert min(span_join.fetch_lag_ms(j, "prefill_dispatch")
               + span_join.fetch_lag_ms(j, "decode_dispatch")) == 0.0
    # Causality on the joined clock: no run starts before its dispatch,
    # no fetch ends before its run.
    assert min(span_join.queue_ms(j, "prefill_dispatch") + span_join.queue_ms(j, "decode_dispatch")) > -1.0
    assert min(span_join.fetch_lag_ms(j, "decode_dispatch")) >= 0.0


def test_readers_on_the_recorded_run(ctx):
    queue = span_device.read(ctx, which="queue", name="prefill_dispatch", q=50)
    lag = span_device.read(ctx, which="fetch_lag", name="prefill_dispatch", q=50)
    lag_decode = span_device.read(ctx, which="fetch_lag", name="decode_dispatch", q=50)
    assert queue > 0 and lag >= 0 and 0 <= lag_decode < 50
    dev = span_device.read(ctx, which="device", name="prefill_dispatch", q=50)
    # queue + run + lag is the prefill_start -> first_token of the same
    # requests, to the millisecond: the three pieces leave nothing over.
    rows = {r["rid"]: r for r in ctx.records.rows}
    j = span_join.load(ctx)
    for d, run in j.pairs:
        if d["name"] != "prefill_dispatch" or d["id"] not in j.fetch_of:
            continue
        whole = (j.fetch_of[d["id"]]["t1_ns"] - d["t0_ns"]) / 1e6
        parts = (
            (run.start_ns + j.clock["offset_ns"] - d["t0_ns"])
            + run.dur_ns
            + (j.fetch_of[d["id"]]["t1_ns"] - run.end_ns - j.clock["offset_ns"])
        ) / 1e6
        assert parts == pytest.approx(whole, abs=1e-6)
        r = rows.get(d["rids"][0])
        if r and r.get("first_token") and r.get("prefill_start"):
            stamped = (r["first_token"] - r["prefill_start"]) * 1e3
            assert whole == pytest.approx(stamped, abs=5.0)
    # Nearly every run in the trace was launched by a span in the ring, so
    # the joined runs' median is the program's own.
    assert dev == pytest.approx(
        trace_reduce.median_run_ms(ctx.trace, "^jit_prefill_step$"), rel=0.2
    )
    assert span_request.read(ctx, start="sent", end="claimed", q=95) >= 0
    assert span_request.read(ctx, start="engine_submit", end="enqueued", q=95) >= 0
    assert 0 <= loop_lag.read(ctx) <= ctx.raw["loop_lag"]["max_ms"]
    rows_mean = span_stat.read(ctx, name="prefill_dispatch", field="rows")
    assert 1.0 <= rows_mean <= 4.0
    share = span_share.read(ctx, name="admit_hold")
    assert 0.0 <= share <= 100.0
    mlp = scope_per_run.read(ctx, program="jit_decode_step", scope="llmq.mlp")
    step = trace_reduce.median_run_ms(ctx.trace, "^jit_decode_step$")
    assert 0 < mlp < step


def test_device_time_has_a_scope(ctx):
    j = span_join.load(ctx)
    scopes = span_join.device_scopes(j)
    named = sum(v for k, v in scopes.items() if k.startswith("llmq."))
    assert named / sum(scopes.values()) >= 0.95
    gaps, unnamed = span_join.idle_gaps(j)
    assert gaps and unnamed == 0  # every gap above 50 us has a host span's name


def test_no_ring_no_metric(monkeypatch):
    """On a program without the ring (or with it off) every reader
    returns None and does not raise."""
    monkeypatch.setattr(span_join, "process_dump", lambda: None)
    bare = SimpleNamespace(records=SimpleNamespace(rows=[], t0=0.0, t1=1.0), trace=None)
    assert span_request.read(bare, start="sent", end="claimed", q=95) is None
    assert loop_lag.read(bare) is None
    assert span_stat.read(bare, name="prefill_dispatch", field="rows") is None
    assert span_share.read(bare, name="admit_hold") is None
    assert span_device.read(bare, which="queue", name="prefill_dispatch", q=50) is None
    assert scope_per_run.read(bare, program="jit_decode_step", scope="llmq.mlp") is None


# --- hand-worked ------------------------------------------------------------------


def _d(i, name, program, seq, t0):
    return {"id": i, "name": name, "program": program, "seq": seq, "ring": "engine",
            "t0_ns": t0, "t1_ns": t0 + 10, "cause": 0, "rows": 1}


def _run(name, start, dur):
    return trace_reduce.Event("/device:TPU:0", trace_reduce.MODULES_LINE, name, start, dur)


def test_match_finds_the_one_shift():
    kinds = "DDPDDDPDDDDP"
    runs = [_run("jit_decode_step(1)" if k == "D" else "jit_prefill_step(2)", i * 100, 90)
            for i, k in enumerate("DD" + kinds)]  # two runs from before the ring
    dispatches = [
        _d(i, "decode_dispatch" if k == "D" else "prefill_dispatch",
           "decode_step" if k == "D" else "prefill_step", i, i * 100)
        for i, k in enumerate(kinds + "DD")  # two not yet run at the trace's end
    ]
    assert span_join.match(dispatches, runs) == (2, False)
    assert span_join.match(dispatches[:3], [_run("jit_prefill_step(2)", 0, 1)] * 9)[0] is None


def test_a_ring_on_for_the_whole_run_is_cut_to_the_profiled_stretch(monkeypatch):
    """Hundreds of dispatches before the profile began: only those of the
    ``profile`` span (and the run-ahead before it) are matched."""
    kinds = "DDPDDDPDDDDPDD"
    early = [_d(i, "decode_dispatch", "decode_step", i, i * 1000) for i in range(300)]
    t_prof = 10 * span_join.RUN_AHEAD_NS
    late = [
        _d(1000 + i, "decode_dispatch" if k == "D" else "prefill_dispatch",
           "decode_step" if k == "D" else "prefill_step", 300 + i, t_prof + i * 1000)
        for i, k in enumerate(kinds)
    ]
    fetches = [
        {"id": 5000 + d["id"], "name": "fetch", "ring": "engine", "cause": d["id"],
         "t0_ns": d["t0_ns"] + 20, "t1_ns": d["t0_ns"] + 900}
        for d in late
    ]
    mark = {"id": 9000, "name": "profile", "ring": "engine", "cause": 0,
            "t0_ns": t_prof - 5, "t1_ns": t_prof + 10**6}
    turn = {"id": 9001, "name": "turn", "ring": "engine", "cause": 0,
            "t0_ns": 0, "t1_ns": t_prof + 10**6}
    runs = [_run("jit_decode_step(1)" if k == "D" else "jit_prefill_step(2)", i * 1000, 800)
            for i, k in enumerate(kinds)]
    c = _context(
        monkeypatch,
        {"spans": early + late + fetches + [mark, turn], "requests": {}, "counters": {}},
        records=SimpleNamespace(rows=[], t0=0.0, t1=1e9), trace=runs,
    )
    j = span_join._join(c)
    assert j.shift == 0 and [d["id"] for d, _ in j.pairs] == [d["id"] for d in late]
    assert j.clock["offset_ns"] == t_prof + 100


def test_anchor_takes_the_smallest_lag_and_reports_the_spread():
    pairs, fetch_of = [], {}
    for i in range(40):
        d = _d(i + 1, "decode_dispatch", "decode_step", i, 0)
        run = _run("jit_decode_step(1)", i * 1000, 900)
        lag = 5_000 + (i % 7) * 40 + (300_000 if i % 5 == 0 else 0)
        pairs.append((d, run))
        fetch_of[d["id"]] = {"t1_ns": run.end_ns + 1_000_000 + lag}
    clock = span_join.anchor_clock(pairs, fetch_of)
    assert clock["offset_ns"] == 1_005_000
    assert clock["residual_ms"] == pytest.approx(0.00004, abs=1e-9)
    assert span_join.anchor_clock(pairs[:5], fetch_of) is None


def test_share_is_the_union_clipped_to_what_the_ring_covered(monkeypatch):
    spans = [
        {"id": 1, "name": "turn", "ring": "engine", "t0_ns": 100, "t1_ns": 600, "cause": 0},
        {"id": 2, "name": "turn", "ring": "engine", "t0_ns": 600, "t1_ns": 1100, "cause": 0},
        {"id": 3, "name": "admit_hold", "ring": "engine", "t0_ns": 0, "t1_ns": 300, "cause": 0},
        {"id": 4, "name": "admit_hold", "ring": "engine", "t0_ns": 250, "t1_ns": 400, "cause": 0},
        {"id": 5, "name": "admit_hold", "ring": "engine", "t0_ns": 900, "t1_ns": 5000, "cause": 0},
    ]
    c = _context(
        monkeypatch, {"spans": spans, "requests": {}, "counters": {}},
        records=SimpleNamespace(rows=[], t0=0.0, t1=1e-6), trace=None,
    )
    # covered 100..1100 clipped to the window's end at 1000: 900 ns, of
    # which 100..400 and 900..1000 are held.
    assert span_share.read(c, name="admit_hold") == pytest.approx(100 * 400 / 900)


def test_loop_lag_is_the_latest_tick_inside_the_window(monkeypatch):
    def c(lag):
        return _context(
            monkeypatch, {"spans": [], "requests": {}, "counters": {}, "loop_lag": lag},
            records=SimpleNamespace(rows=[], t0=10.0, t1=50.0), trace=None,
        )

    late = [[5.0, 900.0], [12.0, 31.5], [30.0, 1678.0], [49.9, 22.0], [50.5, 2400.0]]
    mark = {"ticks": 600, "max_ms": 2400.0, "late_total": 5, "late": late}
    assert loop_lag.read(c(mark)) == 1678.0  # set-up's and the tail's are outside
    assert loop_lag.read(c(dict(mark, late=late[:1]))) == 0.0  # none inside: 0, not nothing
    assert loop_lag.read(c(None)) is None  # a program without the mark


def test_an_unmatched_run_is_scoped_by_the_variant_that_holds_its_operations(monkeypatch):
    """A run launched before the ring was on has no dispatch span to name
    its variant; each variant numbers its instructions its own way, so
    the one whose compiled text holds the run's operations is the one."""
    ops = trace_reduce.OPS_LINE

    def op(name, start, dur):
        return trace_reduce.Event("/device:TPU:0", ops, name, start, dur)

    events = [
        _run("jit_prefill_step(2)", 0, 100), op("fusion.7", 0, 60), op("fusion.9", 60, 40),
        _run("jit_prefill_step(2)", 200, 100), op("fusion.7", 200, 30), op("fusion.8", 230, 70),
    ]
    scopes = {"prefill_step": {
        "greedy/1x128": {"fusion.7": "llmq.mlp", "fusion.9": "llmq.qkv"},
        "greedy/4x128": {"fusion.7": "llmq.attn.xla", "fusion.8": "llmq.mlp"},
    }}
    c = _context(
        monkeypatch,
        {"spans": [{"id": 1, "name": "turn", "ring": "engine", "t0_ns": 0, "t1_ns": 9,
                    "cause": 0}],
         "requests": {}, "counters": {}, "scopes": scopes},
        records=SimpleNamespace(rows=[], t0=0.0, t1=1.0), trace=events,
    )
    got = span_join.device_scopes(span_join._join(c))
    assert got == pytest.approx(
        {"llmq.mlp": 130e-9, "llmq.qkv": 40e-9, "llmq.attn.xla": 30e-9}
    )
