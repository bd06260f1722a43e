"""The reduction from trace events to numbers, on events worked by hand
and on a small trace recorded on the chip (``data/``)."""

import json
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

P0, P1 = "/device:TPU:0", "/device:TPU:1"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE


def ev(plane, line, name, start_us, dur_us):
    return tr.Event(plane, line, name, start_us * 1000, dur_us * 1000)


def hand_trace():
    """Two decode steps and one prefill on chip 0, the same on chip 1.
    A decode step: while(100 us) holding attention (60) and fusion (30),
    then an all-reduce (20) of which 10 us overlap nothing."""
    events = []
    for plane in (P0, P1):
        for k, t in enumerate((0, 200)):
            events += [
                ev(plane, MODS, f"jit_decode_step({7 + k})", t, 130),
                ev(plane, OPS, "while.1", t, 100),
                ev(plane, OPS, "paged_decode_attention_pallas.9", t + 5, 60),
                ev(plane, OPS, "fusion.3", t + 65, 30),
                ev(plane, OPS, "all-reduce.2", t + 100, 20),
                ev(plane, OPS, "copy.1", t + 110, 20),
            ]
        events += [
            ev(plane, MODS, "jit_prefill_step(99)", 400, 300),
            ev(plane, OPS, "fusion.8", 400, 300),
        ]
    return events


def test_union_merges_overlaps_and_nesting():
    assert tr.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == [(0, 20), (30, 40)]


def test_busy_is_the_union_of_the_ops_line_mean_over_chips():
    # per chip: [0,130) + [200,330) + [400,700) = 560 us
    assert tr.busy_seconds(hand_trace()) == pytest.approx(560e-6)


def test_self_time_takes_nested_operations_out():
    ops = sorted(
        (e for e in hand_trace() if e.plane == P0 and e.line == OPS),
        key=lambda e: (e.start_ns, -e.dur_ns),
    )
    own = {(e.name, e.start_ns): t for e, t in tr.self_times(ops)}
    assert own[("while.1", 0)] == 10_000  # 100 - 60 - 30
    assert own[("paged_decode_attention_pallas.9", 5_000)] == 60_000


def test_program_medians_and_shares():
    events = hand_trace()
    assert tr.median_run_ms(events, r"^jit_decode_step$") == pytest.approx(0.130)
    assert tr.program_share_pct(events, r"^jit_prefill_step$") == pytest.approx(
        100 * 300 / 560
    )
    assert tr.op_ms_per_run(
        events, r"^jit_decode_step$", "paged_decode_attention"
    ) == pytest.approx(0.060)
    assert tr.median_run_ms(events, r"^jit_nothing$") is None


def test_exposed_collective_is_what_nothing_else_covers():
    # all-reduce [100,120) against copy [110,130): 10 us exposed per step
    assert tr.exposed_collective_pct(hand_trace()) == pytest.approx(100 * 20 / 560)


def test_breakdown_names_ops_by_program_and_gaps_by_what_came_next():
    b = tr.breakdown(hand_trace())
    ops = dict(map(tuple, b["device_ops"]))
    assert ops["jit_prefill_step/fusion.8"] == pytest.approx(300e-6)
    assert ops["jit_decode_step/paged_decode_attention_pallas.9"] == pytest.approx(120e-6)
    gaps = dict(map(tuple, b["idle_gaps"]))
    assert gaps["before_jit_decode_step"] == pytest.approx(70e-6)
    assert gaps["before_jit_prefill_step"] == pytest.approx(70e-6)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


RECORDED = Path(__file__).parent / "data" / "decode_long_v5e.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_reduces_to_its_known_numbers():
    doc = json.loads(RECORDED.read_text())
    events = tr.events_from_json(doc["events"])
    want = doc["expect"]
    assert tr.busy_seconds(events) == pytest.approx(want["busy_s"], rel=1e-6)
    assert tr.median_run_ms(events, r"^jit_decode_step$") == pytest.approx(
        want["decode_step_dev_ms"], rel=1e-6
    )
    assert tr.op_ms_per_run(
        events, r"^jit_decode_step$", "paged_decode_attention"
    ) == pytest.approx(want["decode_attn_ms"], rel=1e-6)
    # the kernel runs once a layer inside the step, so it cannot exceed it
    assert want["decode_attn_ms"] < want["decode_step_dev_ms"]
