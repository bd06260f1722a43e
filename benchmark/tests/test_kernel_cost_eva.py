"""The bytes and operations of EVA decode attention against shapes worked
by hand, its own row arithmetic, and its two readers on the recorded chat
run (``data/spans_chat_v5e.json``), whose program has neither the scope
nor the fields: nothing to read there, as at a parent commit."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import kernel_cost, kernel_cost_eva, span_join, trace_reduce
from benchmark.readers import decode_eva_roofline, span_field_share

EVABYTE = dict(hidden=4096, heads=32, head_dim=128)
PUBLISHED = dict(window=2048, chunk=16)
DATA = Path(__file__).parent / "data" / "spans_chat_v5e.json"
CONFIG = Path(kernel_cost_eva.__file__).parent / "configs" / "evabyte-6.5b-pp4.json"


def test_one_row_one_sequence_by_hand():
    # One layer, one attended row, one sequence:
    # cache: K and V, 32 heads x 128 values x 2 B each = 16,384 B
    # W_q, W_k, W_v: 3 x 4,096 x 4,096 = 50,331,648 values x 2 B
    # the sequence's hidden in and heads' outputs out: (4,096 + 4,096) x 2 B
    kw = dict(attended=1, rows=1, layers=1, **EVABYTE)
    assert kernel_cost_eva.eva_decode_bytes(**kw) == 16_384 + 100_663_296 + 16_384
    # attention: 4 x 32 heads x 128 = 16,384; the row through the matrices
    assert kernel_cost_eva.eva_decode_flops(**kw) == 16_384 + 100_663_296


def test_attended_rows_are_summaries_of_earlier_windows_and_the_own_window():
    rows = kernel_cost_eva.attended_rows
    assert rows(0, **PUBLISHED) == 0 and rows(1, **PUBLISHED) == 1
    assert rows(2048, **PUBLISHED) == 2048  # the window's last byte sees it whole
    assert rows(2049, **PUBLISHED) == 128 + 1  # the next one its summaries
    assert rows(9200, **PUBLISHED) == 4 * 128 + (9200 - 1 - 8192) + 1
    assert rows(70, window=32, chunk=4) == 2 * 8 + 6
    # brute force: one summary a chunk of every complete window before the
    # query's, and the positions of its own window up to itself
    for n in range(1, 200):
        last = n - 1
        want = sum(1 for c in range(0, last // 32 * 32, 4)) + last % 32 + 1
        assert rows(n, window=32, chunk=4) == want


def test_at_the_cell_size_the_bytes_are_the_bound():
    # 24 sequences of 1,535.5 attended rows (a row a position would be
    # 9,200), 8 layers: 4.83 GB of keys and values + 0.81 GB of matrices:
    # 6.88 ms at 819 GB/s; 24.2 GFLOP: 0.12 ms.
    attended = 24 * 1535.5
    kw = dict(attended=attended, rows=24, layers=8, **EVABYTE)
    peaks = kernel_cost.peaks_for("TPU v5 lite")
    nbytes = kernel_cost_eva.eva_decode_bytes(**kw)
    flops = kernel_cost_eva.eva_decode_flops(**kw)
    assert nbytes == 8 * (attended * 16_384 + 100_663_296 + 24 * 16_384)
    assert 1e3 * nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(6.885, rel=1e-3)
    assert 1e3 * flops / peaks["bf16_flops_per_s"] < 0.25
    assert kernel_cost.roofline_ms(flops, nbytes, peaks) == pytest.approx(6.885, rel=1e-3)


def _recorded(monkeypatch, spans=None):
    raw = json.loads(DATA.read_text())
    dump = {k: raw[k] for k in ("spans", "requests", "counters", "scopes", "loop_lag")}
    if spans is not None:
        dump["spans"] = spans(dump["spans"])
    monkeypatch.setattr(span_join, "process_dump", lambda: dump)
    t0, t1 = raw["window"]
    return SimpleNamespace(
        records=SimpleNamespace(rows=raw["rows"], t0=t0, t1=t1),
        trace=trace_reduce.events_from_json(raw["events"]),
        model=json.loads(CONFIG.read_text()),
        peaks=kernel_cost.peaks_for("TPU v5 lite"),
    )


def test_a_program_without_the_scope_or_the_fields_gives_nothing_to_read(monkeypatch):
    ctx = _recorded(monkeypatch)
    args = dict(name="decode_dispatch", field="summary_rows", of=["summary_rows", "window_rows"])
    assert span_field_share.read(ctx, **args) is None
    assert decode_eva_roofline.read(
        ctx, program="jit_decode_step", scope="llmq.attn.eva_decode"
    ) is None
    empty = SimpleNamespace(_span_join=False, peaks={})
    assert decode_eva_roofline.read(empty, program="jit_decode_step", scope="x") is None
    assert span_field_share.read(empty, **args) is None


def test_the_readers_on_the_recorded_run_with_the_fields_and_the_scope(monkeypatch):
    def with_rows(spans):
        out = []
        for s in spans:
            if s["name"] == "decode_dispatch":
                s = dict(s, summary_rows=512 * s["rows"], window_rows=1023.5 * s["rows"])
            out.append(s)
        return out

    ctx = _recorded(monkeypatch, with_rows)
    share = span_field_share.read(
        ctx, name="decode_dispatch", field="summary_rows", of=["summary_rows", "window_rows"]
    )
    assert share == pytest.approx(100 * 512 / 1535.5)
    monkeypatch.setattr(span_join, "scope_ms_per_run", lambda j, program, scope: 40.0)
    roofline = decode_eva_roofline.read(
        ctx, program="jit_decode_step", scope="llmq.attn.eva_decode"
    )
    # the recorded run's dispatches hold about 90 rows on average, not 24
    steps = [
        s["rows"] for s in span_join.load(ctx).spans
        if s["name"] == "decode_dispatch" and span_join.in_window(ctx, s["t0_ns"])
    ]
    rows = sum(steps) / len(steps)
    least = 1e3 * 8 * (rows * 1535.5 * 16_384 + 100_663_296 + rows * 16_384) / 819e9
    assert roofline == pytest.approx(100 * least / 40.0, rel=1e-6)
