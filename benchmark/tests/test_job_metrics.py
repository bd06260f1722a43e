"""A fixed job's throughput is ``job_tok_s``, to the digit the number the
parent of PR 56 called ``out_tok_s`` there, and a closed loop keeps
``out_tok_s``; the ``prefill_programs.job`` reader on a made-up log."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import metrics, schedule
from benchmark.readers import prefill_log_count

HERE = Path(__file__).resolve().parent
RECORDED = json.loads((HERE / "data" / "job_runs.json").read_text())["runs"]


def parent_fixed_job_out_tok_s(records) -> float:
    """``metrics.end_to_end`` at the parent (commit 0c1dccd), ``fixed_job``
    branch, kept word for word."""
    d = records.drive
    tokens = sum(
        r["completion_tokens"] or 0 for r in records.rows if r["received"]
    )
    return tokens / d.job_seconds if d.job_seconds else None


def job_records(traffic_name: str, seconds: float, job_seconds: float):
    """The records of a finished job of that traffic: every request
    received with the output length its schedule gave it (``ignore_eos``)."""
    traffic = schedule.load_traffic(HERE.parent / "traffic" / f"{traffic_name}.json")
    rows = [
        {"completion_tokens": r.output_tokens, "received": 1.0}
        for r in schedule.make_schedule(traffic, seconds)
    ]
    return SimpleNamespace(rows=rows, drive=SimpleNamespace(job_seconds=job_seconds))


@pytest.mark.parametrize("run", RECORDED, ids=lambda r: f"{r['workload']}-{r['seed']}")
def test_a_recorded_job_reads_the_same_number_under_the_new_name(run):
    records = job_records(run["traffic"], run["seconds"], run["window_s"])
    out = metrics.end_to_end("fixed_job", records, setup_s=run["setup_s"])
    assert set(out) == {"setup_s", "job_tok_s"}
    assert out["job_tok_s"] == parent_fixed_job_out_tok_s(records)
    # and it is the number that run printed on the chip, to the last digit
    assert out["job_tok_s"] == run["reported"], run["reported_as"]


def test_a_job_with_requests_unfinished_counts_what_was_received():
    rows = [{"completion_tokens": 64, "received": 3.0}] * 5 + [{"completion_tokens": 17, "received": None}]
    records = SimpleNamespace(rows=rows, drive=SimpleNamespace(job_seconds=2.5))
    out = metrics.end_to_end("fixed_job", records, setup_s=1.0)
    assert out == {"setup_s": 1.0, "job_tok_s": 128.0}
    assert "out_tok_s" not in out


def test_a_closed_loop_and_an_open_loop_keep_out_tok_s_and_have_no_job_tok_s():
    drive = SimpleNamespace(stats0={"generated_tokens": 1000}, stats1={"generated_tokens": 241000})
    records = SimpleNamespace(drive=drive, t0=10.0, t1=50.0, tpot_ms=lambda: [20.0, 21.0, 22.0],
                              ttft_ms=lambda: [50.0, 60.0])
    closed = metrics.end_to_end("closed_loop", records, setup_s=2.0)
    assert closed == {"setup_s": 2.0, "tpot_p50_ms": 21.0, "out_tok_s": 6000.0}
    opened = metrics.end_to_end("open_loop", records, setup_s=2.0)
    assert opened["ttft_p50_ms"] == 55.0 and opened["out_tok_s"] == 6000.0
    assert "job_tok_s" not in closed and "job_tok_s" not in opened


def test_prefill_programs_counts_the_jobs_dispatches_and_nothing_else():
    records = SimpleNamespace(t0=100.0, t1=135.0)
    log = [(99.5, 4, 4, 512), (100.0, 4, 4, 4096), (101.2, 3, 4, 4096), (134.999, 1, 1, 1024),
           (135.0, 1, 1, 64), (140.0, 2, 4, 64)]
    ctx = SimpleNamespace(records=records, prefill_log=log)
    assert prefill_log_count.read(ctx) == 3.0
    # nothing to read: no log kept, an empty one, or none of it inside the window
    assert prefill_log_count.read(SimpleNamespace(records=records)) is None
    assert prefill_log_count.read(SimpleNamespace(records=records, prefill_log=[])) is None
    assert prefill_log_count.read(SimpleNamespace(records=records, prefill_log=log[-2:])) is None
    spec = json.loads((HERE.parent / "layer_metrics" / "prefill_programs.job.json").read_text())
    assert spec["reader"] == "prefill_log_count" and "args" not in spec
