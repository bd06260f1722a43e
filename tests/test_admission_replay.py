"""The benchmark's own queues replayed through the scheduler's real choice of
an admission wave, with no device: how many rows a prefill program gets and
how much of its grid is prompt. Pins the table under ISSUE 36's Motivation
(PERF.md section 6, PR 36), so that a change to the bucket ladder or to the
rule shows here before it shows on a chip."""

from pathlib import Path

import pytest

from benchmark.schedule import load_traffic, make_schedule
from llmq_tpu.core.config import Config
from llmq_tpu.engine.engine import EngineConfig, _prefill_buckets
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.scheduler import Scheduler, SchedulerConfig, Sequence
from llmq_tpu.models.cache import cache_layout
from llmq_tpu.models.presets import get_preset

ROOT = Path(__file__).resolve().parents[1]
RUN_SECONDS = 40  # BENCHMARK.json's run_seconds: the size of a fixed job

# cell -> (traffic, max_model_len, slots), as the cell's configuration sets them
CELLS = {
    "qwen2.5-3b.drain": ("drain", 8192, 128),
    "qwen2.5-7b-tp4.docqa": ("docqa", 4096, 64),
}


def replay(traffic, max_model_len, slots, grouped):
    """Waves of ``max_prefill_batch`` from a waiting queue kept as deep as a
    worker keeps it (its prefetch less its slots), each wave run as the
    engine runs it: a program a bucket, of one row or of four."""
    cfg = EngineConfig(max_model_len=max_model_len)
    buckets = _prefill_buckets(cfg)
    requests = iter(make_schedule(load_traffic(
        ROOT / "benchmark" / "traffic" / f"{traffic}.json"), RUN_SECONDS))
    depth = max(Config().queue_prefetch, slots + slots // 2) - slots
    sched = Scheduler(
        SchedulerConfig(
            max_num_seqs=cfg.max_prefill_batch, num_pages=4096, page_size=128,
            max_model_len=max_model_len,
        ),
        cache_layout(
            get_preset("tiny"), page_size=128, max_model_len=max_model_len,
            max_num_seqs=cfg.max_prefill_batch,
        ),
    )

    def bucket_of(seq):
        return next(b for b in buckets if b >= seq.num_tokens)

    programs = rows = grid = prompt = 0
    while True:
        for r in requests:
            sched.add(Sequence(
                rid=str(r.index), prompt_ids=[1] * r.prompt_tokens,
                params=SamplingParams(max_tokens=r.output_tokens),
            ))
            if len(sched.waiting) >= depth:
                break
        if not sched.waiting:
            break
        wave = sched.admit(cfg.max_prefill_batch, bucket_of if grouped else None)
        by_bucket = {}
        for seq in wave:
            by_bucket.setdefault(bucket_of(seq), []).append(seq)
            sched.finish(seq, "stop")
        for bucket, group in by_bucket.items():
            programs += 1
            rows += len(group)
            grid += (1 if len(group) == 1 else cfg.max_prefill_batch) * bucket
            prompt += sum(s.num_tokens for s in group)
    return {"programs": programs, "rows": rows / programs, "grid": grid,
            "prompt": prompt, "ratio": grid / prompt}


@pytest.mark.parametrize("cell", CELLS)
def test_fifo_waves_are_what_the_ledger_read(cell):
    """The replay is believed because FIFO reproduces what the chip showed:
    `prefill_rows_mean.closed` 1.16-1.19 on drain and 1.217 on docqa
    (ledger, PRs 30-35)."""
    got = replay(*CELLS[cell], grouped=False)
    want = {
        "qwen2.5-3b.drain": (474, 1.181, 171_168, 128_714),
        "qwen2.5-7b-tp4.docqa": (196, 1.224, 808_960, 583_680),
    }[cell]
    assert (got["programs"], round(got["rows"], 3), got["grid"], got["prompt"]) == want


@pytest.mark.parametrize("cell", CELLS)
def test_grouped_waves_fill_their_programs(cell):
    fifo = replay(*CELLS[cell], grouped=False)
    got = replay(*CELLS[cell], grouped=True)
    assert got["prompt"] == fifo["prompt"]  # the same work
    assert got["rows"] >= 3.3
    assert got["ratio"] <= 1.22 < fifo["ratio"]
    assert got["programs"] * 2.8 <= fifo["programs"]
