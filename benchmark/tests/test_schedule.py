"""The schedule decides the tail: it must not move with ``--seed``."""

import json
from pathlib import Path
from statistics import median

import pytest

from benchmark import schedule

TRAFFIC = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_same_schedule_every_time(path):
    spec = schedule.load_traffic(path)
    a = schedule.make_schedule(spec, 40)
    b = schedule.make_schedule(json.loads(json.dumps(spec)), 40)
    assert a == b
    other = schedule.make_schedule(dict(spec, schedule_seed=spec["schedule_seed"] + 1), 40)
    assert sorted(r.prompt_tokens for r in other) == sorted(r.prompt_tokens for r in a)
    if len(a) > 8:
        assert [r.prompt_tokens for r in other] != [r.prompt_tokens for r in a]


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_seed_changes_tokens_not_sizes(path):
    spec = schedule.load_traffic(path)
    req = schedule.make_schedule(spec, 40)[3]
    one = schedule.prompt_text(11, req.index, req.prompt_tokens)
    two = schedule.prompt_text(3_000_000_019, req.index, req.prompt_tokens)
    assert len(one) == len(two) == len(one.encode()) == req.prompt_tokens
    assert one != two and one == schedule.prompt_text(11, req.index, req.prompt_tokens)
    assert "{" not in one and "}" not in one


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_lengths_have_the_stated_medians_and_clips(path):
    spec = schedule.load_traffic(path)
    reqs = schedule.make_schedule(spec, 40)
    for key, got in (
        ("prompt_tokens", [r.prompt_tokens for r in reqs]),
        ("output_tokens", [r.output_tokens for r in reqs]),
    ):
        d = spec[key]
        assert min(got) >= d["min"] and max(got) <= d["max"]
        want = {
            "lognormal": d.get("median"),
            "uniform": (d["min"] + d["max"]) / 2,
            "fixed": d.get("value"),
        }[d["dist"]]
        assert abs(median(got) - want) <= 0.03 * want + 1


def test_open_loop_rate_and_order():
    spec = schedule.load_traffic(next(p for p in TRAFFIC if p.stem == "chat-steady"))
    reqs = schedule.make_schedule(spec, 40)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    span = spec["warm_seconds"] + 40 + spec["tail_seconds"]
    assert abs(len(reqs) / dues[-1] - spec["rate_rps"]) < 0.02 * spec["rate_rps"]
    assert abs(dues[-1] - span) < 0.05 * span
    in_window = [r for r in reqs if spec["warm_seconds"] <= r.due_s < spec["warm_seconds"] + 40]
    assert len(in_window) >= 300  # ttft_p95_ms rests on some hundreds


def test_closed_loop_clients_and_stagger():
    spec = schedule.load_traffic(next(p for p in TRAFFIC if p.stem == "decode-long"))
    reqs = schedule.make_schedule(spec, 40)
    firsts = [r for r in reqs if r.due_s is not None]
    assert len(firsts) == spec["clients"] == len({r.client for r in reqs})
    assert max(r.due_s for r in firsts) < spec["stagger_seconds"]
    assert len(reqs) == spec["clients"] * spec["requests_per_client"]


def test_fixed_job_scales_with_seconds():
    spec = schedule.load_traffic(next(p for p in TRAFFIC if p.stem == "drain"))
    assert len(schedule.make_schedule(spec, 40)) == round(spec["jobs_per_second"] * 40)
    assert len(schedule.make_schedule(spec, 10)) == round(spec["jobs_per_second"] * 10)
