"""What ``run.py`` and ``calibrate.py`` share: finding a cell's files by
name, the device check, the CPU rehearsal's shrinking, and the warm-up of
every prefill shape a schedule can reach."""

from __future__ import annotations

import asyncio
import json
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_cell(name: str) -> SimpleNamespace:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / config_entry["file"]).read_text())
    traffic_file = HERE / "traffic" / f"{cell['traffic']}.json"

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return SimpleNamespace(
        name=name,
        chips=int(cell["chips"]),
        config=config,
        config_name=config_entry["name"],
        traffic_file=traffic_file,
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=[m for m in bench["per_layer"] if listed(m)],
    )


def apply_rehearsal(cell: SimpleNamespace, traffic: dict) -> None:
    """Shrink the cell to ``preset://tiny`` sizes (see rehearsal.json): the
    configuration, whatever its architecture, is replaced by the tiny
    dense model, which names none, and is held to ``limits.json``."""
    r = json.loads((HERE / "rehearsal.json").read_text())
    cell.config_name = "rehearsal"
    cell.config = dict(r["model"], program_model=r["program_model"], env={},
                       engine=dict(cell.config["engine"], **r["engine"]))
    div = r["length_divisor"]
    for key, floor in (("prompt_tokens", r["min_prompt"]), ("output_tokens", r["min_output"])):
        d = traffic[key]
        for f in ("median", "min", "max", "value"):
            if f in d:
                d[f] = max(floor, int(d[f]) // div)
    traffic["check_lengths"] = [max(r["min_prompt"], n // div) for n in traffic["check_lengths"]]
    for key in ("clients", "rate_rps", "jobs_per_second", "warm_seconds", "stagger_seconds"):
        if key in traffic:
            traffic[key] = r[key]
    traffic["trace_offset_s"] = 0.5
    traffic["trace_seconds"] = 1


def device_facts(chips: int, rehearse: bool) -> dict:
    import jax

    if rehearse:
        if (jax.config.jax_platforms or "").strip().lower() != "cpu":
            raise SystemExit("--rehearse-cpu needs JAX_PLATFORMS=cpu")
    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and platform != "tpu":
        raise SystemExit(f"no accelerator: JAX came up on {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s), JAX sees {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind, "count": chips}


def memory_peak(chips: int):
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()[:chips]
    ]
    peaks = [p for p in peaks if p]
    return max(peaks) if peaks else None


async def warm_shapes(system, schedule) -> dict:
    """Run every (prefill rows, bucket) pair the schedule can reach, and the
    decode step, once: batch composition depends on timing, so the shapes
    the last run happened to hit are not enough. A preempted sequence is
    prefilled again at prompt plus output length, so buckets up to the
    longest total are warmed too."""
    from benchmark.correct import serve_greedy

    core = system.core
    buckets = core._buckets
    top = core.cfg.max_model_len
    lo = next(b for b in buckets if b >= min(r.prompt_tokens for r in schedule))
    hi = next(
        b for b in buckets
        if b >= min(top, max(r.prompt_tokens + r.output_tokens for r in schedule))
    )
    need = [b for b in buckets if lo <= b <= hi]
    big = core.cfg.max_prefill_batch
    for b in need:
        n = min(b, top - 3)
        for attempt in range(3):
            ids = [1 + (i % 120) for i in range(n)]
            await asyncio.gather(
                *(serve_greedy(system.engine, f"warm{b}-{attempt}-{i}", ids, 2)
                  for i in range(big))
            )
            await serve_greedy(system.engine, f"warm{b}-{attempt}-one", ids, 2)
            seen = {(batch, bucket) for _, _, batch, bucket in system.prefill_log}
            if (1, b) in seen and (big, b) in seen:
                break
        else:
            raise RuntimeError(f"could not warm both prefill batch sizes of bucket {b}")
    warmed = sorted({(batch, bucket) for _, _, batch, bucket in system.prefill_log})
    system.timings.clear()
    return {"buckets": need, "programs": len(warmed), "warmed": warmed}

