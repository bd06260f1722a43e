#!/usr/bin/env python3
"""A one-off check, not part of the benchmark: ``benchmark/run.py
--trace 1`` with the profile's host plane kept, to see how far the
anchored clock of ``benchmark/span_join.py`` lies from one fitted on the
profile itself.

    python3 tools/span_probe.py --workload <cell> --seed <n> --seconds <s> --trace 1

``run.py`` deletes the profile before the readers run, so ``span_join``
anchors its clock on ``fetch`` spans. This runs the same command with
two of the benchmark's functions wrapped (``trace_reduce.load_xplane``,
``span_join._join``), keeps the ``llmq.*`` annotations of the host plane
(each carries its span's ``t_mono_ns``) as the profile is loaded, and
prints two more lines: ``clockcheck`` (the offset fitted over those
annotations, with residual and pairs, beside the anchored one) and
``op_stats`` (the stats that the
device's own events carry: whether an ``op_name`` rides there). With
``SPAN_SAMPLE=<path>`` it also writes a cut of the run (dump, device
events, host annotations) for ``tests/data``.
"""

from __future__ import annotations

import json
import os
import runpy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import span_join, trace_reduce  # noqa: E402

HOST: list = []
_load_xplane = trace_reduce.load_xplane
_join = span_join._join


def say(line: str, **facts) -> None:
    print(json.dumps({"line": line, **facts}, default=str), flush=True)


def fit_clock(host_events: list):
    """``host_events``: ``{"start_ns": <profiler>, "t_mono_ns": <stat>}``
    of the annotations on the profile's host plane. Each is one sample
    of (monotonic - profiler): the offset is their median, the residual
    the largest deviation."""
    deltas = sorted(e["t_mono_ns"] - e["start_ns"] for e in host_events
                    if e.get("t_mono_ns"))
    if not deltas:
        return None
    offset = deltas[len(deltas) // 2]
    return {
        "offset_ns": offset,
        "residual_ms": max(abs(d - offset) for d in deltas) / 1e6,
        "samples": len(deltas),
    }


def load_keeping_host(path, lines=(trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)):
    from jax.profiler import ProfileData

    shown = 0
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            if plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith("llmq."):
                        stats = dict(ev.stats)
                        HOST.append({
                            "name": ev.name, "start_ns": int(ev.start_ns),
                            "t_mono_ns": stats.get("t_mono_ns"),
                            "span_id": stats.get("span_id"),
                        })
            elif trace_reduce.DEVICE_PLANE.match(plane.name) and shown < 2 and line.name in lines:
                shown += 1
                for ev in list(line.events)[:2]:
                    say("op_stats", on=line.name, name=ev.name[:80],
                        stats={k: str(v)[:100] for k, v in dict(ev.stats).items()})
    return _load_xplane(path, lines)


def join_and_check(ctx):
    j = _join(ctx)
    fitted = fit_clock(HOST)
    anchored = j.clock if j else None
    say("clockcheck", host_annotations=len(HOST), fitted=fitted, anchored=anchored,
        anchored_minus_fitted_ms=(
            (anchored["offset_ns"] - fitted["offset_ns"]) / 1e6
            if anchored and fitted else None))
    sample = os.environ.get("SPAN_SAMPLE")
    if j is not None and sample:
        write_sample(sample, j, ctx)
    return j


def write_sample(path: str, j, ctx) -> None:
    """Every ``XLA Modules`` event, the ``XLA Ops`` events of one decode
    run and one prefill run, the whole dump, the host annotations."""
    events = ctx.trace or []
    runs = trace_reduce.program_runs(events)
    keep = [  # the first run of each that a dispatch span in the ring launched
        next(((r.start_ns, r.end_ns) for r in runs.get(name, [])
              if id(r) in j.dispatch_of_run), (0, 0))
        for name in ("jit_decode_step", "jit_prefill_step")
    ]
    plane = min((e.plane for e in events), default="")
    cut = [
        [e.plane, e.line, e.name, e.start_ns, e.dur_ns]
        for e in events
        if e.plane == plane and (
            e.line == trace_reduce.MODULES_LINE
            or any(lo <= e.start_ns < hi for lo, hi in keep)
        )
    ]
    stamps = ("rid", "sent", "due", "enqueued", "first_token", "prefill_start")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({
            "source": " ".join(sys.argv[1:]),
            "window": [ctx.records.t0, ctx.records.t1],
            "rows": [{k: r.get(k) for k in stamps} for r in ctx.records.rows
                     if r["rid"] in j.requests],
            "spans": j.spans, "requests": j.requests, "counters": j.counters,
            "scopes": j.scopes, "loop_lag": j.loop_lag, "host": HOST,
            "events": cut,
        }, fh)


if __name__ == "__main__":
    trace_reduce.load_xplane = load_keeping_host
    span_join._join = join_and_check
    sys.argv = [str(ROOT / "benchmark" / "run.py")] + sys.argv[1:]
    runpy.run_path(sys.argv[0], run_name="__main__")
