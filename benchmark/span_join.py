"""The program's own spans (``llmq_tpu/obs/spans.py``) joined to the
device trace, for the readers that need both.

``run.py`` hands a reader its context and nothing of the program, so the
dump is fetched from the process (``spans.dump_process()``). On a program that has no span ring (the parent of the PR that added it)
everything here returns ``None`` and the metrics are left out.

The ring follows the profiler: it is on while the benchmark's ``Tracer``
holds a profile session, so the spans cover the traced seconds inside
the window, like the device events.

**One clock.** Spans are stamped ``time.monotonic_ns()``; device events
are on the profiler's clock, counted from the session's start. The
offset between them is anchored (``anchor_clock``): a ``fetch`` that
waited for its program ends as soon as the program's result is on the
host, so over many matched (fetch, device run) pairs the smallest
(fetch end - device end) is the offset plus the shortest copy-out, and
the spread among the smallest few is its residual. So every time that
crosses the two clocks is relative to that shortest copy-out: a fetch
lag reads "above the smallest lag seen" (the smallest reads 0), a queue
reads that much too long (about 2 ms on a v5e: ``tools/span_probe.py``
fits the clock from the profile's host plane, which ``run.py`` deletes
before the readers run, and prints the difference).

**Which run a dispatch launched.** The device runs programs in the order
they were dispatched. Dispatch spans (``prefill_dispatch``,
``decode_dispatch``) and the step programs' runs on the ``XLA Modules``
line are two sequences of program names that differ by a shift (runs
dispatched before the ring was on; dispatches not yet run when the trace
stopped): the shift is the one at which every overlapping pair names the
same program.
"""

from __future__ import annotations

import json
from types import SimpleNamespace
from typing import Any, Dict, List, Optional, Tuple

from . import trace_reduce
from .stats import percentile

DISPATCHES = ("prefill_dispatch", "decode_dispatch")
#: The profiler's host side (``TraceAnnotation.is_enabled``, which the
#: ring follows and the ``profile`` span marks) comes up after the device
#: trace has begun and outlasts it: by ~0.5 s on one chip, 2.7 s on four
#: (my chip runs, PR 28). So a run in the trace may have been dispatched
#: that long, plus the run-ahead queue, before the ``profile`` span.
RUN_AHEAD_NS = 6_000_000_000
MAX_SHIFT = 200


def process_dump() -> Optional[Dict[str, Any]]:
    try:
        from llmq_tpu.obs import spans
    except ImportError:  # a program without the ring
        return None
    return spans.dump_process()


def anchor_clock(pairs: List[Tuple[dict, Any]], fetch_of: Dict[int, dict]) -> Optional[Dict[str, float]]:
    lags = sorted(
        fetch_of[d["id"]]["t1_ns"] - run.end_ns
        for d, run in pairs
        if d["id"] in fetch_of
    )
    if len(lags) < 8:
        return None
    few = lags[: max(5, len(lags) // 10)]
    return {
        "offset_ns": few[0],
        "residual_ms": (few[-1] - few[0]) / 1e6,
        "samples": len(lags),
    }


def step_runs(events, programs: set) -> List[Any]:
    """Runs of the step programs on the first device plane, in order."""
    runs = [
        run
        for name, rs in trace_reduce.program_runs(events).items()
        if name in programs
        for run in rs
    ]
    return sorted(runs, key=lambda e: e.start_ns)


def match(dispatches: List[dict], runs: List[Any]) -> Tuple[Optional[int], bool]:
    """The shift k with ``dispatches[i]`` <-> ``runs[i + k]``, and whether
    another shift fitted as well. None where no shift fits."""
    want = ["jit_" + d["program"] for d in dispatches]
    have = [trace_reduce.program_name(r.name) for r in runs]
    fits = []
    for k in sorted(range(-MAX_SHIFT, MAX_SHIFT + 1), key=abs):
        lo, hi = max(0, -k), min(len(want), len(have) - k)
        if hi - lo < min(8, len(want), len(have)):
            continue
        if all(want[i] == have[i + k] for i in range(lo, hi)):
            fits.append((lo - hi, abs(k), k))  # the longest overlap first
    if not fits:
        return None, False
    fits.sort()
    return fits[0][2], len(fits) > 1


def load(ctx) -> Optional[SimpleNamespace]:
    """The joined view, made once per run and kept on the context."""
    cached = getattr(ctx, "_span_join", None)
    if cached is not None:
        return cached or None
    joined = _join(ctx)
    ctx._span_join = joined or False
    if joined:
        print(json.dumps({"line": "spans", **summary(joined)}, default=float), flush=True)
    return joined


def _join(ctx) -> Optional[SimpleNamespace]:
    dump = process_dump()
    if not dump or not (dump.get("spans") or dump.get("loop_lag")):
        return None
    spans = dump.get("spans", [])
    j = SimpleNamespace(
        spans=spans,
        requests=dump.get("requests", {}),
        counters=dump.get("counters", {}),
        scopes=dump.get("scopes", {}),
        loop_lag=dump.get("loop_lag"),
        engine=[s for s in spans if s.get("ring") == "engine"],
        pairs=[], fetch_of={}, clock=None, shift=None,
        ambiguous=False, runs=[], events=ctx.trace, dispatch_of_run={},
    )
    turns = [s for s in j.engine if s["name"] == "turn"]
    j.covered = (turns[0]["t0_ns"], turns[-1]["t1_ns"]) if turns else None
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        if s["name"] == "fetch" and by_id.get(s["cause"], {}).get("name") in DISPATCHES:
            j.fetch_of[s["cause"]] = s
    if ctx.trace is None:
        return j
    # A ring that was on for longer than the profile (``set_tracing``,
    # ``LLMQ_SPANS``) holds dispatches from long before it: only those
    # from the profiled stretch (``profile`` spans), and the run-ahead
    # queue's worth before it, can have a run in the trace.
    profiled = [s for s in j.engine if s["name"] == "profile"]
    lo = min(s["t0_ns"] for s in profiled) - RUN_AHEAD_NS if profiled else 0
    hi = max(s["t1_ns"] for s in profiled) if profiled else float("inf")
    dispatches = sorted(
        (s for s in j.engine
         if s["name"] in DISPATCHES and lo <= s["t0_ns"] <= hi),
        key=lambda s: s["t0_ns"],
    )
    j.runs = step_runs(ctx.trace, {"jit_" + d["program"] for d in dispatches})
    if not dispatches or not j.runs:
        return j
    j.shift, j.ambiguous = match(dispatches, j.runs)
    if j.shift is None:
        return j
    for i, d in enumerate(dispatches):
        if 0 <= i + j.shift < len(j.runs):
            j.pairs.append((d, j.runs[i + j.shift]))
            j.dispatch_of_run[id(j.runs[i + j.shift])] = d
    j.clock = anchor_clock(j.pairs, j.fetch_of)
    return j


def in_window(ctx, t_ns: int) -> bool:
    return ctx.records.t0 * 1e9 <= t_ns < ctx.records.t1 * 1e9


# --- what the readers ask for -------------------------------------------------


def queue_ms(j, name: str) -> List[float]:
    """Dispatch span start -> device start of the run it launched (too
    long by the shortest copy-out: the clock is anchored)."""
    if not j.clock:
        return []
    off = j.clock["offset_ns"]
    return [
        (run.start_ns + off - d["t0_ns"]) / 1e6
        for d, run in j.pairs
        if d["name"] == name
    ]


def fetch_lag_ms(j, name: str) -> List[float]:
    """Device end of a run -> end of its ``fetch`` span, above the
    smallest such lag of the run (the clock is anchored on it)."""
    if not j.clock:
        return []
    off = j.clock["offset_ns"]
    return [
        (j.fetch_of[d["id"]]["t1_ns"] - run.end_ns - off) / 1e6
        for d, run in j.pairs
        if d["name"] == name and d["id"] in j.fetch_of
    ]


def device_ms(j, name: str) -> List[float]:
    """Device time of the runs that the dispatch spans ``name`` launched."""
    return [run.dur_ns / 1e6 for d, run in j.pairs if d["name"] == name]


def late_ticks(j, ctx) -> Optional[List[Tuple[float, float]]]:
    """``(t_mono, late_ms)`` of the loop-lag mark's ticks that ran more
    than 20 ms late inside the window (``obs.spans.LoopLag``, on for the
    worker's whole life); None on a program without the mark."""
    if not j.loop_lag:
        return None
    return [
        (t, late) for t, late in j.loop_lag["late"]
        if ctx.records.t0 <= t < ctx.records.t1
    ]


def merged_map(maps: Dict[str, Dict[str, str]]) -> Dict[str, Optional[str]]:
    """One map for several variants of a program: an instruction keeps
    its scope only where every variant that has it agrees."""
    merged: Dict[str, Optional[str]] = {}
    for m in maps.values():
        for instr, scope in m.items():
            merged[instr] = scope if merged.get(instr, scope) == scope else None
    return merged


def scope_of_run(j, program: str, run, ops: List[str]) -> Optional[Dict[str, Optional[str]]]:
    """The instruction -> scope map of the variant a run executed: the
    matched dispatch names it; an unmatched run (launched before the ring
    was on) is the variant whose compiled text holds the most of the
    run's own operations (``ops``: their names), each variant numbering
    its instructions its own way."""
    maps = j.scopes.get(program.removeprefix("jit_"), {})
    d = j.dispatch_of_run.get(id(run))
    exact = maps.get(f"{d.get('mode')}/{d.get('variant')}") if d else None
    if exact is not None or not maps:
        return exact
    return max(maps.values(), key=lambda m: sum(name in m for name in ops))


def device_scopes(j) -> Dict[str, float]:
    """Own seconds of the device operations by the ``llmq.*`` scope they
    were traced under (first device plane); ``?<program>`` where the
    program's scope map is not known or does not hold the operation."""
    events = j.events
    if events is None:
        return {}
    ops_by_plane = trace_reduce._by_plane(events, trace_reduce.OPS_LINE)
    if not ops_by_plane:
        return {}
    plane = sorted(ops_by_plane)[0]
    runs = sorted(
        (e for e in events if e.line == trace_reduce.MODULES_LINE and e.plane == plane),
        key=lambda e: e.start_ns,
    )
    # The operations of each run first: which variant a run was is read
    # from all of them.
    per_run: Dict[int, List[Tuple[str, int]]] = {}
    i = 0
    for ev, own in trace_reduce.self_times(ops_by_plane[plane]):
        while i < len(runs) and runs[i].end_ns <= ev.start_ns:
            i += 1
        inside = i < len(runs) and runs[i].start_ns <= ev.start_ns
        per_run.setdefault(i if inside else -1, []).append((ev.name, own))
    out: Dict[str, float] = {}
    for i, ops in per_run.items():
        program = trace_reduce.program_name(runs[i].name) if i >= 0 else ""
        scope_map = (
            scope_of_run(j, program, runs[i], [name for name, _ in ops]) if i >= 0 else None
        )
        for name, own in ops:
            key = (scope_map or {}).get(name) or f"?{program}"
            out[key] = out.get(key, 0.0) + own / 1e9
    return out


def scope_ms_per_run(j, program: str, scope: str) -> Optional[float]:
    """Own time under ``scope`` per run of ``program`` (``jit_decode_step``),
    summed over the run's layers."""
    if j.events is None:
        return None
    maps = j.scopes.get(program.removeprefix("jit_"), {})
    if not maps:
        return None
    runs = trace_reduce.program_runs(j.events).get(program, [])
    if not runs:
        return None
    merged = merged_map(maps)
    total = sum(
        s for (prog, name), s in trace_reduce.op_seconds_by_program(j.events).items()
        if prog == program and merged.get(name) == scope
    )
    return total * 1e3 / len(runs) if total else None


def idle_gaps(j, top: int = 10, unnamed_over_ns: int = 50_000) -> Tuple[List[list], int]:
    """Device idle gaps by the innermost engine-thread span open when the
    gap began (``turn`` where the thread was between spans: waiting for
    work, or in the loop's own bookkeeping; ``before_<program>`` where
    the clocks are not joined or no span was open), and how many gaps
    longer than ``unnamed_over_ns`` no span names."""
    gaps: Dict[str, float] = {}
    unnamed = 0
    runs = j.runs
    off = j.clock["offset_ns"] if j.clock else None
    for prev, nxt in zip(runs, runs[1:]):
        gap = nxt.start_ns - prev.end_ns
        if gap <= 0:
            continue
        key = None
        if off is not None:
            t = prev.end_ns + off
            open_now = [s for s in j.engine if s["t0_ns"] <= t < s["t1_ns"]]
            if open_now:
                key = max(open_now, key=lambda s: s["t0_ns"])["name"]
        if key is None:
            key = f"before_{trace_reduce.program_name(nxt.name)}"
            unnamed += gap > unnamed_over_ns
        gaps[key] = gaps.get(key, 0.0) + gap / 1e9
    return [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:top]], unnamed


def summary(j) -> Dict[str, Any]:
    """The ``spans`` earlier line of a traced run."""
    from llmq_tpu.obs.spans import self_times_ns

    own = self_times_ns(j.spans)
    by_name: Dict[str, List[float]] = {}
    for s in j.spans:
        row = by_name.setdefault(s["name"], [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (s["t1_ns"] - s["t0_ns"]) / 1e6
        row[2] += own.get(s["id"], 0) / 1e6
    scopes = device_scopes(j)
    gaps, unnamed = idle_gaps(j)
    busy = sum(scopes.values())
    named = sum(v for k, v in scopes.items() if k.startswith("llmq."))
    out: Dict[str, Any] = {
        "clock": dict(j.clock or {}, shift=j.shift, ambiguous=j.ambiguous,
                      pairs=len(j.pairs)),
        "covered_s": (j.covered[1] - j.covered[0]) / 1e9 if j.covered else None,
        "span_n_total_ms_self_ms": {k: [v[0], round(v[1], 3), round(v[2], 3)]
                                    for k, v in sorted(by_name.items())},
        "device_scopes": [[k, round(v, 6)] for k, v in
                          sorted(scopes.items(), key=lambda kv: -kv[1])[:16]],
        "scoped_share_pct": 100.0 * named / busy if busy else None,
        "idle_gaps": gaps,
        "idle_gaps_over_50us_unnamed": unnamed,
        "counters": j.counters,
    }
    if j.loop_lag:
        # The mark runs for the worker's whole life: its latest tick since
        # start, and the four latest still in its list with what the
        # engine thread was in meanwhile (where its ring was on).
        out["loop_lag"] = {
            "ticks": j.loop_lag["ticks"],
            "max_ms": j.loop_lag["max_ms"],
            "late_total": j.loop_lag["late_total"],
            "latest": [
                {
                    "t_mono": t,
                    "late_ms": late,
                    "engine_during": sorted(
                        ([e["name"], round((e["t1_ns"] - e["t0_ns"]) / 1e6, 1)]
                         for e in j.engine
                         if e["name"] != "turn"
                         and e["t0_ns"] < t * 1e9
                         and e["t1_ns"] > (t - late / 1e3) * 1e9),
                        key=lambda kv: -kv[1],
                    )[:3],
                }
                for t, late in sorted(j.loop_lag["late"], key=lambda r: -r[1])[:4]
            ],
        }
    for name in DISPATCHES:
        q, f = queue_ms(j, name), fetch_lag_ms(j, name)
        out[name] = {
            "n": len(q),
            "queue_p50_ms": percentile(q, 50),
            "fetch_lag_p50_ms": percentile(f, 50),
            "rows_mean": (
                sum(d["rows"] for d, _ in j.pairs if d["name"] == name) / len(q)
                if q else None
            ),
        }
    return out
