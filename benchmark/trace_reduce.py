"""From a profiler trace to numbers: the benchmark's own reduction.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it with nothing but JAX. A trace is reduced in two steps, so that
the second can be checked on a small recorded trace kept as JSON
(``tests/data``):

1. ``load_xplane`` flattens the device planes to events
   ``(plane, line, name, start_ns, dur_ns)``;
2. the functions below work on those events alone.

Which lines are read. A TPU device plane (``/device:TPU:<n>``) carries a
line ``XLA Modules`` (one event per run of a compiled program, named
``jit_<fn>(<fingerprint>)``) and a line ``XLA Ops`` (one event per HLO
operation, nested where an operation such as ``while`` contains others).
*Busy* is the union of the intervals of the ``XLA Ops`` line: a moment
counts as busy when any operation runs on that chip. Busy seconds are the
mean over the device planes that have operations; the window is the
traced span on the host's clock.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
)
#: ``jit_decode_step(1234)``: the program's name without its fingerprint.
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path, lines=(OPS_LINE, MODULES_LINE)) -> List[Event]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    events: List[Event] = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in lines:
                continue
            for ev in line.events:
                events.append(
                    Event(plane.name, line.name, op_name(ev.name),
                          int(ev.start_ns), int(ev.duration_ns))
                )
    return events


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line
    (``%fusion.3 = bf16[...] fusion(...)``): keep the operation's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def traced_seconds(events: List[Event]) -> Optional[float]:
    """Length of the traced window on the device's own clock: from the
    first operation's start to the last one's end, over all chips. (The
    host's span around start_trace and stop_trace also holds the seconds
    the profiler takes to start and to write its file.)"""
    ops = [e for e in events if e.line == OPS_LINE]
    if not ops:
        return None
    return (max(e.end_ns for e in ops) - min(e.start_ns for e in ops)) / 1e9


def events_from_json(rows: Iterable[list]) -> List[Event]:
    return [Event(*row) for row in rows]


def _by_plane(events: List[Event], line: str) -> Dict[str, List[Event]]:
    out: Dict[str, List[Event]] = {}
    for ev in events:
        if ev.line == line:
            out.setdefault(ev.plane, []).append(ev)
    for evs in out.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
    return out


def union_ns(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            if hi > merged[-1][1]:
                merged[-1] = (merged[-1][0], hi)
        else:
            merged.append((lo, hi))
    return merged


def _length(intervals: List[Tuple[int, int]]) -> int:
    return sum(hi - lo for lo, hi in intervals)


def busy_seconds(events: List[Event]) -> Optional[float]:
    """Mean over the chips of the union of the ``XLA Ops`` intervals."""
    per_plane = [
        _length(union_ns((e.start_ns, e.end_ns) for e in evs)) / 1e9
        for evs in _by_plane(events, OPS_LINE).values()
    ]
    return sum(per_plane) / len(per_plane) if per_plane else None


def program_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def program_runs(events: List[Event]) -> Dict[str, List[Event]]:
    """Runs of each compiled program on the first device plane (every
    chip of a mesh runs the same programs)."""
    planes = _by_plane(events, MODULES_LINE)
    if not planes:
        return {}
    first = planes[sorted(planes)[0]]
    out: Dict[str, List[Event]] = {}
    for ev in first:
        out.setdefault(program_name(ev.name), []).append(ev)
    return out


def self_times(ops: List[Event]) -> List[Tuple[Event, int]]:
    """(operation, its own nanoseconds): duration less that of the
    operations nested inside it. ``ops`` is one plane's line, sorted by
    start and, at equal starts, longest first."""
    out: List[List] = []
    stack: List[int] = []  # indices into out
    for ev in ops:
        while stack and out[stack[-1]][0].end_ns <= ev.start_ns:
            stack.pop()
        if stack and ev.end_ns <= out[stack[-1]][0].end_ns:
            out[stack[-1]][1] -= ev.dur_ns
        out.append([ev, ev.dur_ns])
        stack.append(len(out) - 1)
    return [(ev, max(0, own)) for ev, own in out]


def op_seconds_by_program(events: List[Event]) -> Dict[Tuple[str, str], float]:
    """Own seconds of every operation, keyed by (program, operation), on
    the first device plane."""
    ops_by_plane = _by_plane(events, OPS_LINE)
    if not ops_by_plane:
        return {}
    plane = sorted(ops_by_plane)[0]
    runs = sorted(
        (e for e in events if e.line == MODULES_LINE and e.plane == plane),
        key=lambda e: e.start_ns,
    )
    out: Dict[Tuple[str, str], float] = {}
    i = 0
    for ev, own in self_times(ops_by_plane[plane]):
        while i < len(runs) and runs[i].end_ns <= ev.start_ns:
            i += 1
        prog = (
            program_name(runs[i].name)
            if i < len(runs) and runs[i].start_ns <= ev.start_ns
            else "?"
        )
        key = (prog, ev.name)
        out[key] = out.get(key, 0.0) + own / 1e9
    return out


def median_run_ms(events: List[Event], pattern: str) -> Optional[float]:
    """Median device duration of the runs of programs matching ``pattern``."""
    from .stats import percentile

    rx = re.compile(pattern)
    durs = [
        e.dur_ns / 1e6
        for name, runs in program_runs(events).items()
        if rx.search(name)
        for e in runs
    ]
    return percentile(durs, 50)


def program_share_pct(events: List[Event], pattern: str) -> Optional[float]:
    """Time of the programs matching ``pattern`` over busy time, first plane."""
    rx = re.compile(pattern)
    busy = busy_seconds(events)
    if not busy:
        return None
    t = sum(
        e.dur_ns
        for name, runs in program_runs(events).items()
        if rx.search(name)
        for e in runs
    )
    return 100.0 * (t / 1e9) / busy


def op_ms_per_run(events: List[Event], program: str, op: str) -> Optional[float]:
    """Own time of the operations matching ``op`` inside programs matching
    ``program``, summed over a run's layers, per run of the program."""
    prx, orx = re.compile(program), re.compile(op)
    n_runs = sum(
        len(runs) for name, runs in program_runs(events).items() if prx.search(name)
    )
    if not n_runs:
        return None
    t = sum(
        s for (prog, name), s in op_seconds_by_program(events).items()
        if prx.search(prog) and orx.search(name)
    )
    return t * 1e3 / n_runs if t else None


def exposed_collective_pct(events: List[Event]) -> Optional[float]:
    """Collective time during which no other operation runs on that chip,
    over busy time; mean over the chips."""
    shares = []
    for evs in _by_plane(events, OPS_LINE).values():
        own = self_times(evs)
        coll = union_ns(
            (e.start_ns, e.end_ns) for e, _ in own if COLLECTIVE.search(e.name)
        )
        if not coll:
            continue
        other = union_ns(
            (e.start_ns, e.start_ns + o)
            for e, o in own
            if not COLLECTIVE.search(e.name) and o > 0
        )
        # Exposed: the collective intervals less what overlaps other work.
        covered, j = 0, 0
        for lo, hi in coll:
            while j < len(other) and other[j][1] <= lo:
                j += 1
            k = j
            while k < len(other) and other[k][0] < hi:
                covered += min(hi, other[k][1]) - max(lo, other[k][0])
                k += 1
        busy = _length(union_ns((e.start_ns, e.end_ns) for e in evs))
        shares.append(100.0 * (_length(coll) - covered) / busy)
    return sum(shares) / len(shares) if shares else None


def breakdown(events: List[Event], top: int = 10) -> dict:
    """Top device operations by own time, and the longest idle gaps by the
    program that ran next (the program has no host spans on this clock
    yet, so a gap is named by what it waited for)."""
    ops = sorted(
        ((f"{prog}/{name}", s) for (prog, name), s in op_seconds_by_program(events).items()),
        key=lambda kv: -kv[1],
    )[:top]
    gaps: Dict[str, float] = {}
    planes = _by_plane(events, MODULES_LINE)
    if planes:
        runs = planes[sorted(planes)[0]]
        for prev, nxt in zip(runs, runs[1:]):
            gap = nxt.start_ns - prev.end_ns
            if gap > 0:
                key = f"before_{program_name(nxt.name)}"
                gaps[key] = gaps.get(key, 0.0) + gap / 1e9
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[k, v] for k, v in idle],
    }
