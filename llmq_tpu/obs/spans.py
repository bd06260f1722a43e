"""Span rings: what one thread was doing, and when, on the clock the
request stamps use. And the one mark that is always on: how late the
worker's event loop runs its callbacks.

A :class:`SpanRing` is a fixed-size in-memory ring of spans with a single
writer: the engine thread has one, the worker's event loop another. A
span is ``(name, t0_ns, t1_ns, id, cause, fields)``: ``cause`` is the id
of the span that caused it (by default the span open around it), spans
of one request carry its ``rid`` in their fields, and stamps are
``time.monotonic_ns()``, the clock of ``RequestOutput.timing``, so spans
and request stamps join without conversion. Nothing is written anywhere
until :meth:`SpanRing.dump` is asked for.

Off (the default) means off. A writer tests ``ring.on`` itself, in
place, before every ``begin`` / ``end`` / ``add``: an attribute load and
a branch is all a span site costs a ring that is off, and the code
around it stays what it was. While a ring is on, each span is also
entered as a ``jax.profiler.TraceAnnotation`` with ``span_id``,
``cause_id`` and ``t_mono_ns`` as its stats: that costs an atomic load
while no profile is being taken, and puts the span on the host plane of
the profile, beside the device lines, while one is.

A ring is on when it was switched on (``AsyncEngine.set_tracing``, the
worker's ``set_tracing``; ``LLMQ_SPANS=<file>`` switches both on at start
and has the dump written to that file at shutdown), and the engine
switches its rings on for as long as a profile of the process is being
taken, so that a profile never lacks them.

:class:`LoopLag` is not a ring and has no switch: ten timer callbacks a
second on the worker's loop, each noting how late it ran. (A loop on
virtual time, the fleet simulator's, cannot run late and gets none.)

Import-cheap like the rest of ``obs``: jax is imported when a ring is
first switched on, not before.
"""

from __future__ import annotations

import asyncio
import faulthandler
import itertools
import logging
import os
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Callable, Deque, Dict, Iterable, List, Optional, Tuple

from llmq_tpu.utils import clock

logger = logging.getLogger(__name__)

#: Ids are unique across the rings of a process, so that a merged dump
#: needs no renumbering. ``next`` on a ``count`` is atomic in CPython.
_ids = itertools.count(1)

#: What is alive in this process. The benchmark's readers are handed
#: only their context, never the worker: :func:`dump_process` is how
#: they reach what the program recorded.
_rings: "weakref.WeakSet[SpanRing]" = weakref.WeakSet()
_loop_lags: "weakref.WeakSet[LoopLag]" = weakref.WeakSet()

now_ns = time.monotonic_ns


def spans_path() -> Optional[str]:
    """``LLMQ_SPANS``: a worker started with it has both rings on from
    the start and writes their dump to this file at shutdown."""
    return os.environ.get("LLMQ_SPANS") or None


def stall_stacks_path() -> Optional[str]:
    """``LLMQ_STALL_STACKS``: where :class:`LoopLag` has every thread's
    stack written while the loop stands still (off unless set)."""
    return os.environ.get("LLMQ_STALL_STACKS") or None


class SpanRing:
    def __init__(
        self, name: str, capacity: int = 1 << 16, max_requests: int = 8192
    ) -> None:
        self.name = name
        self.capacity = capacity
        self.max_requests = max_requests
        self.on = False
        #: Switched on by hand (``set``): the profiler's coming and going
        #: then changes nothing.
        self.forced = False
        #: What ``follow_profiler`` was last told: the writer compares its
        #: own look at the profiler with this, and calls only on a change.
        self.profiled = False
        self._profile_since = 0
        self._buf: List[Optional[tuple]] = []
        self._n = 0
        # Open spans, innermost last: [name, t0, id, cause, fields, annotation]
        self._stack: List[list] = []
        #: rid -> stamps (monotonic seconds), kept while the ring is on.
        self.requests: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        #: dispatch index -> id of the dispatch's span, for the ``fetch``
        #: that the dispatch causes (``end_dispatch`` / ``cause_of``).
        self._dispatch_span: Dict[int, int] = {}
        #: Called by ``dump`` with the dump, to add what only the ring's
        #: owner knows (the engine adds ``scopes``).
        self.extra: Optional[Callable[[Dict[str, Any]], None]] = None
        self._annotation = None
        _rings.add(self)

    # --- the switch -------------------------------------------------------
    def set(self, on: bool) -> None:
        """Switch the ring on or off by hand, from its writer's thread."""
        self.forced = bool(on)
        self._switch(self.forced or self.profiled)

    def follow_profiler(self, active: bool) -> None:
        """On while a profile is being taken, unless switched on by hand.
        A ``profile`` span marks the stretch the profile covered, as the
        writer saw it: which spans to look for in its trace."""
        self.profiled = active
        if active:
            self._switch(True)
            self._profile_since = now_ns()
        else:
            if self.on:
                self.add("profile", self._profile_since, now_ns())
            self._switch(self.forced)

    def _switch(self, on: bool) -> None:
        if on and not self._buf:
            self._buf = [None] * self.capacity
        if on and self._annotation is None:
            try:
                from jax.profiler import TraceAnnotation

                self._annotation = TraceAnnotation
            except ImportError:  # a worker that runs no model
                self._annotation = False
        if not on:
            self.close_all()
            self._dispatch_span.clear()
        self.on = bool(on)

    # --- writing (the writer has tested ``on``) ---------------------------
    def begin(self, name: str, cause: Optional[int] = None, **fields: Any) -> int:
        """Open a span; its cause is the span open around it unless given.
        Returns its id."""
        span_id = next(_ids)
        if cause is None:
            cause = self._stack[-1][2] if self._stack else 0
        t0 = now_ns()
        annotation = None
        if self._annotation:
            annotation = self._annotation(
                "llmq." + name, span_id=span_id, cause_id=cause, t_mono_ns=t0
            )
            annotation.__enter__()
        self._stack.append([name, t0, span_id, cause, fields, annotation])
        return span_id

    def end(self, **fields: Any) -> int:
        """Close the innermost open span; returns its id (0: none open)."""
        if not self._stack:
            return 0
        name, t0, span_id, cause, kept, annotation = self._stack.pop()
        t1 = now_ns()
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if fields:
            kept.update(fields)
        self._write((name, t0, t1, span_id, cause, kept or None))
        return span_id

    def then(self, name: str, **fields: Any) -> int:
        """Close the innermost open span and open ``name`` as caused by
        it: a ``fetch`` becomes the ``emit`` of what it fetched."""
        return self.begin(name, self.end(), **fields)

    def end_dispatch(self, dispatch_idx: int) -> None:
        """Close a dispatch span at the launch's return, and keep its id
        for the ``fetch`` of that dispatch (``cause_of``)."""
        self._dispatch_span[dispatch_idx] = self.end(seq=dispatch_idx)

    def cause_of(self, dispatch_idx: int) -> int:
        return self._dispatch_span.pop(dispatch_idx, 0)

    def inside(self, name: str) -> bool:
        """Whether the innermost open span is a ``name``."""
        return bool(self._stack) and self._stack[-1][0] == name

    def close_all(self) -> None:
        while self._stack:
            self.end()

    def add(
        self, name: str, t0_ns: int, t1_ns: int, cause: int = 0, **fields: Any
    ) -> int:
        """A span whose ends are known only afterwards (a hold that was
        cleared, a tick that came late). No annotation: it cannot be
        backdated."""
        span_id = next(_ids)
        self._write((name, int(t0_ns), int(t1_ns), span_id, cause, fields or None))
        return span_id

    def _write(self, row: tuple) -> None:
        self._buf[self._n % self.capacity] = row
        self._n += 1

    def note_request(self, rid: str, **stamps: Any) -> None:
        """Stamps of one request (monotonic seconds), merged by rid."""
        row = self.requests.get(rid)
        if row is None:
            row = self.requests[rid] = {}
            if len(self.requests) > self.max_requests:
                self.requests.popitem(last=False)
        row.update(stamps)

    # --- reading ----------------------------------------------------------
    def dump(self) -> Dict[str, Any]:
        """``{"spans": [...], "requests": {rid: {stamp: t}}, "counters":
        {...}}``: the spans still in the ring in order of their start.
        May be called from another thread than the writer's: a span
        written meanwhile may be missed, never torn (rows are whole
        tuples)."""
        n, cap = self._n, self.capacity
        rows = self._buf[:n] if n <= cap else self._buf[n % cap:] + self._buf[: n % cap]
        spans = [
            {
                "name": name, "t0_ns": t0, "t1_ns": t1, "id": span_id,
                "cause": cause, "ring": self.name, **(fields or {}),
            }
            for name, t0, t1, span_id, cause, fields in (r for r in rows if r)
        ]
        spans.sort(key=lambda s: s["t0_ns"])
        dump = {
            "spans": spans,
            "requests": {rid: dict(row) for rid, row in list(self.requests.items())},
            "counters": {
                f"{self.name}.spans_written": n,
                f"{self.name}.spans_overwritten": max(0, n - cap),
            },
        }
        if self.extra is not None:
            self.extra(dump)
        return dump


def merge_dumps(dumps: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """One dump of several rings': spans in order of their start, request
    stamps merged by rid, every other key (``scopes``) taken as it is."""
    out: Dict[str, Any] = {"spans": [], "requests": {}, "counters": {}}
    for dump in dumps:
        for key, value in dump.items():
            if key == "spans":
                out["spans"].extend(value)
            elif key == "requests":
                for rid, row in value.items():
                    out["requests"].setdefault(rid, {}).update(row)
            elif isinstance(value, dict):
                out.setdefault(key, {}).update(value)
            else:
                out[key] = value
    out["spans"].sort(key=lambda s: s["t0_ns"])
    return out


def dump_process() -> Dict[str, Any]:
    """The merged dump of every ring alive in this process, and under
    ``loop_lag`` what its event loop's lag mark holds."""
    out = merge_dumps(ring.dump() for ring in list(_rings))
    for lag in list(_loop_lags):
        out["loop_lag"] = lag.snapshot()
    return out


def self_times_ns(spans: List[Dict[str, Any]]) -> Dict[int, int]:
    """id -> the span's own nanoseconds: its duration less the part its
    child spans cover. A child is a span of the same ring that lies
    inside it; spans added afterwards (``admit_hold``, ``loop_tick``)
    overlap others freely and are nobody's child."""
    own: Dict[int, int] = {}
    by_ring: Dict[str, List[Dict[str, Any]]] = {}
    for s in spans:
        by_ring.setdefault(s.get("ring", ""), []).append(s)
    for rows in by_ring.values():
        rows = sorted(rows, key=lambda s: (s["t0_ns"], -s["t1_ns"]))
        stack: List[Dict[str, Any]] = []
        for s in rows:
            while stack and stack[-1]["t1_ns"] <= s["t0_ns"]:
                stack.pop()
            own[s["id"]] = s["t1_ns"] - s["t0_ns"]
            if stack and s["t1_ns"] <= stack[-1]["t1_ns"]:
                own[stack[-1]["id"]] -= s["t1_ns"] - s["t0_ns"]
                stack.append(s)
            elif not stack:
                stack.append(s)
    return {k: max(0, v) for k, v in own.items()}


class LoopLag:
    """How late an event loop runs its callbacks, for the loop's whole
    life: a ``call_later`` chain ``period_s`` apart, each tick noting how
    late it ran.

    ``max_ms`` is the latest tick since :meth:`start`; ``late`` holds the
    last 256 ticks that ran more than ``late_s`` late as ``(t_mono,
    late_ms)``, so that a reader can take the largest inside any stretch.
    A tick more than ``stall_s`` late is logged at once, as a warning: a
    wedged loop is an operator's signal. While ``ring`` is on, each tick
    is also a ``loop_tick`` span from the instant it was due to the
    instant it ran.

    ``stack_sink``: an open file (``LLMQ_STALL_STACKS``; a debugging
    aid, never on unasked). Each tick then re-arms
    ``faulthandler.dump_traceback_later(stall_s)``, which restarts its
    watchdog thread: the dump is made by a C thread that needs no
    interpreter lock, so when the loop stands still that long every
    thread's stack is written *while* it stands. It reads the other
    threads' frames unlocked, and 1 of 16 such dumps ended the process
    (PERF.md section 6).
    """

    def __init__(
        self,
        ring: SpanRing,
        *,
        period_s: float = 0.1,
        late_s: float = 0.02,
        stall_s: float = 0.5,
        stack_sink=None,
    ) -> None:
        self.ring = ring
        self.period_s, self.late_s, self.stall_s = period_s, late_s, stall_s
        self.stack_sink = stack_sink
        #: A registry gauge that is set whenever ``max_ms`` rises.
        self.gauge = None
        self.ticks = 0
        self.max_ms = 0.0
        self.late: Deque[Tuple[float, float]] = deque(maxlen=256)
        self.late_total = 0
        self._handle: Optional[asyncio.TimerHandle] = None
        self._due = 0.0
        _loop_lags.add(self)

    def start(self) -> None:
        """From the loop's own thread. Nothing on a loop that runs on
        virtual time (``sim/vloop.py``): no callback is late there, and
        ten more timers per virtual second and worker are the
        simulator's to pay."""
        if self._handle is None and not clock.get_clock().virtual:
            self._arm(asyncio.get_running_loop())

    def stop(self) -> None:
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None
            if self.stack_sink is not None:
                faulthandler.cancel_dump_traceback_later()
                self.stack_sink.close()
                self.stack_sink = None

    def _arm(self, loop: asyncio.AbstractEventLoop) -> None:
        self._due = time.monotonic() + self.period_s
        self._handle = loop.call_later(self.period_s, self._tick, loop)
        if self.stack_sink is not None:
            faulthandler.dump_traceback_later(
                self.period_s + self.stall_s, file=self.stack_sink
            )

    def _tick(self, loop: asyncio.AbstractEventLoop) -> None:
        now = time.monotonic()
        late = now - self._due
        self.ticks += 1
        late_ms = late * 1e3
        if late_ms > self.max_ms:
            self.max_ms = late_ms
            if self.gauge is not None:
                self.gauge.set(late_ms)
        if late > self.late_s:
            self.late.append((now, late_ms))
            self.late_total += 1
            if late > self.stall_s:
                logger.warning(
                    "event loop stood still: a %.0f ms timer ran %.0f ms late "
                    "(t_mono %.3f)", self.period_s * 1e3, late_ms, now,
                )
        if self.ring.on:
            self.ring.add(
                "loop_tick", int(self._due * 1e9), int(now * 1e9),
                late_ms=late_ms,
            )
        self._arm(loop)

    def snapshot(self) -> Dict[str, Any]:
        return {
            "ticks": self.ticks,
            "max_ms": self.max_ms,
            "late_total": self.late_total,
            "late": [list(row) for row in list(self.late)],
        }
