"""The three load generators. Each drives the running system with the
requests of a schedule and returns what it saw from the client's side;
``metrics.py`` turns that and the program's stamps into numbers.

The generator shares the process (and the event loop) with the worker:
one process per chip. It reports how late it sent (send instant minus due
instant), so that a starved generator is not read as a fast server.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time
from typing import Dict, List, Optional

from .schedule import Request, prompt_text


@dataclasses.dataclass
class Sent:
    request: Request
    rid: str
    due: float  # monotonic instant the request was due
    sent: float  # instant just before the publish


@dataclasses.dataclass
class Drive:
    """What one run's traffic was, from the client's side."""

    window: tuple  # (t0, t1) monotonic: the measured window
    sent: List[Sent]
    stats0: dict  # engine.stats() at t0
    stats1: dict  # ... at t1
    compile0: dict
    compile1: dict
    job_seconds: Optional[float] = None  # fixed job: first submit to last result
    unfinished: int = 0
    inflight_first_token: Dict[str, float] = dataclasses.field(default_factory=dict)


def _texts(seed: int, requests: List[Request]) -> Dict[int, str]:
    return {r.index: prompt_text(seed, r.index, r.prompt_tokens) for r in requests}


async def sleep_until(t: float) -> None:
    # Coarse sleep, then a short spin: asyncio's timer is good to ~1 ms.
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        await asyncio.sleep(left - 0.0005 if left > 0.002 else 0)


class Tracer:
    """Starts and stops the profiler inside the window, off the loop."""

    def __init__(self, trace_dir: Optional[str], seconds: float, system) -> None:
        self.dir, self.seconds, self.system = trace_dir, seconds, system
        self.span: Optional[tuple] = None
        #: Live sequences and their cached tokens in the middle of the trace.
        self.live_kv: Optional[dict] = None
        self._task: Optional[asyncio.Task] = None

    def arm(self, at: float) -> None:
        if self.dir:
            self._task = asyncio.ensure_future(self._run(at))

    async def _run(self, at: float) -> None:
        import jax

        await sleep_until(at)
        t0 = time.monotonic()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # device lines only: cheaper, smaller
        options.host_tracer_level = 1
        await asyncio.to_thread(
            jax.profiler.start_trace, self.dir, profiler_options=options
        )
        await asyncio.sleep(self.seconds / 2)
        live = [s for s in self.system.inflight().values() if s.prefilled]
        self.live_kv = {
            "sequences": len(live),
            "tokens": sum(s.num_tokens for s in live),
        }
        await asyncio.sleep(self.seconds / 2)
        await asyncio.to_thread(jax.profiler.stop_trace)
        self.span = (t0, time.monotonic())

    async def done(self) -> None:
        if self._task is not None:
            await self._task


async def open_loop(system, spec, schedule, seed, seconds, meter, tracer) -> Drive:
    """Requests sent at their due instants whether or not earlier ones
    have ended. The same traffic runs ``warm_seconds`` before the window
    opens and goes on after it closes, until every request that was due
    inside the window has its first token (``first_token_limit_s`` at
    most)."""
    texts = _texts(seed, schedule)
    warm = float(spec["warm_seconds"])
    limit = float(spec["first_token_limit_s"])
    start = time.monotonic() + 0.05
    t0, t1 = start + warm, start + warm + seconds
    tracer.arm(t0 + float(spec.get("trace_offset_s", 5.0)))
    sent: List[Sent] = []
    marks: dict = {}

    async def mark_until(now: float) -> None:
        """Engine and compile counters at the window's two edges, taken as
        the traffic passes them."""
        for name, t in (("0", t0), ("1", t1)):
            if name not in marks and now >= t:
                await sleep_until(t)
                marks[name] = (system.stats(), meter.snapshot())

    def due_in_window_pending() -> List[Sent]:
        running = system.inflight()
        out = []
        for s in sent:
            if not t0 <= s.due < t1 or s.rid in system.timings:
                continue
            seq = running.get(s.rid)
            if seq is None or not seq.t_first_token:
                out.append(s)
        return out

    for req in schedule:
        due = start + req.due_s
        await mark_until(due)
        if due >= t1 and (
            not due_in_window_pending() or time.monotonic() > t1 + limit
        ):
            break
        await sleep_until(due)
        rid = f"r{req.index}"
        t_sent = await system.publish(rid, texts[req.index], req.output_tokens)
        sent.append(Sent(req, rid, due, t_sent))
    await mark_until(t1)  # a schedule that ended early
    while due_in_window_pending() and time.monotonic() < t1 + limit:
        await asyncio.sleep(0.02)
    await tracer.done()
    inflight = {
        rid: seq.t_first_token
        for rid, seq in system.inflight().items()
        if seq.t_first_token
    }
    return Drive(
        (t0, t1), sent, marks["0"][0], marks["1"][0], marks["0"][1], marks["1"][1],
        inflight_first_token=inflight,
    )


async def closed_loop(system, spec, schedule, seed, seconds, meter, tracer) -> Drive:
    """``clients`` clients, each sending its next request when its last
    one ends. They start staggered over ``stagger_seconds`` and run for
    ``warm_seconds`` before the window opens, so that completions are
    spread evenly and the cache is full when it does."""
    texts = _texts(seed, schedule)
    by_client: Dict[int, List[Request]] = {}
    for req in schedule:
        by_client.setdefault(req.client, []).append(req)
    warm = float(spec["warm_seconds"])
    start = time.monotonic() + 0.05
    t0, t1 = start + warm, start + warm + seconds
    tracer.arm(t0 + float(spec.get("trace_offset_s", 5.0)))
    sent: List[Sent] = []
    waiters: Dict[str, asyncio.Future] = {}

    def on_result(rid: str) -> None:
        waiter = waiters.get(rid)
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    system.on_result = on_result
    stop = False

    async def client(reqs: List[Request]) -> None:
        await sleep_until(start + reqs[0].due_s)
        for lap in itertools.count():  # a fast system laps its list
            for req in reqs:
                if stop:
                    return
                rid = f"r{req.index}" + (f"-{lap}" if lap else "")
                waiters[rid] = asyncio.get_running_loop().create_future()
                now = time.monotonic()
                t_sent = await system.publish(rid, texts[req.index], req.output_tokens)
                sent.append(Sent(req, rid, now, t_sent))
                await waiters[rid]

    tasks = [asyncio.ensure_future(client(r)) for r in by_client.values()]
    try:
        await sleep_until(t0)
        s0, c0 = system.stats(), meter.snapshot()
        while time.monotonic() < t1:
            for task in tasks:
                if task.done():
                    task.result()
            await asyncio.sleep(min(0.25, max(0.0, t1 - time.monotonic())))
        s1, c1 = system.stats(), meter.snapshot()
    finally:
        stop = True
        for task in tasks:
            task.cancel()
    await tracer.done()
    return Drive((t0, t1), sent, s0, s1, c0, c1)


async def fixed_job(system, spec, schedule, seed, seconds, meter, tracer) -> Drive:
    """All jobs submitted at the start, as fast as the client can publish
    them; the job's time runs from the first submit to the last result.
    A job that has not finished at three times ``--seconds`` is cut, and
    its unfinished requests have failed."""
    texts = _texts(seed, schedule)
    want = {f"r{req.index}" for req in schedule}
    done = asyncio.Event()

    def on_result(rid: str) -> None:
        want.discard(rid)
        if not want:
            done.set()

    system.on_result = on_result
    sent: List[Sent] = []
    s0, c0 = system.stats(), meter.snapshot()
    t0 = time.monotonic()
    tracer.arm(t0 + float(spec.get("trace_offset_s", 0.25 * seconds)))
    for req in schedule:
        rid = f"r{req.index}"
        t_sent = await system.publish(rid, texts[req.index], req.output_tokens)
        sent.append(Sent(req, rid, t0, t_sent))
    try:
        await asyncio.wait_for(done.wait(), timeout=3.0 * seconds)
    except asyncio.TimeoutError:
        pass
    t1 = max(system.results.values(), default=time.monotonic())
    if want:
        t1 = time.monotonic()
    s1, c1 = system.stats(), meter.snapshot()
    await tracer.done()
    return Drive(
        (t0, t1), sent, s0, s1, c0, c1, job_seconds=t1 - t0, unfinished=len(want)
    )


def slowest_tpot(records, prefill_log, n: int = 5) -> list:
    """The requests with the largest TPOT: prompt length, admission
    instant (seconds into the window) and how many prefill dispatches ran
    between their first and last token (there is no per-token stamp)."""
    rows = []
    for r in records.finished_in_window():
        k = r["completion_tokens"] or 0
        if k < 2 or not r["first_token"] or not r["last_token"]:
            continue
        rows.append(
            {
                "rid": r["rid"],
                "tpot_ms": (r["last_token"] - r["first_token"]) / (k - 1) * 1e3,
                "prompt_tokens": r["prompt_tokens"],
                "admitted_s": r["admitted"] - records.t0,
                "first_token_s": r["first_token"] - records.t0,
                "preempt_count": r["preempt_count"],
                "prefill_dispatches_between": sum(
                    1 for t, *_ in prefill_log if r["first_token"] <= t <= r["last_token"]
                ),
            }
        )
    return sorted(rows, key=lambda x: -x["tpot_ms"])[:n]


GENERATORS = {
    "open_loop": open_loop,
    "closed_loop": closed_loop,
    "fixed_job": fixed_job,
}
