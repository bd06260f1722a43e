"""Virtual-time asyncio event loop for the fleet sim.

A discrete-event simulation wants ``await asyncio.sleep(30)`` to cost
nothing: when every runnable callback has drained, time should jump
straight to the next scheduled timer. :class:`VirtualTimeLoop` does that
by overriding ``loop.time()`` with a virtual monotonic counter and
wrapping the selector so that the idle wait (``select(timeout)``)
*advances* the counter instead of blocking the process.

Because the whole stack reads time through :mod:`llmq_tpu.utils.clock`,
installing :class:`LoopClock` makes the janitor's staleness windows, the
deadline plane, redelivery backoff, and heartbeat cadences all march to
the same virtual clock — a 2,000-worker hour of queue time runs in
seconds and is exactly reproducible.

No file except this one should need to know the loop is virtual: the
broker's ``loop.call_later`` backoff timers and every ``asyncio.sleep``
in worker/janitor code are already loop-clock relative.
"""

from __future__ import annotations

import asyncio
import selectors
from typing import Any, Awaitable, Optional, TypeVar

from llmq_tpu.utils import clock

T = TypeVar("T")

# Wall-clock origin for virtual runs: clock.wall() == EPOCH + loop.time().
# Any fixed value works (determinism is the point); an arbitrary recent
# stamp keeps datetime renderings plausible in traces.
EPOCH = 1_700_000_000.0


class _InstantSelector:
    """Selector wrapper that converts idle waits into time jumps.

    ``BaseEventLoop._run_once`` computes how long it may sleep (the gap
    to the earliest timer) and passes it to ``select``. Real fds are
    still polled (timeout 0) so transport callbacks fire; when nothing
    is ready the requested sleep is applied to the virtual clock
    instead of the OS. A ``None`` timeout means the loop would block
    forever — with no external I/O in a sim that is a deadlock, and
    raising beats hanging the test suite.
    """

    def __init__(self, inner: selectors.BaseSelector) -> None:
        self._inner = inner
        self.loop: Optional["VirtualTimeLoop"] = None

    def select(self, timeout: Optional[float] = None) -> list:
        events = self._inner.select(0)
        if events:
            return events
        if timeout is None:
            raise RuntimeError(
                "virtual-time deadlock: every task is waiting and no "
                "timer is scheduled (a sim component is awaiting an "
                "event nothing will set)"
            )
        if timeout > 0 and self.loop is not None:
            self.loop._advance(timeout)
        return []

    def __getattr__(self, name: str) -> Any:
        return getattr(self._inner, name)


class VirtualTimeLoop(asyncio.SelectorEventLoop):
    """SelectorEventLoop whose ``time()`` is a jumpable virtual counter.

    Timers (``call_later``/``call_at``, hence every ``asyncio.sleep``)
    key off ``loop.time()``, so overriding it plus the selector's idle
    wait is sufficient — no task or future machinery changes.
    """

    def __init__(self, *, start: float = 0.0, epoch: float = EPOCH) -> None:
        self._vnow = float(start)
        self.epoch = float(epoch)
        sel = _InstantSelector(selectors.DefaultSelector())
        super().__init__(sel)
        sel.loop = self

    def time(self) -> float:
        return self._vnow

    def _advance(self, dt: float) -> None:
        self._vnow += dt


class LoopClock(clock.Clock):
    """The injectable clock for virtual runs: monotonic == loop time,
    wall == a fixed epoch plus loop time (so wall-time policy — deadline
    stamps, heartbeat staleness — advances in lockstep)."""

    virtual = True

    def __init__(self, loop: VirtualTimeLoop) -> None:
        self._loop = loop

    def monotonic(self) -> float:
        return self._loop.time()

    def time(self) -> float:
        return self._loop.epoch + self._loop.time()


def run_virtual(main: Awaitable[T], *, epoch: float = EPOCH) -> T:
    """Run ``main`` to completion on a fresh virtual-time loop.

    Installs :class:`LoopClock` for the duration (restoring the prior
    clock after — nested/sequential runs compose) and cancels any tasks
    the coroutine left behind, mirroring ``asyncio.run``'s teardown.
    """
    loop = VirtualTimeLoop()
    prev = clock.get_clock()
    clock.set_clock(LoopClock(loop))
    try:
        asyncio.set_event_loop(loop)
        try:
            return loop.run_until_complete(main)
        finally:
            _cancel_pending(loop)
    finally:
        clock.set_clock(prev)
        asyncio.set_event_loop(None)
        loop.close()


def _cancel_pending(loop: VirtualTimeLoop) -> None:
    tasks = [t for t in asyncio.all_tasks(loop) if not t.done()]
    for task in tasks:
        task.cancel()
    if tasks:
        loop.run_until_complete(
            asyncio.gather(*tasks, return_exceptions=True)
        )
    loop.run_until_complete(loop.shutdown_asyncgens())
