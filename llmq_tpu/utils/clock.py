"""Injectable time source for every scheduling-policy decision.

The janitor's staleness math, the deadline plane, heartbeat cadence,
redelivery backoff, TTL expiry, and the watchdog's bracket stamps all
need *one* answer to "what time is it" — and the fleet simulator
(``llmq_tpu/sim``) needs to be that answer, so thousands of virtual
workers can live through hours of fleet time in seconds of CPU.

:class:`Clock` defaults to the real ``time.monotonic`` / ``time.time``,
and the process-wide instance is only ever replaced by the sim harness
(or a test): with the default installed, every call site compiles down
to the exact same clock reads it made before injection existed, so
production behavior — traces, heartbeats, TTL stamps — is unchanged.

Policy modules must read time through :func:`monotonic` / :func:`wall`
(the ``raw-clock-read`` lint rule enforces it); this module is the one
blessed place that touches ``time`` directly.
"""

from __future__ import annotations

import time as _time


class Clock:
    """A monotonic + wall clock pair. The default reads the real clocks;
    the sim installs a subclass that reads virtual loop time."""

    #: True on a clock that reads a virtual loop's time: nothing there
    #: can run late, so marks of real lateness (``obs.spans.LoopLag``)
    #: stay off.
    virtual = False

    def monotonic(self) -> float:
        """Monotonic seconds (durations, cadences, deadlines-in-process)."""
        return _time.monotonic()

    def time(self) -> float:
        """Epoch seconds (cross-process stamps: TTLs, heartbeats, traces)."""
        return _time.time()


_clock: Clock = Clock()


def get_clock() -> Clock:
    return _clock


def set_clock(clock: Clock) -> None:
    """Install a process-wide clock (sim harness / tests). Pass a fresh
    ``Clock()`` to restore real time."""
    global _clock
    _clock = clock


def monotonic() -> float:
    """Module-level shorthand: ``get_clock().monotonic()``."""
    return _clock.monotonic()


def wall() -> float:
    """Module-level shorthand: ``get_clock().time()``."""
    return _clock.time()
