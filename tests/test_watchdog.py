"""Unit tests for the dispatch watchdog and the device-fault taxonomy.

The watchdog is pure host-side machinery (threads + monotonic clocks),
so everything here runs without building an engine: deadline math
against synthetic histogram percentiles, trip detection on an
artificially slow bracket, and the exception-precedence contract of the
guard's ``__exit__``.
"""

import threading
import time

import pytest

from llmq_tpu.core.faults import (
    FAULT_HUNG,
    FAULT_MESH,
    FAULT_OOM,
    FAULT_XLA,
    DeviceFaultError,
    HungDispatchError,
    StepCompileError,
    classify_failure,
)
from llmq_tpu.engine.watchdog import NO_GUARD, DispatchWatchdog

pytestmark = pytest.mark.unit


def make_watchdog(percentiles=None, **kw):
    table = percentiles or {}
    kw.setdefault("mult", 3.0)
    kw.setdefault("min_s", 0.05)
    kw.setdefault("poll_s", 0.005)
    return DispatchWatchdog(percentile_fn=table.get, **kw)


class TestDeadlineMath:
    def test_p99_times_mult_when_above_floor(self):
        wd = make_watchdog({"decode_block": 2.0}, mult=3.0, min_s=0.5)
        try:
            assert wd.deadline_for("decode_block") == pytest.approx(6.0)
        finally:
            wd.stop()

    def test_floor_wins_over_small_p99(self):
        wd = make_watchdog({"decode_block": 0.01}, mult=3.0, min_s=4.0)
        try:
            assert wd.deadline_for("decode_block") == pytest.approx(4.0)
        finally:
            wd.stop()

    def test_no_history_uses_floor(self):
        wd = make_watchdog({}, min_s=7.5)
        try:
            # Kinds that never get a histogram (snapshot gathers before
            # any dispatch) fall back to the floor alone.
            assert wd.deadline_for("snapshot_gather") == pytest.approx(7.5)
        finally:
            wd.stop()

    def test_percentile_error_falls_back_to_floor(self):
        def boom(kind):
            raise RuntimeError("histogram unavailable")

        wd = DispatchWatchdog(
            mult=3.0, min_s=1.25, percentile_fn=boom, poll_s=0.005
        )
        try:
            assert wd.deadline_for("prefill") == pytest.approx(1.25)
        finally:
            wd.stop()


class TestGuard:
    def test_overrun_bracket_raises_hung_dispatch(self):
        trips = []
        wd = make_watchdog(
            {}, min_s=0.05, on_trip=lambda *a: trips.append(a)
        )
        try:
            with pytest.raises(HungDispatchError) as exc_info:
                with wd.guard("decode_block"):
                    time.sleep(0.3)
            assert classify_failure(exc_info.value) == FAULT_HUNG
            assert exc_info.value.kind == "decode_block"
            assert wd.trips == 1
            assert trips and trips[0][0] == "decode_block"
        finally:
            wd.stop()

    def test_fast_bracket_is_clean_and_updates_last_ok(self):
        wd = make_watchdog({}, min_s=5.0)
        try:
            time.sleep(0.05)
            before = wd.last_ok_age_s()
            with wd.guard("prefill"):
                pass
            assert wd.trips == 0
            assert wd.last_ok_age_s() < before
        finally:
            wd.stop()

    def test_inflight_exception_takes_precedence_over_trip(self):
        wd = make_watchdog({}, min_s=0.05)
        try:
            # The dispatch both overruns AND raises: the raise is the
            # richer signal (real XLA error text) and must not be
            # swallowed by the trip.
            with pytest.raises(ValueError, match="real failure"):
                with wd.guard("decode_block"):
                    time.sleep(0.3)
                    raise ValueError("real failure")
            assert wd.trips == 1  # the trip is still counted
        finally:
            wd.stop()

    def test_failed_bracket_does_not_update_last_ok(self):
        wd = make_watchdog({}, min_s=5.0)
        try:
            with wd.guard("prefill"):
                pass
            with pytest.raises(ValueError):
                with wd.guard("decode_block"):
                    time.sleep(0.1)
                    raise ValueError("boom")
            # last_ok reflects the clean prefill, not the failed decode.
            assert wd.last_ok_age_s() >= 0.1
        finally:
            wd.stop()

    def test_wedged_kind_visible_mid_bracket(self):
        wd = make_watchdog({}, min_s=0.05)
        entered = threading.Event()
        release = threading.Event()

        def wedge():
            try:
                with wd.guard("verify"):
                    entered.set()
                    release.wait(timeout=5.0)
            except HungDispatchError:
                pass

        t = threading.Thread(target=wedge)
        t.start()
        try:
            assert entered.wait(timeout=2.0)
            deadline = time.monotonic() + 2.0
            while wd.wedged_kind() is None and time.monotonic() < deadline:
                time.sleep(0.01)
            # While the call is stuck the side thread sees the wedge...
            assert wd.wedged_kind() == "verify"
        finally:
            release.set()
            t.join(timeout=5.0)
            wd.stop()
        # ...and once the bracket exits, the wedge surface clears.
        assert wd.wedged_kind() is None


class TestDefaultOff:
    def test_engine_config_defaults_off(self):
        from llmq_tpu.engine.engine import EngineConfig

        cfg = EngineConfig()
        assert cfg.watchdog_mult == 0.0
        assert cfg.watchdog_min_s > 0

    def test_engine_config_rejects_bad_knobs(self):
        from llmq_tpu.engine.engine import EngineConfig

        with pytest.raises(ValueError, match="watchdog_mult"):
            EngineConfig(watchdog_mult=-1.0)
        with pytest.raises(ValueError, match="watchdog_min_s"):
            EngineConfig(watchdog_min_s=0.0)

    def test_no_guard_is_shared_reusable_noop(self):
        # The default-off bracket is one shared nullcontext: no state,
        # no allocation, reusable any number of times.
        for _ in range(3):
            with NO_GUARD:
                pass


class TestClassifyFailure:
    @pytest.mark.parametrize(
        "exc, want",
        [
            (HungDispatchError("decode_block", 9.0, 4.0), FAULT_HUNG),
            (
                RuntimeError(
                    "XlaRuntimeError: RESOURCE_EXHAUSTED: out of memory "
                    "allocating 1234 bytes"
                ),
                FAULT_OOM,
            ),
            (RuntimeError("XlaRuntimeError: INTERNAL: dispatch failed"), FAULT_XLA),
            (RuntimeError("mesh shape mismatch for collective"), FAULT_MESH),
            (ValueError("bad argument"), None),
            (KeyError("nope"), None),
            # What the compiler says when it REFUSES a program is not a
            # device fault (core/faults.is_compile_failure): the same
            # program fails on every rebuild. Texts as libtpu 0.0.34 and
            # jax 0.9.0 print them (tests/test_tpu_compile.py pins the
            # first against the real compiler).
            (
                RuntimeError(
                    "JaxRuntimeError: RESOURCE_EXHAUSTED: XLA:TPU compile "
                    "permanent error. Ran out of memory in memory space hbm. "
                    "Used 18.93G of 15.75G hbm."
                ),
                None,
            ),
            (RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel"), None),
            (
                ValueError(
                    "The Pallas TPU lowering currently requires that the last "
                    "two dimensions of your block shape are divisible by 8 "
                    "and 128"
                ),
                None,
            ),
            (StepCompileError("step program 'decode_step' does not compile"), None),
        ],
    )
    def test_mapping(self, exc, want):
        assert classify_failure(exc) == want

    def test_oom_wins_over_xla_wrapper(self):
        # A real HBM OOM *is* an XlaRuntimeError; the resource-exhausted
        # text must classify as OOM (the recoverable ladder), not as a
        # generic XLA error (the rebuild hammer).
        exc = RuntimeError(
            "jaxlib.xla_extension.XlaRuntimeError: RESOURCE_EXHAUSTED: "
            "Out of memory while trying to allocate"
        )
        assert classify_failure(exc) == FAULT_OOM

    def test_device_fault_error_carries_reason(self):
        err = DeviceFaultError(FAULT_XLA, "engine step failed: boom")
        assert err.failure_reason == FAULT_XLA
        assert "boom" in str(err)
