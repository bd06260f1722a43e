"""Device-fault taxonomy shared by the engine and the workers.

The engine raises device-side failures (a wedged dispatch detected by
the watchdog, an XLA runtime error, an HBM allocation failure, a mesh /
topology mismatch); the worker classifies them into a small fixed set of
machine-readable reasons that flow into ``ErrorInfo.failure_reason``,
dead-letter / quarantine headers (``x-failure-reason``), and the poison
fingerprint. Kept dependency-free (no jax, no pydantic) so the generic
worker base can import it without dragging the engine stack in.
"""

from __future__ import annotations

from typing import Optional

# Failure classes. Values are wire-visible (headers, ErrorInfo, traces).
FAULT_HUNG = "hung_dispatch"
FAULT_XLA = "xla_runtime_error"
FAULT_OOM = "hbm_oom"
FAULT_MESH = "mesh_error"
FAULT_NUMERICAL = "numerical_fault"

# Every class above is recoverable by an in-process engine rebuild; the
# tuple exists so callers can gate on membership rather than string sets.
DEVICE_FAULT_REASONS = (
    FAULT_HUNG,
    FAULT_XLA,
    FAULT_OOM,
    FAULT_MESH,
    FAULT_NUMERICAL,
)


class HungDispatchError(RuntimeError):
    """A watchdog-bracketed device call exceeded its deadline.

    Raised on the engine thread when the overdue call eventually
    returns (a transient stall): the caller gets a classifiable
    exception instead of silently-late results. A call that never
    returns cannot be unwound — the watchdog's trip state and the
    heartbeat's ``last_dispatch_ok_age_s`` surface it instead, and the
    process-level recovery (janitor reclaim / hard exit) takes over.
    """

    def __init__(self, kind: str, elapsed: float, deadline: float):
        super().__init__(
            f"device dispatch {kind!r} exceeded its watchdog deadline "
            f"({elapsed:.2f}s elapsed > {deadline:.2f}s allowed)"
        )
        self.kind = kind
        self.elapsed = elapsed
        self.deadline = deadline


class LogitGuardError(RuntimeError):
    """An on-device numerics guard flagged the logits of a dispatch
    (non-finite values, out-of-bound magnitude, or an entropy collapse).

    Raised on the engine thread when the guard word fetched alongside a
    dispatch's tokens trips a threshold. Carries enough context for
    blame attribution: which check fired, the dispatch kind, and the
    request ids that were riding the flagged dispatch (``suspects``) —
    the recovery path re-runs exactly those on a rebuilt core to decide
    job-poison vs device-fault.
    """

    def __init__(
        self,
        check: str,
        detail: str,
        suspects: tuple = (),
        kind: str = "",
    ):
        super().__init__(
            f"logit guard tripped [{check}] on {kind or 'dispatch'}: {detail}"
        )
        self.check = check
        self.detail = detail
        self.suspects = tuple(suspects)
        self.kind = kind


class StepCompileError(RuntimeError):
    """A step program the compiler refused: it does not fit the device's
    memory, a Pallas kernel failed to lower, or Mosaic rejected it.

    Not a device fault. The same program fails the same way on a rebuilt
    engine and for every job, so nothing is retried: the engine stops,
    in-flight jobs go back to the queue, and the worker exits non-zero
    with this error — a worker that can compile nothing must not look
    like a worker that is up."""


class DeviceFaultError(RuntimeError):
    """A classified device fault the engine could not recover from
    in-process (rebuild unavailable, rebuild failed, or the OOM
    degradation ladder ran dry). The worker maps ``failure_reason``
    straight into its dead-letter / quarantine headers."""

    def __init__(self, failure_reason: str, message: str):
        super().__init__(message)
        self.failure_reason = failure_reason


# What the installed toolchain says when it refuses a program
# (tests/test_tpu_compile.py pins the first two against the real
# compiler): XLA's compile-time memory check, Mosaic's kernel compile,
# and the Pallas→Mosaic lowering's own shape rules.
_COMPILE_MARKERS = (
    "xla:tpu compile",
    "mosaic failed to compile",
    "pallas tpu lowering",
    "loweringexception",
)


def is_compile_failure(exc: BaseException) -> bool:
    """True when ``exc`` says a program could not be *compiled* — as
    opposed to a fault while a compiled program ran, which keeps the
    device-fault handling below. Textual for the same reason as
    :func:`classify_failure`."""
    if isinstance(exc, StepCompileError):
        return True
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(marker in text for marker in _COMPILE_MARKERS)


def classify_failure(exc: BaseException) -> Optional[str]:
    """Map an exception escaping the engine step loop to a device-fault
    class, or ``None`` for ordinary application errors (which keep their
    generic handling). Matching is textual beyond the two typed cases:
    jaxlib's ``XlaRuntimeError`` carries its status code ("RESOURCE_
    EXHAUSTED", "INTERNAL", ...) in the message, and we must not import
    jaxlib here just to isinstance-check it."""
    if isinstance(exc, HungDispatchError):
        return FAULT_HUNG
    if isinstance(exc, LogitGuardError):
        return FAULT_NUMERICAL
    if isinstance(exc, DeviceFaultError):
        return exc.failure_reason
    if is_compile_failure(exc):
        return None  # never retried as a device fault: see StepCompileError
    text = f"{type(exc).__name__}: {exc}".lower()
    # Order matters: a real HBM OOM *is* an XlaRuntimeError, so the
    # allocation signature must win over the generic XLA match.
    if "resource_exhausted" in text or "out of memory" in text:
        return FAULT_OOM
    if "mesh" in text or "device topology" in text or "slice_config" in text:
        return FAULT_MESH
    if "xlaruntimeerror" in text or "jaxruntimeerror" in text or (
        "xla" in text and "error" in text
    ):
        return FAULT_XLA
    return None
