"""Which device this process runs on, and where its compiled programs live.

The rule is plain JAX's: on the chip ``JAX_PLATFORMS`` is unset and JAX
takes the TPU; tests and CPU rehearsals set ``JAX_PLATFORMS=cpu`` (plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for meshes). Nothing
here ever switches platform. A process that did not ask for the CPU and
did not get a TPU is an error, not a slower run: every kernel/backend
choice on the worker → engine → gateway path goes through :func:`on_tpu`.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fixed, git-ignored home of the persistent compile cache when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset. Derived from the package
#: location only: a directory that moves between runs (a temporary
#: directory, one named after the process or the clock, one under $HOME
#: when HOME changes) is never found again.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cpu_requested() -> bool:
    """True iff this process was told to run on the host CPU
    (``JAX_PLATFORMS=cpu``). Does not initialise a backend."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def on_tpu() -> bool:
    """True on a TPU; False on the CPU *when the CPU was asked for*.

    Anything else — JAX fell back to the CPU because the chip was busy or
    absent, or came up on a backend this code was never run on — raises,
    so a Pallas kernel can never run interpreted, nor give way to the XLA
    reference, on a machine that was meant to use its chip.
    """
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if backend == "cpu" and cpu_requested():
        return False
    raise RuntimeError(
        f"JAX came up on {backend!r} but JAX_PLATFORMS="
        f"{jax.config.jax_platforms!r} did not ask for the CPU: no TPU was "
        "found (absent, or held by another process). Set JAX_PLATFORMS=cpu "
        "to run on the CPU on purpose."
    )


def enable_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its one directory.

    Call once per process, before the first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and this
    sets no other; otherwise the cache lives in :data:`DEFAULT_CACHE_DIR`.
    A CPU run without the variable keeps JAX's default (no cache), so
    tests behave as before. Returns the directory in use (None = off).
    """
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        if cpu_requested():
            return None
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    # Keep every program, not only those that took over a second (JAX's
    # default): a cold start compiles dozens of small ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileMeter:
    """Counts what JAX compiles in this process: seconds spent tracing,
    lowering and compiling (cache reads included), and persistent-cache
    requests / hits / misses (a miss is counted when the entry is
    written). JAX offers no way to remove a listener, so create one per
    process and read :meth:`snapshot` deltas."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.requests = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration: float, **_: object) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration

    def snapshot(self) -> dict:
        return {
            "compile_seconds": round(self.seconds, 3),
            "cache_requests": self.requests,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }
