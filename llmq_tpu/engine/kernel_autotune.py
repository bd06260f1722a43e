"""On-hardware A/B probes for ``tp_overlap=auto`` and the int4 matmul.

Which of two programs wins (GSPMD's all-reduces or the ppermute rings;
the XLA dequantize-then-matmul or the dequant-in-VMEM kernel) depends on
the chip generation and the shapes — so ``auto`` is resolved by
*measuring* on the deployment hardware, not hardcoded.

A probe always runs in a SUBPROCESS (``python -m
llmq_tpu.engine.kernel_autotune <mode> ...``): a chip belongs to one
process at a time, so the probing child must own it briefly and exit
*before* the calling process initialises its JAX backend. A caller that
already holds the chip gets no child (it could only fail or hang): the
drivers say so at ERROR level and the engine starts on the default.

A probe that fails — the child crashed, a candidate did not compile, the
budget ran out — is reported at ERROR level with the child's last
output, never as a quiet win; the engine then starts on the default
(off / xla).
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import time
from typing import Optional


def run_tp_overlap_ab(
    *,
    hidden_size: int,
    intermediate_size: int,
    max_seqs: int = 192,
    num_layers: int = 8,
    dtype: str = "bfloat16",
) -> tuple:
    """In-process GSPMD-vs-ring A/B for ``tp_overlap`` (the child body).

    Times a decode-shaped row-parallel layer pair — o_proj-like [S, H] x
    [H, H] and down_proj-like [S, I] x [I, H] with a column-parallel up
    projection between them, chained over ``num_layers`` so nothing can
    be elided — once with GSPMD's all-reduces and once with the
    ``ops/collective_matmul`` ppermute rings, over ALL visible devices as
    the tp axis. Returns ``("off", False)`` on a CPU run or where the
    shapes do not divide the device count; ``measured`` is True only for
    a real timing. On a TPU a failure raises (the child exits non-zero).
    """
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if jax.devices()[0].platform != "tpu":
        return "off", False  # ICI overlap is the whole point
    from llmq_tpu.ops import collective_matmul as cm
    from llmq_tpu.parallel.mesh import TP_AXIS, make_mesh

    tp = len(jax.devices())
    if tp <= 1 or hidden_size % tp or intermediate_size % tp:
        return "off", False
    mesh = make_mesh(tensor_parallel=tp)
    plan = cm.ring_plan(mesh)
    H, I, S = hidden_size, intermediate_size, max_seqs
    dt = jnp.dtype(dtype)

    def rnd(seed, shape, spec):
        arr = jax.random.normal(
            jax.random.key(seed), shape, jnp.float32
        ).astype(dt) * (0.5 / shape[0] ** 0.5)
        return jax.device_put(arr, NamedSharding(mesh, spec))

    wo = rnd(0, (H, H), P(TP_AXIS, None))  # o_proj-like, row-parallel
    wu = rnd(1, (H, I), P(None, TP_AXIS))  # up-like, column-parallel
    wd = rnd(2, (I, H), P(TP_AXIS, None))  # down-like, row-parallel
    x0 = rnd(3, (S, H), P(None, None))

    @functools.partial(jax.jit, static_argnames=("which",))
    def run(h, *, which):
        ring = which == "ring"

        def mm(a, w):
            return cm.row_parallel_matmul(a, w, plan if ring else None)

        def layer(_, h):
            h = h + mm(h, wo)
            # Column-parallel up stays GSPMD for BOTH candidates (the
            # model keeps it GSPMD too); its [S, I] output is
            # tp-sharded, which is exactly the ring's down input spec.
            return h + mm(h @ wu, wd)

        return jax.lax.fori_loop(0, num_layers, layer, h)

    def timeit(which, n=10):
        jax.block_until_ready(run(x0, which=which))
        t0 = time.monotonic()
        for _ in range(n):
            out = run(x0, which=which)
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / (n * num_layers)

    times = {which: timeit(which) for which in ("gspmd", "ring")}
    diff = float(
        jnp.max(
            jnp.abs(
                run(x0, which="ring").astype(jnp.float32)
                - run(x0, which="gspmd").astype(jnp.float32)
            )
        )
    )
    # The ring must win by a real margin (5%) AND agree numerically
    # (different reduction order, so a loose tolerance — greedy
    # token parity is asserted elsewhere, this guards against a
    # broken ring, not ulps).
    choice = (
        "on" if times["ring"] < 0.95 * times["gspmd"] and diff < 0.5
        else "off"
    )
    shown = " ".join(f"{k}={v*1e6:.1f}us" for k, v in times.items())
    print(
        f"kernel-autotune: tp-overlap A/B {shown} per layer "
        f"(tp={tp}, |diff|={diff:.2e}) -> {choice}",
        file=sys.stderr,
    )
    return choice, True


def run_int4_matmul_ab(
    *,
    hidden_size: int,
    intermediate_size: int,
    max_seqs: int = 192,
    group_size: int = 128,
) -> tuple:
    """In-process Pallas-vs-XLA A/B for the int4 group-quantized matmul
    (the child body).

    Times a decode-shaped MLP projection — [S, H] x [H, I] with
    per-group scale+zero int4 weights — as the XLA dequantize-then-
    matmul and as the dequant-in-VMEM kernel
    (``ops/pallas_matmul.int4_matmul_pallas``). Decode is weight-stream
    bound, so whichever streams the packed bytes faster wins. Returns
    ``("xla", False)`` on a CPU run (interpret-mode timings are
    meaningless); ``measured`` is True only for a real timing. On a TPU
    a failure raises — the kernel not compiling is a failure to report,
    not an A/B the XLA path won.
    """
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        return "xla", False
    from llmq_tpu.models import quant as qm
    from llmq_tpu.ops.pallas_matmul import int4_matmul_pallas

    H, I, S = hidden_size, intermediate_size, max_seqs
    w = jax.random.normal(jax.random.key(0), (H, I), jnp.float32)
    qt = qm.quantize_array_int4(w, group_size=group_size)
    x = jax.random.normal(jax.random.key(1), (S, H), jnp.bfloat16)

    xla_f = jax.jit(
        lambda: x
        @ qm.dequantize_int4_parts(
            qt["q"], qt["scale"], qt["zero"], jnp.bfloat16
        )
    )
    pallas_f = jax.jit(
        lambda: int4_matmul_pallas(x, qt["q"], qt["scale"], qt["zero"])
    )

    def timeit(f, n=10):
        jax.block_until_ready(f())
        t0 = time.monotonic()
        for _ in range(n):
            out = f()
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / n

    times = {"xla": timeit(xla_f), "pallas": timeit(pallas_f)}
    diff = float(
        jnp.max(
            jnp.abs(
                pallas_f().astype(jnp.float32)
                - xla_f().astype(jnp.float32)
            )
        )
    )
    # Same contract as the tp-overlap A/B: a real margin (5%) AND
    # numerical agreement (different accumulation order — the
    # kernel compensates in f32, XLA reduces in bf16 — so the bound
    # guards against a broken kernel, not ulps).
    choice = (
        "pallas"
        if times["pallas"] < 0.95 * times["xla"] and diff < 0.5
        else "xla"
    )
    shown = " ".join(f"{k}={v*1e6:.1f}us" for k, v in times.items())
    print(
        f"kernel-autotune: int4-matmul A/B {shown} "
        f"(HxI {H}x{I}, S={S}, |diff|={diff:.2e}) -> {choice}",
        file=sys.stderr,
    )
    return choice, True


def _int4_matmul_cache_key(
    hidden: int, inter: int, seqs: int, group: int, identity: str
) -> str:
    return f"int4mm:h{hidden}:i{inter}:s{seqs}:g{group}:{identity}"


def _probe_blocked() -> Optional[str]:
    """Why a probing child cannot run from this process, or None. A chip
    belongs to one process: once this one has initialised a JAX backend
    a child that needs the chip can only fail or hang."""
    if "jax" not in sys.modules:
        return None
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return (
            "this process has already initialised JAX and holds the chip; "
            "the probing child has to run before that"
        )
    return None


def _run_probe_child(
    argv: list, valid: tuple, default: str, what: str, timeout_s, logger
) -> Optional[str]:
    """Run one probing child and return its choice. Whatever goes wrong —
    a non-zero exit (a candidate did not compile, the chip was taken), an
    answer outside ``valid``, the budget running out — is reported at
    ERROR level with the end of the child's output; the caller then gets
    ``default``. From a process that already holds the chip no child is
    started: that too is an ERROR, and the answer is None (nothing was
    probed, the engine's own default applies)."""

    def report(level: int, msg: str) -> None:
        if logger is not None:
            logger.log(level, msg)
        else:
            print(f"kernel-autotune: {msg}", file=sys.stderr)

    blocked = _probe_blocked()
    if blocked is not None:
        report(
            logging.ERROR,
            f"{what} probe NOT RUN ({blocked}); starting on the default "
            f"({default})",
        )
        return None
    if timeout_s is None:
        timeout_s = float(os.environ.get("LLMQ_BENCH_AB_TIMEOUT", 420))
    cmd = [sys.executable, "-m", "llmq_tpu.engine.kernel_autotune", *argv]
    try:
        proc = subprocess.run(
            cmd, timeout=timeout_s, capture_output=True, text=True
        )
    except subprocess.TimeoutExpired as exc:
        tail = (exc.stderr or b"")[-1500:]
        if isinstance(tail, bytes):
            tail = tail.decode("utf-8", "replace")
        report(
            logging.ERROR,
            f"{what} probe FAILED: no answer in {timeout_s:.0f}s; starting "
            f"on {default}. Child stderr ends:\n{tail}",
        )
        return default
    lines = proc.stdout.strip().splitlines()
    choice = lines[-1] if lines else ""
    if proc.returncode == 0 and choice in valid:
        detail = (proc.stderr.strip().splitlines() or ["no detail"])[-1]
        report(logging.INFO, f"{what}: {choice} (A/B {detail})")
        return choice
    report(
        logging.ERROR,
        f"{what} probe FAILED: child exit {proc.returncode}, answer "
        f"{choice!r}; starting on {default}. Child stderr ends:\n"
        f"{proc.stderr[-1500:]}",
    )
    return default


def autotune_tp_overlap(
    *,
    hidden_size: int,
    intermediate_size: int,
    max_seqs: int = 192,
    tp: Optional[int] = None,
    dtype: str = "bfloat16",
    timeout_s: Optional[float] = None,
    logger=None,
) -> Optional[str]:
    """Subprocess A/B driver for ``tp_overlap=auto``.

    Returns the winning mode ("on"/"off"), or ``None`` when the probe
    does not apply (a CPU run, ``LLMQ_KERNEL_AUTOTUNE=0``) or cannot run
    (this process holds the chip — reported loudly); a failed or
    timed-out probe is reported loudly and returns "off" (the
    literal-GSPMD default). Deliberately does NOT short-circuit on
    ``LLMQ_TP_OVERLAP`` — env precedence belongs to
    ``ops/dispatch.resolve_tp_overlap``, whose ``auto`` branch only
    reaches here when no pin is set. Call it
    BEFORE the parent initialises its backend (the worker pattern).
    """
    if os.environ.get("LLMQ_KERNEL_AUTOTUNE", "1").lower() in ("0", "false"):
        return None
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return None  # no ICI to overlap
    return _run_probe_child(
        [
            "tp-overlap",
            str(hidden_size),
            str(intermediate_size),
            str(max_seqs),
            dtype,
        ],
        ("on", "off"),
        "off",
        "tp_overlap",
        timeout_s,
        logger,
    )


# --- per-host result cache (lives in the CHILD: only it knows which
# chip + toolchain it measured on) ------------------------------------------


def cache_path_from_env():
    """None when disabled (``LLMQ_AUTOTUNE_CACHE=0``); by default one
    file inside the checkout, next to the compile cache
    (``utils/platform.DEFAULT_CACHE_DIR``) — never under ``$HOME``, so no
    state outside the tree decides which kernel runs. Fleets restart
    workers constantly (SLURM arrays, preemption recovery) and the chip
    doesn't change under them; a checkout may still be NFS-shared ACROSS
    a fleet mixing chip generations, so entries carry the measuring chip
    + jax version in the key (see :func:`resolve_choice`) and never match
    foreign hardware."""
    from pathlib import Path

    from llmq_tpu.utils.platform import DEFAULT_CACHE_DIR

    env = os.environ.get("LLMQ_AUTOTUNE_CACHE", "")
    if env.lower() in ("0", "false"):
        return None
    return Path(env) if env else DEFAULT_CACHE_DIR / "autotune.json"


def _tp_overlap_cache_key(
    hidden: int, inter: int, seqs: int, tp: int, dtype: str, identity: str
) -> str:
    return f"tpovl:h{hidden}:i{inter}:s{seqs}:tp{tp}:{dtype}:{identity}"


def resolve_choice(measure, *, key: str, valid: tuple) -> str:
    """Cache-or-measure for the probing child. ``measure()`` must return
    ``(choice, measured)`` — only MEASURED results are ever stored (the
    A/B's internal failure fallbacks must not pin a stale default).
    ``key`` names the probe, its shapes and the measuring chip + jax
    version; a cached entry outside ``valid`` is measured again."""
    import json

    path = cache_path_from_env()
    if path is not None and path.exists():
        try:
            entry = json.loads(path.read_text()).get(key)
            if entry and entry.get("choice") in valid:
                print(
                    f"kernel-autotune: cached A/B for this chip -> "
                    f"{entry['choice']} ({path})",
                    file=sys.stderr,
                )
                return entry["choice"]
        except Exception:  # noqa: BLE001 — corrupt cache = re-measure
            pass
    choice, measured = measure()
    if path is not None and measured:
        try:
            path.parent.mkdir(parents=True, exist_ok=True)
            try:
                data = json.loads(path.read_text()) if path.exists() else {}
            except Exception:  # noqa: BLE001 — corrupt file: start over
                data = {}
            data[key] = {"choice": choice}
            path.write_text(json.dumps(data, indent=1))
        except Exception:  # noqa: BLE001 — cache is best-effort
            pass
    return choice


def _main() -> None:
    import jax

    from llmq_tpu.utils.platform import enable_compile_cache, on_tpu

    enable_compile_cache()
    # A child that wanted the chip and came up on the CPU (the chip was
    # taken) must fail here, not answer as if it had measured; a CPU
    # run on purpose (JAX_PLATFORMS=cpu, the preflight) goes on to the
    # unmeasured defaults below.
    on_tpu()

    if len(sys.argv) > 1 and sys.argv[1] == "tp-overlap":
        # tp-overlap mode: argv = ["tp-overlap", hidden, inter, seqs,
        # dtype?]. Must print a mode and exit 0 even on CPU (the
        # preflight suite executes every scripted leg in tiny mode).
        hidden, inter, seqs = (int(a) for a in sys.argv[2:5])
        dtype = sys.argv[5] if len(sys.argv) > 5 else "bfloat16"
        dev = jax.devices()[0]
        identity = f"{dev.device_kind or dev.platform}/jax{jax.__version__}"
        tp = len(jax.devices())

        def measure_overlap():
            return run_tp_overlap_ab(
                hidden_size=hidden,
                intermediate_size=inter,
                max_seqs=seqs,
                dtype=dtype,
            )

        print(
            resolve_choice(
                measure_overlap,
                key=_tp_overlap_cache_key(
                    hidden, inter, seqs, tp, dtype, identity
                ),
                valid=("on", "off"),
            )
        )
        return

    if len(sys.argv) > 1 and sys.argv[1] == "int4-matmul":
        # int4-matmul mode: argv = ["int4-matmul", hidden, inter, seqs,
        # group?]. Must print a mode and exit 0 even on CPU (the
        # preflight suite executes every scripted leg in tiny mode).
        hidden, inter, seqs = (int(a) for a in sys.argv[2:5])
        group = int(sys.argv[5]) if len(sys.argv) > 5 else 128
        dev = jax.devices()[0]
        identity = f"{dev.device_kind or dev.platform}/jax{jax.__version__}"

        def measure_int4():
            return run_int4_matmul_ab(
                hidden_size=hidden,
                intermediate_size=inter,
                max_seqs=seqs,
                group_size=group,
            )

        print(
            resolve_choice(
                measure_int4,
                key=_int4_matmul_cache_key(
                    hidden, inter, seqs, group, identity
                ),
                valid=("pallas", "xla"),
            )
        )
        return

    sys.exit(f"kernel-autotune: want tp-overlap|int4-matmul: {sys.argv[1:]}")


if __name__ == "__main__":
    _main()
