"""On-hardware A/B selection of the paged-decode attention kernel.

Four candidates exist (``ops/pallas_attention.py``): live (the default:
a schedule that visits only live pages), v1 (BlockSpec page pipeline over
a fixed grid), v2 (chunked manual-DMA over a fixed grid) and v3 (v2 plus
the step's KV write fused into the kernel). Which one wins depends on the
chip generation, page size and pool residency — so the choice is made by
*measuring* on the deployment hardware, not hardcoded. Both ``bench.py``
and the TPU worker (``workers/tpu_worker.py``) call this module so
production workers get the same self-calibration the benchmark does —
throughput must not depend on an operator knowing ``LLMQ_DECODE_KERNEL``.

The probe always runs in a SUBPROCESS (``python -m
llmq_tpu.engine.kernel_autotune``): a chip belongs to one process at a
time, so the probing child must own it briefly and exit *before* the
calling process initialises its JAX backend. A caller that already holds
the chip gets no child (it could only fail or hang): the drivers say so
at ERROR level and the engine starts on the default kernel.

An explicit ``LLMQ_DECODE_KERNEL`` env var always wins. A probe that
fails — the child crashed, a candidate kernel did not compile, the
budget ran out — is reported at ERROR level with the child's last
output, never as a quiet win; the engine then starts on the default
(live / off / xla) and ``stats()["decode_kernel"]`` shows what runs.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import time
from typing import Optional


# The unset ``LLMQ_DECODE_KERNEL`` first: what a failed probe answers.
DECODE_KERNELS = ("live", "v1", "v2", "v3")


def run_ab(
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    num_layers: int,
    max_seqs: int,
    page_size: int,
    kv_dtype: str = "bfloat16",
) -> tuple:
    """In-process kernel A/B (the child-process body).

    The pool must NOT fit in VMEM (~128 MB) or every kernel looks
    infinitely fast (round-3 finding); ~300 MB per side with per-layer
    distinct pages defeats caching while leaving the caller's HBM alone.
    Returns ``("live", False)`` on a CPU run (nothing to measure). On a TPU
    any failure raises — a candidate that does not compile is a failure
    of the probe (the child exits non-zero), not a lost A/B.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.ops.attention import write_kv_pages
    from llmq_tpu.ops.pallas_attention import (
        paged_decode_attention_live,
        paged_decode_attention_pallas,
        paged_decode_attention_pallas_v2,
        paged_decode_attention_pallas_v3,
    )

    if jax.devices()[0].platform != "tpu":
        return "live", False  # Pallas candidates only differ on real TPUs

    H, NKV, D = num_heads, num_kv_heads, head_dim
    L = num_layers
    S = max_seqs
    PAGE = page_size
    PPS = 4
    # The v1-vs-v2/v3 trade is KV-bandwidth-bound, so the probe pool
    # must use the PRODUCTION pool dtype: an fp8 cache moves half the
    # bytes of bf16 and can rank the kernels differently.
    kvd = jnp.dtype(kv_dtype)
    per_page = PAGE * NKV * D * kvd.itemsize
    ctx = min(PPS * PAGE - 2, int(PAGE * 2.6))
    # Pool sizing. Two constraints pull apart: the pool must NOT fit
    # in VMEM (~128 MB) or every kernel looks infinitely fast, and
    # the page each sequence WRITES must be distinct across sequences
    # (all three candidates write the step's KV row; a collision on
    # the written page makes the XLA scatter — one winner — and the
    # fused v3 kernel — own row each — legitimately disagree,
    # spuriously tripping the numerics guard). READ pages may collide
    # freely: TPU DMAs stream from HBM either way, so timing is
    # unaffected. Prefer fully-distinct pages when a probe-sized HBM
    # budget allows; otherwise distinct written pages only (GQA
    # models with few KV heads have small pages — 300 MB is only
    # ~127 pages at qwen2.5-3b shapes, far under S*PPS).
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    budget = int(0.4 * limit) if limit else 6 * 2**30
    per_pool_page = 2 * L * per_page  # K and V sides, all layers
    p_full = S * PPS + 1
    p_budget = max(PPS * 4, min(budget // max(1, per_pool_page), 4096))
    # VMEM-defeating floor (~300 MiB pool): below it every kernel
    # times as cache-resident and the ranking is meaningless
    # (round-3 finding).
    p_floor = 300 * 2**20 // max(1, per_pool_page)
    wcol = (ctx - 1) // PAGE  # the page column the step writes into
    rng = np.random.default_rng(0)
    if p_budget >= p_full:
        P = max(p_full, p_floor)
        perm = rng.permutation(np.arange(1, P))[: S * PPS]
        bt = jnp.asarray(perm.reshape(S, PPS).astype(np.int32))
    elif p_budget >= S + 1:
        P = max(p_budget, p_floor, S + 1)
        pages = rng.integers(1, P, size=(S, PPS))
        pages[:, wcol] = rng.permutation(np.arange(1, P))[:S]
        bt = jnp.asarray(pages.astype(np.int32))
    else:
        print(
            f"kernel-autotune: pool budget {budget >> 20} MiB < "
            f"{S + 1} pages x {per_pool_page >> 10} KiB; skipping A/B",
            file=sys.stderr,
        )
        return "live", False
    def rnd(seed, shape, dtype=jnp.bfloat16):
        return jax.random.normal(jax.random.key(seed), shape, jnp.float32).astype(dtype)

    q = rnd(0, (S, H, D))
    kp = rnd(1, (L, P, PAGE, NKV, D), kvd)
    vp = rnd(2, (L, P, PAGE, NKV, D), kvd)
    kn = rnd(3, (S, NKV, D))
    vn = rnd(4, (S, NKV, D))
    cl = jnp.full((S,), ctx, jnp.int32)
    positions = (cl - 1)[:, None]
    w = jnp.asarray([1 << 30], jnp.int32)
    scale = D**-0.5

    # v1/v2 pay the separate XLA KV scatter the engine runs before
    # them; v3 writes in-kernel. Time each candidate as the engine
    # would actually run it, so the ranking is apples-to-apples.
    # Donation matters: without it XLA must preserve the caller's
    # pool, which forces a full-pool copy around v3's in-place alias
    # and penalizes it artificially.
    @functools.partial(
        jax.jit, static_argnames=("which",), donate_argnums=(0, 1)
    )
    def step(kp, vp, li, *, which):
        if which == "v3":
            out, kp, vp = paged_decode_attention_pallas_v3(
                q, kp, vp, kn, vn, bt, cl, w, li, scale=scale
            )
            return out, kp, vp
        kp, vp = write_kv_pages(
            kp, vp, kn[:, None], vn[:, None], bt, positions, layer=li
        )
        kern = {
            "live": paged_decode_attention_live,
            "v1": paged_decode_attention_pallas,
            "v2": paged_decode_attention_pallas_v2,
        }[which]
        return kern(q, kp, vp, bt, cl, w, li, scale=scale), kp, vp

    def timeit(which, n=2):
        nonlocal kp, vp
        for li in range(L):
            out, kp, vp = step(kp, vp, jnp.int32(li), which=which)
        jax.block_until_ready(out)
        t0 = time.monotonic()
        for _ in range(n):
            for li in range(L):
                out, kp, vp = step(kp, vp, jnp.int32(li), which=which)
            jax.block_until_ready(out)
        return (time.monotonic() - t0) / (n * L)

    times = {which: timeit(which) for which in DECODE_KERNELS}
    # Numerics guard: per-candidate agreement with v1. Each guard call
    # rewrites the same (kn, vn) row at the same position, so the pool
    # state is identical for all three.
    outs = {}
    for which in DECODE_KERNELS:
        o, kp, vp = step(kp, vp, jnp.int32(0), which=which)
        outs[which] = o.astype(jnp.float32)
    diffs = {
        a: float(jnp.max(jnp.abs(outs[a] - outs["v1"])))
        for a in DECODE_KERNELS
    }
    # The default holds unless another beats it by 8 % (and v1 holds
    # against a default that disagrees with it).
    choice = "live" if diffs["live"] < 0.05 else "v1"
    for cand in DECODE_KERNELS[1:]:
        if times[cand] < 0.92 * times[choice] and diffs[cand] < 0.05:
            choice = cand
    for arr in (q, kp, vp, kn, vn, *outs.values()):
        arr.delete()
    shown = " ".join(f"{k}={v*1e3:.3f}ms" for k, v in times.items())
    dshown = " ".join(f"{k}|diff|={v:.2e}" for k, v in diffs.items())
    print(
        f"kernel-autotune: decode A/B {shown} per layer ({dshown}) "
        f"-> {choice}",
        file=sys.stderr,
    )
    return choice, True


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

    Same contract as :func:`autotune_decode_kernel`: returns the winning
    mode ("on"/"off"), or ``None`` when the probe does not apply (a CPU
    run, ``LLMQ_KERNEL_AUTOTUNE=0``) or cannot run (this process holds
    the chip — reported loudly); a failed or timed-out probe is reported
    loudly and returns "off" (the literal-GSPMD default). Deliberately does NOT short-circuit on ``LLMQ_TP_OVERLAP``
    — env precedence belongs to ``ops/dispatch.resolve_tp_overlap``,
    whose ``auto`` branch only reaches here when no pin is set. Call it
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


def autotune_decode_kernel(
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    num_layers: int,
    max_seqs: int = 192,
    page_size: int = 128,
    kv_dtype: str = "bfloat16",
    timeout_s: Optional[float] = None,
    logger=None,
) -> Optional[str]:
    """Subprocess A/B driver for callers that have NOT yet initialised a
    JAX backend (one process per chip — see module docstring).

    Returns the winning kernel name, or ``None`` when the probe does not
    apply (explicit ``LLMQ_DECODE_KERNEL`` set, a CPU run, or
    ``LLMQ_KERNEL_AUTOTUNE=0``) or cannot run (this process holds the
    chip — reported loudly). A failed or timed-out probe is reported
    loudly and returns the default, ``"live"``. The caller is expected to
    export the choice via ``LLMQ_DECODE_KERNEL`` before building its
    engine.
    """
    if os.environ.get("LLMQ_DECODE_KERNEL"):
        return None
    if os.environ.get("LLMQ_KERNEL_AUTOTUNE", "1").lower() in ("0", "false"):
        return None
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return None  # CPU runs take the XLA attention path anyway
    return _run_probe_child(
        [
            str(num_heads),
            str(num_kv_heads),
            str(head_dim),
            str(num_layers),
            str(max_seqs),
            str(page_size),
            str(kv_dtype),
        ],
        DECODE_KERNELS,
        DECODE_KERNELS[0],
        "decode kernel",
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


def _cache_key(shapes: tuple, identity: str, kv_dtype: str) -> str:
    h, kv, d, layers, seqs, page = shapes
    return (
        f"decode:h{h}:kv{kv}:d{d}:l{layers}:s{seqs}:p{page}"
        f":{kv_dtype}:{identity}"
    )


def _tp_overlap_cache_key(
    hidden: int, inter: int, seqs: int, tp: int, dtype: str, identity: str
) -> str:
    return f"tpovl:h{hidden}:i{inter}:s{seqs}:tp{tp}:{dtype}:{identity}"


def resolve_choice(
    shapes: tuple, identity: str, measure, kv_dtype: str = "bfloat16",
    *, key: Optional[str] = None, valid: tuple = DECODE_KERNELS
) -> str:
    """Cache-or-measure for the probing child. ``measure()`` must return
    ``(choice, measured)`` — only MEASURED results are ever stored (the
    A/B's internal failure fallbacks must not pin a stale default).

    ``key``/``valid`` generalize the cache beyond the decode-kernel probe
    (the tp-overlap A/B passes its own key and ``("on", "off")``);
    defaults keep the original decode-kernel behaviour."""
    import json

    path = cache_path_from_env()
    key = key if key is not None else _cache_key(shapes, identity, kv_dtype)
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
                (),
                identity,
                measure_overlap,
                dtype,
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
                (),
                identity,
                measure_int4,
                key=_int4_matmul_cache_key(
                    hidden, inter, seqs, group, identity
                ),
                valid=("pallas", "xla"),
            )
        )
        return

    shapes = tuple(int(a) for a in sys.argv[1:7])
    kv_dtype = sys.argv[7] if len(sys.argv) > 7 else "bfloat16"
    h, kv, d, layers, seqs, page = shapes
    dev = jax.devices()[0]
    identity = f"{dev.device_kind or dev.platform}/jax{jax.__version__}"

    def measure():
        return run_ab(
            num_heads=h,
            num_kv_heads=kv,
            head_dim=d,
            num_layers=layers,
            max_seqs=seqs,
            page_size=page,
            kv_dtype=kv_dtype,
        )

    print(resolve_choice(shapes, identity, measure, kv_dtype))


if __name__ == "__main__":
    _main()
