"""Is the tp-overlap ppermute ring worth turning on for this slice?

The chunked collective matmuls in ``ops/collective_matmul.py`` win only
when each ICI hop (one chunk's worth of ppermute) hides behind the next
chunk's local matmul. Whether that holds is a pure hardware question —
ICI hop latency vs MXU chunk time at decode-sized operands — so this
micro-bench measures both sides on the actual slice, per tp degree:

    1. raw collective latency/bandwidth at the decode activation shape:
       all-reduce (what GSPMD pays per row-parallel layer), its
       reduce-scatter + all-gather decomposition, and a single
       neighbour ppermute hop (the ring's unit of overlap)
    2. the ring row-parallel matmul (o_proj- and down_proj-shaped) A/B'd
       against the GSPMD matmul + all-reduce it replaces

    ring < gspmd  -> overlap pays on this slice: set LLMQ_TP_OVERLAP=on
                     (or tp_overlap=auto and let the worker A/B decide)
    ring >= gspmd -> GSPMD's fused all-reduce is already at the ICI
                     floor here; leave tp_overlap off

Same elision-proofing as profile_int8_matmul.py: every timed loop chains
iteration N's output into iteration N+1's input inside one jitted
fori_loop with the activation donated, so XLA cannot dead-code the
collectives, and measured ICI bandwidth above the chip's physical peak
rejects the run.
"""
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

if os.environ.get("JAX_PLATFORMS", "") == "cpu":
    # CPU smoke mode: the collectives need >1 device, so force a virtual
    # 8-way host platform (same trick as tests/conftest.py) before any
    # backend initialises.
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from llmq_tpu.ops import collective_matmul as cm
from llmq_tpu.parallel.mesh import TP_AXIS, make_mesh

ON_TPU = jax.default_backend() == "tpu"
if ON_TPU:
    S, H, I, N_ITERS = 192, 2048, 11008, 30
else:  # smoke-testable off-TPU
    S, H, I, N_ITERS = 16, 128, 256, 3
S = int(os.environ.get("PROF_S", S))
H = int(os.environ.get("PROF_H", H))
I = int(os.environ.get("PROF_I", I))  # noqa: E741
N_ITERS = int(os.environ.get("PROF_N", N_ITERS))
DTYPE = jnp.bfloat16

NDEV = len(jax.devices())
if NDEV < 2:
    print(f"collectives: {NDEV} device(s) visible; nothing to measure")
    sys.exit(0)


# Aggregate ICI bandwidth per chip, GB/s (datasheet order of magnitude).
# Effective collective bandwidth above this means the dependence chain
# failed and XLA elided hops — the number must not be trusted.
_ICI_PEAK_GBS = {
    "v2": 80.0,
    "v3": 130.0,
    "v4": 300.0,
    "v5 lite": 200.0,
    "v5e": 200.0,
    "v5p": 600.0,
    "v6 lite": 200.0,
    "v6e": 450.0,
}


def ici_peak_gbs():
    if not ON_TPU:
        return None  # CPU smoke mode: no meaningful peak to gate on
    kind = jax.devices()[0].device_kind.lower()
    for key in sorted(_ICI_PEAK_GBS, key=len, reverse=True):
        if key in kind:
            return _ICI_PEAK_GBS[key]
    return None


def reject_if_elided(label, gibs):
    peak = ici_peak_gbs()
    if peak is None:
        return
    gbs = gibs * (2**30 / 1e9)
    if gbs > 1.5 * peak:
        sys.exit(
            f"{label}: measured {gbs:.0f} GB/s effective ICI bandwidth"
            f" > 1.5x this chip's aggregate peak ({peak:.0f} GB/s) — the"
            " compiler elided hops; measurement rejected"
        )


def time_collective(mesh, spec, step, x_global, n=N_ITERS):
    """us/op for a shape-preserving collective ``step`` on local shards.

    The carry IS the collective's output, the loop runs inside the
    shard_map body, and the global input buffer is donated — each hop's
    result feeds the next, so no hop can be elided.
    """

    @partial(jax.jit, donate_argnums=(0,))
    def chained(xg):
        def inner(xl):
            return jax.lax.fori_loop(0, n, lambda _, c: step(c), xl)

        return cm._shard_mapped(inner, mesh, (spec,), spec)(xg)

    jax.block_until_ready(chained(jnp.copy(x_global)))  # compile
    fresh = jnp.copy(x_global)  # donated; copy made outside the clock
    t0 = time.monotonic()
    jax.block_until_ready(chained(fresh))
    return (time.monotonic() - t0) / n * 1e6


def time_matmul(f, x_sharded, w, n=N_ITERS):
    """us/op for a row-parallel matmul, template-style tiny-fold chain."""
    tiny = jnp.finfo(DTYPE).smallest_subnormal

    @partial(jax.jit, donate_argnums=(0,))
    def chained(xc):
        def body(_, c):
            ys = f(c, w)
            return c + ys.ravel()[:1].astype(c.dtype) * tiny

        return jax.lax.fori_loop(0, n, body, xc)

    jax.block_until_ready(chained(jnp.copy(x_sharded)))
    fresh = jnp.copy(x_sharded)
    t0 = time.monotonic()
    jax.block_until_ready(chained(fresh))
    return (time.monotonic() - t0) / n * 1e6


def bench_tp(tp):
    mesh = make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp])
    nbytes = S * H * jnp.dtype(DTYPE).itemsize
    x = jax.device_put(
        jax.random.normal(jax.random.key(0), (S, H), DTYPE),
        NamedSharding(mesh, P()),
    )
    x_sh = jax.device_put(x, NamedSharding(mesh, P(None, TP_AXIS)))
    chunk = H // tp
    fwd = [(j, (j + 1) % tp) for j in range(tp)]

    # Per-device ICI bytes for the ring algorithms XLA lowers to:
    # all-reduce moves 2(tp-1)/tp of the buffer, RS/AG (tp-1)/tp,
    # one ppermute hop moves exactly the local shard.
    legs = [
        (
            "all_reduce",
            P(),
            lambda c: jax.lax.psum(c, TP_AXIS) * (1.0 / tp),
            x,
            2 * (tp - 1) / tp * nbytes,
        ),
        (
            "reduce_scatter",
            P(None, TP_AXIS),
            # tile is a local copy (not ICI traffic); it slightly
            # overstates RS cost, identically at every tp degree.
            lambda c: jax.lax.psum_scatter(
                jnp.tile(c, (1, tp)), TP_AXIS, scatter_dimension=1, tiled=True
            )
            * (1.0 / tp),
            x_sh,
            (tp - 1) / tp * nbytes,
        ),
        (
            "all_gather",
            P(None, TP_AXIS),
            lambda c: jax.lax.dynamic_slice_in_dim(
                jax.lax.all_gather(c, TP_AXIS, axis=1, tiled=True),
                jax.lax.axis_index(TP_AXIS) * chunk,
                chunk,
                1,
            ),
            x_sh,
            (tp - 1) / tp * nbytes,
        ),
        (
            "ppermute_hop",
            P(None, TP_AXIS),
            lambda c: jax.lax.ppermute(c, TP_AXIS, fwd),
            x_sh,
            nbytes / tp,
        ),
    ]
    for name, spec, step, operand, bytes_moved in legs:
        us = time_collective(mesh, spec, step, operand)
        gibs = bytes_moved / (us / 1e6) / 2**30
        reject_if_elided(f"tp={tp} {name}", gibs)
        print(
            f"tp={tp}  {name:<14} [{S}x{H} bf16]  "
            f"{us:8.1f} us  {gibs:7.2f} GiB/s ICI-eff"
        )

    # Ring vs GSPMD row-parallel matmul at the two decode projection
    # shapes the overlap path rewrites (o_proj [H,H], down_proj [I,H]).
    plan = cm.ring_plan(mesh)
    repl = NamedSharding(mesh, P())
    verdicts = []
    for name, k_dim in (("o_proj", H), ("down_proj", I)):
        if k_dim % tp or H % tp:
            print(f"tp={tp}  {name}: {k_dim}x{H} not tp-divisible; skipped")
            continue
        w = jax.device_put(
            jax.random.normal(jax.random.key(1), (k_dim, H), DTYPE),
            NamedSharding(mesh, P(TP_AXIS, None)),
        )
        xk = jax.device_put(
            jax.random.normal(jax.random.key(2), (S, k_dim), DTYPE),
            NamedSharding(mesh, P(None, TP_AXIS)),
        )
        us_gspmd = time_matmul(
            lambda c, wl: jax.lax.with_sharding_constraint(c @ wl, repl), xk, w
        )
        us_ring = time_matmul(
            lambda c, wl: cm.row_parallel_matmul(c, wl, plan), xk, w
        )
        speedup = us_gspmd / us_ring
        verdicts.append(speedup)
        print(
            f"tp={tp}  {name:<14} [{S}x{k_dim}@{k_dim}x{H}]  "
            f"ring {us_ring:8.1f} us vs gspmd {us_gspmd:8.1f} us"
            f"  -> ring {speedup:.2f}x"
        )
    return verdicts


def main():
    print(
        f"collectives: {NDEV} {jax.devices()[0].platform} device(s), "
        f"S={S} H={H} I={I} n={N_ITERS}"
    )
    verdicts = []
    tp = 2
    while tp <= NDEV:
        verdicts = bench_tp(tp) or verdicts  # verdict = largest tp degree
        tp *= 2
    if not verdicts:
        return
    best = max(verdicts)
    if best > 1.05:
        print(
            f"ring matmul wins at full tp (best {best:.2f}x) -> overlap"
            " pays on this slice: set LLMQ_TP_OVERLAP=on or tp_overlap=auto"
        )
    else:
        print(
            f"ring matmul does not beat GSPMD at full tp (best {best:.2f}x)"
            " -> leave tp_overlap off"
        )


if __name__ == "__main__":
    main()
