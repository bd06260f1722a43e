"""Does XLA fuse the int8→bf16 weight convert into the MXU dot?

The whole int8 decode-throughput claim (models/quant.py) rests on the
weight operand staying int8 in HBM: `x @ q.astype(bf16) * scale` must
read q AS int8 and convert on-chip. If XLA instead materializes a bf16
copy, traffic is 2.5x the int8 bytes and int8 decode is SLOWER than
bf16. This micro-bench answers it in one run at decode shapes:

    int8 time ≈ 0.5-0.6x bf16 time  -> fused (ship int8 for decode)
    int8 time ≥ 1x bf16 time        -> not fused (needs a Pallas
                                       dequant-in-kernel matmul before
                                       int8 helps decode; it still
                                       halves FOOTPRINT either way)

Shapes mirror the 3B bench config's per-layer MLP matmul (the dominant
weight stream): x [192, 2048] @ W [2048, 11008], plus a layer-stacked
scan variant matching how the engine actually reads weights.
"""
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

if jax.default_backend() == "cpu":  # smoke-testable off-TPU
    S, H, I, L = 32, 256, 512, 2
else:
    S, H, I, L = 192, 2048, 11008, 8
S = int(os.environ.get("PROF_S", S))
H = int(os.environ.get("PROF_H", H))
I = int(os.environ.get("PROF_I", I))  # noqa: E741
L = int(os.environ.get("PROF_L", L))

x = jax.random.normal(jax.random.key(0), (S, H), jnp.bfloat16)
w_bf16 = jax.random.normal(jax.random.key(1), (L, H, I), jnp.bfloat16)
w_q = jax.random.randint(jax.random.key(2), (L, H, I), -127, 127, jnp.int8)
scale = jax.random.uniform(jax.random.key(3), (L, I), jnp.bfloat16)


@jax.jit
def scan_bf16(x, w):
    def body(c, wl):
        return c, x @ wl

    _, ys = jax.lax.scan(body, 0, w)
    return ys


@jax.jit
def scan_int8(x, wq, sc):
    def body(c, xs):
        wl, sl = xs
        return c, (x @ wl.astype(x.dtype)) * sl

    _, ys = jax.lax.scan(body, 0, (wq, sc))
    return ys


def timeit(f, *args, n=20):
    """Time n iterations of f with a data dependence between them.

    The old version dispatched f(*args) n times with IDENTICAL inputs
    and dead outputs — nothing stopped XLA from eliding the matmul body
    (the result was never consumed), which shows up as impossible
    effective bandwidth. Here each iteration's output is folded back
    into the next iteration's activation (scaled by the smallest
    subnormal, so the values are numerically unchanged but the compiler
    cannot prove it), the whole chain runs inside ONE jitted fori_loop,
    and the activation buffer is donated. Every weight read is live.
    """
    tiny = jnp.finfo(x.dtype).smallest_subnormal

    @partial(jax.jit, donate_argnums=(0,))
    def chained(x0):
        def body(_, xc):
            ys = f(xc, *args)
            return xc + ys.ravel()[:1].astype(xc.dtype) * tiny

        return jax.lax.fori_loop(0, n, body, x0)

    jax.block_until_ready(chained(jnp.copy(x)))  # compile
    fresh = jnp.copy(x)  # donated; make the copy outside the clock
    t0 = time.monotonic()
    jax.block_until_ready(chained(fresh))
    return (time.monotonic() - t0) / n / L * 1e3  # ms per layer


# Datasheet HBM bandwidth per chip, GB/s. A measured *weight-stream*
# bandwidth above this is physically impossible — it means XLA elided
# work despite the dependence chain, and the number must not be trusted.
_HBM_PEAK_GBS = {
    "v2": 700.0,
    "v3": 900.0,
    "v4": 1228.0,
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}


def hbm_peak_gbs():
    if jax.default_backend() != "tpu":
        return None  # CPU smoke mode: no meaningful peak to gate on
    kind = jax.devices()[0].device_kind.lower()
    for key in sorted(_HBM_PEAK_GBS, key=len, reverse=True):
        if key in kind:
            return _HBM_PEAK_GBS[key]
    return None


def reject_if_elided(label, gibs):
    peak = hbm_peak_gbs()
    if peak is None:
        return
    gbs = gibs * (2**30 / 1e9)
    if gbs > 1.2 * peak:
        sys.exit(
            f"{label}: measured {gbs:.0f} GB/s effective weight bandwidth"
            f" > 1.2x this chip's HBM peak ({peak:.0f} GB/s) — the"
            " compiler elided work; measurement rejected"
        )


from llmq_tpu.ops.pallas_matmul import int8_matmul_pallas  # noqa: E402

interp = jax.default_backend() != "tpu"


@jax.jit
def scan_pallas(x, wq, sc):
    def body(c, xs):
        wl, sl = xs
        return c, int8_matmul_pallas(x, wl, sl, interpret=interp)

    _, ys = jax.lax.scan(body, 0, (wq, sc))
    return ys


ms_bf16 = timeit(scan_bf16, w_bf16)
ms_int8 = timeit(scan_int8, w_q, scale)
ms_pallas = timeit(scan_pallas, w_q, scale.astype(jnp.float32))
bytes_bf16 = H * I * 2
bytes_int8 = H * I * 1
gibs_bf16 = bytes_bf16 / ms_bf16 * 1e3 / 2**30
gibs_int8 = bytes_int8 / ms_int8 * 1e3 / 2**30
gibs = bytes_int8 / ms_pallas * 1e3 / 2**30
reject_if_elided("bf16 XLA", gibs_bf16)
reject_if_elided("int8 XLA", gibs_int8)
reject_if_elided("int8 Pallas", gibs)
print(f"bf16 XLA:    {ms_bf16:.3f} ms/layer ({gibs_bf16:.0f} GiB/s eff)")
print(f"int8 XLA:    {ms_int8:.3f} ms/layer ({gibs_int8:.0f} GiB/s int8-eff)")
print(f"int8 Pallas: {ms_pallas:.3f} ms/layer ({gibs:.0f} GiB/s int8-eff)")
ratio = ms_int8 / ms_bf16
verdict = "FUSED (int8 wins as-is)" if ratio < 0.8 else (
    "NOT fused — enable LLMQ_INT8_MATMUL=pallas"
    if ratio > 0.95 else "marginal"
)
print(f"int8/bf16 = {ratio:.2f} -> {verdict}")
if ms_pallas < min(ms_int8, ms_bf16):
    print("pallas kernel is the fastest int8 path on this chip")
