"""Micro-bench decode-attention kernels at REAL pool size (HBM-resident).

The round-3 finding: a small test pool fits in VMEM and makes any kernel
look infinitely fast — benchmark only with the full stacked [L,P,...]
pool (2.3 GiB per K and V at the 3B bench config).
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from llmq_tpu.ops.pallas_attention import paged_decode_attention_pallas

if jax.default_backend() == "cpu":
    # Smoke-testable off-TPU: tiny shapes, Pallas interpret mode. The
    # numbers are meaningless (interpret is ~1000x slow) — this exists
    # so the CPU pre-flight can prove every command in the hardware
    # session runbook executes end to end before chips are rented.
    S, H, NKV, D = 8, 4, 2, 16
    PAGE, PPS, L, P, CTX = 8, 4, 2, 33, 20
else:
    # bench config shapes: qwen2.5-3b, S=192, page 128, max_model_len 512
    S = 192
    H, NKV, D = 16, 2, 128
    PAGE = 128
    PPS = 4
    L = 36
    P = 961  # pool pages per layer (auto-sized in the engine at this config)
    CTX = 330
S = int(os.environ.get("PROF_S", S))
H = int(os.environ.get("PROF_H", H))
L = int(os.environ.get("PROF_L", L))
INTERP = jax.default_backend() != "tpu"

if "--int4" in sys.argv or os.environ.get("PROF_MODE", "") == "int4":
    # int4 mode: profile the group-quantized dequant-in-VMEM matmul
    # kernel against the XLA dequant path and the bf16 matmul floor at
    # the decode MLP shape. Decode is weight-stream-bound, so the
    # figure of merit is GiB/s of PACKED weight bytes — the kernel only
    # earns its keep if streaming a quarter of the bytes actually beats
    # the bf16 matmul wall clock.
    from llmq_tpu.models import quant as qm
    from llmq_tpu.ops.pallas_matmul import int4_matmul_pallas

    if jax.default_backend() == "cpu":
        M, K, N, GROUP = 8, 256, 512, 128
    else:
        M, K, N, GROUP = S, 2048, 11008, 128  # 3B MLP up-proj at S slots
    M = int(os.environ.get("PROF_M", M))
    K = int(os.environ.get("PROF_K", K))
    N = int(os.environ.get("PROF_N", N))
    w = jax.random.normal(jax.random.key(5), (K, N), jnp.float32)
    qt = qm.quantize_array_int4(w, group_size=GROUP)
    wb = (w.astype(jnp.bfloat16) + 0).block_until_ready()
    x = jax.random.normal(jax.random.key(6), (M, K), jnp.bfloat16)
    packed_bytes = qt["q"].size  # one byte carries two int4 weights

    def timeit(f, n=10):
        out = f()
        jax.block_until_ready(out)
        t0 = time.monotonic()
        for _ in range(n):
            out = f()
        jax.block_until_ready(out)
        return (time.monotonic() - t0) / n * 1000

    bf16_f = jax.jit(lambda: x @ wb)
    xla_f = jax.jit(
        lambda: x
        @ qm.dequantize_int4_parts(
            qt["q"], qt["scale"], qt["zero"], jnp.bfloat16
        )
    )
    kern_f = jax.jit(
        lambda: int4_matmul_pallas(
            x, qt["q"], qt["scale"], qt["zero"], interpret=INTERP
        )
    )
    print(f"int4 matmul: M={M} K={K} N={N} group={GROUP} "
          f"(packed {packed_bytes/2**20:.1f} MiB vs bf16 "
          f"{K*N*2/2**20:.1f} MiB)", flush=True)
    ms = timeit(bf16_f)
    print(f"bf16 matmul:      {ms:.3f} ms ({K*N*2/ms*1e3/2**30:.0f} GiB/s)")
    ms = timeit(xla_f)
    print(f"int4 XLA dequant: {ms:.3f} ms "
          f"({packed_bytes/ms*1e3/2**30:.0f} GiB/s packed)")
    ms = timeit(kern_f)
    print(f"int4 kernel:      {ms:.3f} ms "
          f"({packed_bytes/ms*1e3/2**30:.0f} GiB/s packed)")
    diff = jnp.max(
        jnp.abs(
            kern_f().astype(jnp.float32) - xla_f().astype(jnp.float32)
        )
    )
    print("max|diff| kernel vs XLA dequant:", float(diff))
    sys.exit(0)

rng = np.random.default_rng(0)
q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
print(f"pool: {L*P*PAGE*NKV*D*2/2**30:.2f} GiB per side", flush=True)
# Generate the pools ON DEVICE: a host float64 standard_normal at this
# shape is ~9 GiB and swaps the machine before the TPU is ever touched.
kp = jax.random.normal(jax.random.key(1), (L, P, PAGE, NKV, D), jnp.bfloat16)
vp = jax.random.normal(jax.random.key(2), (L, P, PAGE, NKV, D), jnp.bfloat16)
jax.block_until_ready((kp, vp))
print("pool ready on device", flush=True)
# distinct pages per seq, like the real allocator
bt_np = np.zeros((S, PPS), np.int32)
perm = np.arange(P)
rng.shuffle(perm)
for s in range(S):
    bt_np[s] = perm[(s * PPS) % (P - PPS):(s * PPS) % (P - PPS) + PPS]
bt = jnp.asarray(bt_np)
cl = jnp.full((S,), CTX, jnp.int32)
w = jnp.asarray([1 << 30], jnp.int32)
scale = D ** -0.5


def timeit_layers(f, n=3):
    """Run over all L layers per iteration (different li -> different pages,
    defeats any caching; matches the engine's access pattern)."""
    outs = [f(jnp.int32(li)) for li in range(L)]
    jax.block_until_ready(outs[-1])
    t0 = time.monotonic()
    for _ in range(n):
        outs = [f(jnp.int32(li)) for li in range(L)]
    jax.block_until_ready(outs)
    return (time.monotonic() - t0) / (n * L) * 1000


live_pages = -(-CTX // PAGE)
kv_bytes = S * live_pages * PAGE * NKV * D * 2 * 2
tot_bytes = S * PPS * PAGE * NKV * D * 2 * 2
print(f"live KV/layer: {kv_bytes/2**20:.1f} MiB (floor@819GB/s "
      f"{kv_bytes/819e9*1e3:.3f} ms); with dead pages: {tot_bytes/2**20:.1f} MiB")

ms = timeit_layers(
    lambda li: paged_decode_attention_pallas(q, kp, vp, bt, cl, w, layer=li,
                                             scale=scale, interpret=INTERP))
print(f"current: {ms:.3f} ms/layer -> x{L}: {ms*L:.1f} ms/step  "
      f"({tot_bytes/ms*1e3/2**30:.0f} GiB/s eff)")

from llmq_tpu.ops.pallas_attention import paged_decode_attention_pallas_v2

ms = timeit_layers(
    lambda li: paged_decode_attention_pallas_v2(q, kp, vp, bt, cl, w, layer=li,
                                                scale=scale, interpret=INTERP))
print(f"v2 manual-DMA: {ms:.3f} ms/layer -> x{L}: {ms*L:.1f} ms/step  "
      f"({kv_bytes/ms*1e3/2**30:.0f} GiB/s live-eff)")

a = paged_decode_attention_pallas(q, kp, vp, bt, cl, w, layer=jnp.int32(0), scale=scale, interpret=INTERP)
b = paged_decode_attention_pallas_v2(q, kp, vp, bt, cl, w, layer=jnp.int32(0), scale=scale, interpret=INTERP)
diff = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
print("max|diff| v2 vs v1 on TPU:", float(diff))

# v3 fused-KV-write vs v1/v2 + their separate XLA scatter — the engine's
# actual per-layer cost for each choice (same framing as the bench A/B:
# donation so v3's in-place alias isn't penalized by a pool copy).
import functools

from llmq_tpu.ops.attention import write_kv_pages
from llmq_tpu.ops.pallas_attention import paged_decode_attention_pallas_v3

kn = jax.random.normal(jax.random.key(3), (S, NKV, D), jnp.bfloat16)
vn = jax.random.normal(jax.random.key(4), (S, NKV, D), jnp.bfloat16)
positions = (cl - 1)[:, None]


@functools.partial(jax.jit, static_argnames=("which",), donate_argnums=(0, 1))
def engine_step(kp, vp, li, *, which):
    if which == "v3":
        out, kp, vp = paged_decode_attention_pallas_v3(
            q, kp, vp, kn, vn, bt, cl, w, li, scale=scale, interpret=INTERP)
        return out, kp, vp
    kp, vp = write_kv_pages(kp, vp, kn[:, None], vn[:, None], bt, positions,
                            layer=li)
    kern = (paged_decode_attention_pallas_v2 if which == "v2"
            else paged_decode_attention_pallas)
    return kern(q, kp, vp, bt, cl, w, li, scale=scale, interpret=INTERP), kp, vp


def timeit_engine(which, n=3):
    global kp, vp
    for li in range(L):
        out, kp, vp = engine_step(kp, vp, jnp.int32(li), which=which)
    jax.block_until_ready(out)
    t0 = time.monotonic()
    for _ in range(n):
        for li in range(L):
            out, kp, vp = engine_step(kp, vp, jnp.int32(li), which=which)
        jax.block_until_ready(out)
    return (time.monotonic() - t0) / (n * L) * 1000


for which in ("v1", "v2", "v3"):
    ms = timeit_engine(which)
    print(f"{which} incl. KV write: {ms:.3f} ms/layer -> x{L}: "
          f"{ms*L:.1f} ms/step")
o3, kp, vp = engine_step(kp, vp, jnp.int32(0), which="v3")
o1, kp, vp = engine_step(kp, vp, jnp.int32(0), which="v1")
print("max|diff| v3 vs v1 (incl. write):",
      float(jnp.max(jnp.abs(o3.astype(jnp.float32) - o1.astype(jnp.float32)))))

# partial-occupancy case: half the slots empty (bench tail / mixed load)
cl_half = jnp.where(jnp.arange(S) % 2 == 0, CTX, 0)
ms = timeit_layers(
    lambda li: paged_decode_attention_pallas_v2(q, kp, vp, bt, cl_half, w, layer=li,
                                                scale=scale, interpret=INTERP))
print(f"v2 half-empty: {ms:.3f} ms/layer (dead-slot skipping)")
ms = timeit_layers(
    lambda li: paged_decode_attention_pallas(q, kp, vp, bt, cl_half, w, layer=li,
                                             scale=scale, interpret=INTERP))
print(f"v1 half-empty: {ms:.3f} ms/layer (fixed schedule)")
