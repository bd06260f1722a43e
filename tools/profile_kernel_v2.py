"""Micro-bench the int4 group-quantized matmul kernel (``--int4``) at the
decode MLP shape. The decode-attention schedules are timed by
``tools/decode_kernel_bench.py``.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

# Smoke-testable off-TPU: tiny shapes, Pallas interpret mode. The numbers
# are meaningless (interpret is ~1000x slow) — this exists so the CPU
# pre-flight can prove every command in the hardware session runbook
# executes end to end before chips are rented. On the chip: the bench's
# 192 slots.
S = int(os.environ.get("PROF_S", 8 if jax.default_backend() == "cpu" else 192))
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

sys.exit("profile_kernel_v2.py: want --int4 (or PROF_MODE=int4)")
