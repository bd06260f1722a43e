"""Pallas TPU int8/int4 weight-only matmuls: dequantize in VMEM, never in HBM.

The int8 decode win (``models/quant.py``) assumes XLA fuses the
``q.astype(bf16)`` convert into the dot operand read so the HBM side
stays int8. ``tools/profile_int8_matmul.py`` measures whether it does on
the deployment chip; THIS kernel is the guaranteed path if it doesn't:
weight tiles are DMA'd to VMEM as int8 (half the bytes of bf16) and
converted + scaled on-chip, so weight HBM traffic is halved by
construction.

Enabled with ``LLMQ_INT8_MATMUL=pallas`` (checked at trace time by
``models/quant.py::matmul``). Scope: tp == 1 meshes — the dense matmuls
are partitioned by GSPMD, which cannot split an opaque ``pallas_call``;
single-chip deployments (e.g. the int8 9B-on-16GB config) are exactly
where the weight stream dominates. Off-TPU the kernel runs in interpret
mode for the numerics tests.

Tiling: grid ``(M/bm, N/bn, K/bk)`` with a float32 VMEM accumulator per
(m, n) tile; K is innermost so the accumulator lives across the
contraction. Cross-block accumulation is Kahan-compensated (a second
f32 VMEM scratch holds the running error term): at K=4096 the blocked
sum would otherwise drift a few output ulps from an unblocked dot,
which is exactly the noise the int4 parity tier has to budget for. The
int8 per-output-channel scale is applied once on the final K step,
then cast to the activation dtype.

``int4_matmul_pallas`` (``LLMQ_INT4_MATMUL=pallas``) is the group rung:
two 4-bit codes per byte along K (``models/quant.py::pack_int4``),
unpacked + affine-dequantized per block in VMEM — HBM weight traffic is
a QUARTER of bf16. K blocks align to group boundaries so each block's
``[groups_per_block, bn]`` scale/zero tile maps 1:1 onto the grid; the
zero-point does not commute with the dot, so dequant happens before the
MXU (bf16 multiply, f32 accumulate, same as int8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kahan_add(acc_ref, comp_ref, p):
    """Compensated accumulation: acc += p with the rounding error of each
    add carried in comp_ref, so the cross-K-block sum is ~1 ulp from an
    unblocked reduction regardless of nk."""
    y = p - comp_ref[...]
    t = acc_ref[...] + y
    comp_ref[...] = (t - acc_ref[...]) - y
    acc_ref[...] = t


def _int8_matmul_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, comp_ref, *, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    # Multiply in bf16, accumulate in f32: int8 values (±127) are exact
    # in bf16's 8 mantissa bits, and an f32×f32 dot would run the MXU at
    # a fraction of its bf16 rate — harmless for bandwidth-bound decode,
    # but compute-bound prefill shares this kernel.
    x = x_ref[...]  # [bm, bk] activation dtype (bf16 in production)
    w = q_ref[...].astype(x.dtype)  # [bk, bn] — int8 converts in VMEM
    _kahan_add(
        acc_ref,
        comp_ref,
        jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ),
    )

    @pl.when(ik == nk - 1)
    def _finish():
        scale = s_ref[...].astype(jnp.float32)  # [1, bn]
        o_ref[...] = (acc_ref[...] * scale).astype(o_ref.dtype)


def _int4_matmul_kernel(
    x_ref, q_ref, s_ref, z_ref, o_ref, acc_ref, comp_ref, *, nk: int, group: int
):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    x = x_ref[...]  # [bm, bk]
    qp = q_ref[...]  # [bk//2, bn] uint8, two codes per byte along K
    bk2, bn = qp.shape
    bk = bk2 * 2
    # Unpack: even K rows sit in the low nibble, odd in the high —
    # stacking on a new axis then collapsing restores the row order
    # (same layout as models/quant.py::unpack_int4).
    # (Through int32: Mosaic has no uint8 -> float32 cast.)
    qi = qp.astype(jnp.int32)
    lo = (qi & 0xF).astype(jnp.float32)
    hi = (qi >> 4).astype(jnp.float32)
    w4 = jnp.stack([lo, hi], axis=1).reshape(bk, bn)
    # Affine dequant per group in f32 (the single definition of the
    # math lives in models/quant.py::dequantize_int4_parts — this block
    # mirrors it so backends agree), then down to the MXU dtype.
    s = s_ref[...].astype(jnp.float32)  # [bk//group, bn]
    z = z_ref[...].astype(jnp.float32)
    wg = w4.reshape(bk // group, group, bn)
    w = ((wg - z[:, None, :]) * s[:, None, :]).reshape(bk, bn).astype(x.dtype)
    _kahan_add(
        acc_ref,
        comp_ref,
        jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ),
    )

    @pl.when(ik == nk - 1)
    def _finish():
        # Scales are already applied per block — the accumulator IS the output.
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick_block(dim: int, *prefs: int) -> int:
    """Largest preferred tile that DIVIDES dim. Padding the weight to a
    non-dividing grid would materialize a padded int8 copy inside the
    jitted graph on every call — tripling the very HBM traffic this
    kernel exists to halve (real MLP dims like 11008 = 256*43 don't
    divide 512). Falls back to the smallest preference (padding path,
    correct but copy-paying) only when nothing divides."""
    for p in prefs:
        if dim % p == 0:
            return p
    return min(prefs[-1], dim)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def int8_matmul_pallas(
    x: jnp.ndarray,  # [M, K] bf16/f32 activations
    q: jnp.ndarray,  # [K, N] int8 weight
    scale: jnp.ndarray,  # [N] per-output-channel scale
    *,
    block_m: int = 256,
    block_n: int = 0,  # 0 = auto: largest of 512/256/128 dividing N
    block_k: int = 0,  # 0 = auto: largest of 512/256/128 dividing K
    interpret: bool = False,
) -> jnp.ndarray:
    """``(x @ q) * scale`` with q read from HBM as int8. Returns x.dtype.

    Ragged edges are zero-padded to the block grid (padding contributes
    zeros to the contraction, and padded output rows/cols are sliced
    off) — activation-side padding is cheap; weight-side padding is
    avoided by the auto block picker (see ``_pick_block``).
    """
    M, K = x.shape
    K2, N = q.shape
    assert K == K2 and scale.shape == (N,), (x.shape, q.shape, scale.shape)
    bm = min(block_m, M)
    bn = block_n or _pick_block(N, 512, 256, 128)
    bk = block_k or _pick_block(K, 512, 256, 128)
    bn = min(bn, N)
    bk = min(bk, K)
    mp, np_, kp = -(-M // bm) * bm, -(-N // bn) * bn, -(-K // bk) * bk
    if (mp, kp) != (M, K):
        x = jnp.pad(x, ((0, mp - M), (0, kp - K)))
    if (kp, np_) != (K, N):
        q = jnp.pad(q, ((0, kp - K), (0, np_ - N)))
    if np_ != N:
        scale = jnp.pad(scale, (0, np_ - N))
    nk = kp // bk

    out = pl.pallas_call(
        functools.partial(_int8_matmul_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, q, scale.reshape(1, np_))
    return out[:M, :N]


def _pick_block_k_int4(k: int, group: int) -> int:
    """K tile for the int4 kernel: a multiple of the quant group (so
    every block's scale/zero tile covers whole groups) that divides K
    (no weight-side padding — see ``_pick_block``), as large as fits
    under 512. ``base`` always divides K: the group does by
    construction, and K is even (packing requires it)."""
    base = group if group % 2 == 0 else 2 * group
    cap = max(base, 512 - 512 % base)
    for cand in range(cap, base - 1, -base):
        if k % cand == 0:
            return cand
    return base


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)
def int4_matmul_pallas(
    x: jnp.ndarray,  # [M, K] bf16/f32 activations
    q: jnp.ndarray,  # [K//2, N] packed uint8 weight
    scale: jnp.ndarray,  # [G, N] per-group scales
    zero: jnp.ndarray,  # [G, N] per-group zero-points
    *,
    block_m: int = 256,
    block_n: int = 0,  # 0 = auto: largest of 512/256/128 dividing N
    block_k: int = 0,  # 0 = auto: group-aligned, dividing K, <= 512
    interpret: bool = False,
) -> jnp.ndarray:
    """``x @ dequant(q, scale, zero)`` with q read from HBM packed 4-bit.
    Returns x.dtype. M/N ragged edges are zero-padded and sliced off;
    K never pads (``_pick_block_k_int4`` only returns divisors)."""
    M, K = x.shape
    K2, N = q.shape
    G = scale.shape[0]
    assert K == 2 * K2 and scale.shape == (G, N) and zero.shape == (G, N), (
        x.shape,
        q.shape,
        scale.shape,
        zero.shape,
    )
    assert K % G == 0, (K, G)
    group = K // G
    bm = min(block_m, M)
    bn = block_n or _pick_block(N, 512, 256, 128)
    bn = min(bn, N)
    bk = block_k or _pick_block_k_int4(K, group)
    assert bk % 2 == 0 and bk % group == 0 and K % bk == 0, (bk, group, K)
    mp, np_ = -(-M // bm) * bm, -(-N // bn) * bn
    if mp != M:
        x = jnp.pad(x, ((0, mp - M), (0, 0)))
    if np_ != N:
        q = jnp.pad(q, ((0, 0), (0, np_ - N)))
        scale = jnp.pad(scale, ((0, 0), (0, np_ - N)))
        zero = jnp.pad(zero, ((0, 0), (0, np_ - N)))
    nk = K // bk
    gpb = bk // group
    # One K block covers gpb (<= 4 at group 128) group rows, and a TPU
    # block's second-minor dim must be a multiple of 8 or the whole axis:
    # split the group axis [G, N] -> [nk, gpb, N] (a free reshape) so each
    # block IS a whole axis. The kernel still sees [gpb, bn].
    group_spec = pl.BlockSpec((None, gpb, bn), lambda i, j, k: (k, 0, j))

    out = pl.pallas_call(
        functools.partial(_int4_matmul_kernel, nk=nk, group=group),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            group_spec,
            group_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, q, scale.reshape(nk, gpb, np_), zero.reshape(nk, gpb, np_))
    return out[:M, :N]
