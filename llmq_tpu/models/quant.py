"""Weight-only int8 quantization (``--dtype int8``).

Replaces the capability the reference inherited from vLLM's quantization
support: int8 weight storage halves HBM footprint AND HBM bandwidth —
decode is weight-bound once attention runs at the bandwidth floor
(PERF_NOTES round 4), and it is what lets a ~9B bf16 model (~18 GB)
fit a single 16 GB v5e chip.

Representation — a quantized weight is a plain nested dict

    {"q": int8[..., in, out], "scale": float32[..., out]}

with symmetric per-output-channel scales (``w ≈ q * scale``). Using a
dict (not a custom pytree class) means the whole machinery — ``lax.scan``
leading-axis slicing, ``device_put`` with sharding trees, donation, the
weight streamer — handles quantized params with zero special cases; only
the matmul call sites and the sharding-spec builder know the shape.

Math: per-column scales commute with the contraction, so

    x @ (q * scale) == (x @ q_as_bf16) * scale

and the kernel runs as a bf16 MXU matmul whose weight operand is
converted from int8 on the fly (XLA fuses the convert into the dot
operand read — the HBM side stays int8).

Embeddings quantize per ROW (the lookup axis): ``q[ids] * scale[ids]``.

int4 (``--dtype int4``) extends the ladder one rung below int8 with
AWQ-style asymmetric group quantization:

    {"q": uint8[..., in/2, out], "scale": f[..., groups, out],
     "zero": f[..., groups, out]}

Two 4-bit codes pack per byte along the CONTRACTION axis (even row in
the low nibble, odd row in the high nibble), so the packed axis maps
1:1 onto the weight's contraction axis for sharding and the ring
chunks of ``ops/collective_matmul.py`` — which slice the OUTPUT axis —
never see the packing at all. Per-group affine dequant is

    w ≈ (unpack(q) - zero) * scale,   q ∈ [0, 15], zero an integer float

with ``group`` input rows per (scale, zero) pair. The zero-point does
NOT commute with the contraction (unlike int8's symmetric per-column
scale), so every consumer dequantizes before the dot: the Pallas
kernel (``LLMQ_INT4_MATMUL=pallas``) dequantizes per block in VMEM,
the XLA fallback materializes one layer slice, and ring chunks
dequantize per chunk. ``dequantize_int4_parts`` is the single
definition of that affine math — kernels and references share it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from llmq_tpu.utils.platform import on_tpu

Params = Dict[str, Any]

# Keys quantized under --dtype int8: every large matmul operand. Norms,
# biases, the MoE router and the tiny shared-expert gate stay bf16 (their
# bytes are noise; router logits are precision-sensitive).
QUANTIZED_LAYER_KEYS = (
    "q_proj",
    "k_proj",
    "v_proj",
    "o_proj",
    "gate_proj",
    "up_proj",
    "down_proj",
    "expert_gate_proj",
    "expert_up_proj",
    "expert_down_proj",
    "shared_gate_proj",
    "shared_up_proj",
    "shared_down_proj",
)
QUANTIZED_TOP_KEYS = ("embed", "lm_head")


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "scale" in w


def is_int4(w: Any) -> bool:
    """True for the packed int4 group-quantized dict (int8 has no zero-point)."""
    return is_quantized(w) and "zero" in w


# Default AWQ-style group size (input rows per scale/zero pair).
INT4_GROUP_SIZE = 128


def int4_group(k: int, group_size: int = INT4_GROUP_SIZE) -> int:
    """Largest usable group size: ``group_size`` when it divides the
    contraction dim, else the gcd (tiny test models have K < 128)."""
    return group_size if k % group_size == 0 else math.gcd(k, group_size)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack 4-bit codes ``[..., K, N] -> uint8[..., K//2, N]`` along the
    contraction axis: even rows in the low nibble, odd rows high."""
    lo = q[..., 0::2, :].astype(jnp.uint8)
    hi = q[..., 1::2, :].astype(jnp.uint8)
    return lo | (hi << 4)


def unpack_int4(qp: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_int4`: ``uint8[..., K//2, N] -> int32[..., K, N]``."""
    lo = (qp & 0xF).astype(jnp.int32)
    hi = (qp >> 4).astype(jnp.int32)
    stacked = jnp.stack([lo, hi], axis=-2)  # [..., K//2, 2, N]
    return stacked.reshape(*qp.shape[:-2], qp.shape[-2] * 2, qp.shape[-1])


def dequantize_int4_parts(
    qp: jnp.ndarray, scale: jnp.ndarray, zero: jnp.ndarray, dtype
) -> jnp.ndarray:
    """The one affine-dequant definition: unpack, subtract the per-group
    zero-point, scale — all in f32 — then cast. Kernels, ring chunks,
    the XLA fallback, and test references all call (or mirror) this so
    numerics agree across backends."""
    q = unpack_int4(qp).astype(jnp.float32)
    k = q.shape[-2]
    groups = scale.shape[-2]
    group = k // groups
    qg = q.reshape(*q.shape[:-2], groups, group, q.shape[-1])
    deq = (qg - zero.astype(jnp.float32)[..., :, None, :]) * scale.astype(
        jnp.float32
    )[..., :, None, :]
    return deq.reshape(*q.shape).astype(dtype)


def quantize_array_int4(
    w: jnp.ndarray,
    *,
    group_size: int = INT4_GROUP_SIZE,
    scale_dtype=jnp.float32,
) -> Params:
    """Asymmetric int4 group quantization over the contraction
    (second-to-last) axis. Weights only — embeddings and the LM head
    stay on the int8 rung (logit parity is precision-sensitive and
    their bytes are amortized over the whole batch)."""
    k = w.shape[-2]
    if k % 2:
        raise ValueError(f"int4 packing needs an even contraction dim, got {k}")
    group = int4_group(k, group_size)
    groups = k // group
    w32 = w.astype(jnp.float32)
    wg = w32.reshape(*w32.shape[:-2], groups, group, w32.shape[-1])
    wmin = wg.min(axis=-2)
    wmax = wg.max(axis=-2)
    scale = (wmax - wmin) / 15.0
    scale = jnp.where(scale > 0, scale, 1.0)
    # Zero-points are stored as floats (rounded to integers for AWQ
    # fidelity) rather than packed 4-bit, so they need no [0, 15] clip —
    # an all-positive group legitimately wants a negative zero-point.
    zero = jnp.round(-wmin / scale)
    q = jnp.round(wg / scale[..., :, None, :] + zero[..., :, None, :])
    q = jnp.clip(q, 0, 15).astype(jnp.uint8).reshape(*w32.shape)
    return {
        "q": pack_int4(q),
        "scale": scale.astype(scale_dtype),
        "zero": zero.astype(scale_dtype),
    }


def quantize_array(
    w: jnp.ndarray, *, axis: int, scale_dtype=jnp.float32
) -> Params:
    """Symmetric int8 quantization with the scale reduced over ``axis``
    (the contraction dim for weights, the feature dim for embeddings).
    ``scale_dtype`` should be the model's compute dtype — matmul outputs
    and embedding lookups inherit it."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.round(w32 / jnp.expand_dims(scale, axis))
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(scale_dtype)}


@partial(jax.jit, donate_argnums=(0,), static_argnames=("axis", "scale_dtype"))
def quantize_array_donated(w, *, axis: int, scale_dtype=jnp.float32) -> Params:
    """``quantize_array`` freeing the input buffer on dispatch — for
    init/load flows where the full-precision tree would not fit HBM."""
    return quantize_array(w, axis=axis, scale_dtype=scale_dtype)


@partial(
    jax.jit, donate_argnums=(0,), static_argnames=("group_size", "scale_dtype")
)
def quantize_array_int4_donated(
    w, *, group_size: int = INT4_GROUP_SIZE, scale_dtype=jnp.float32
) -> Params:
    """``quantize_array_int4`` freeing the input buffer on dispatch."""
    return quantize_array_int4(w, group_size=group_size, scale_dtype=scale_dtype)


# Set by disable_pallas_matmul(); checked at trace time alongside the
# env var.
_PALLAS_DISABLED_REASON: str | None = None


def disable_pallas_matmul(reason: str) -> None:
    """Turn off the Pallas int8 matmul for the REST OF THIS PROCESS
    (trace-time check — affects every engine traced afterwards, which
    in the worker/bench deployment model is exactly one). The engine
    calls this on tp>1 meshes: GSPMD cannot partition the opaque
    ``pallas_call`` over sharded weights, so tracing with it enabled
    would replicate every weight on every chip."""
    global _PALLAS_DISABLED_REASON
    _PALLAS_DISABLED_REASON = reason


def _pallas_int8_enabled() -> bool:
    """``LLMQ_INT8_MATMUL=pallas``: route int8 matmuls through the
    dequantize-in-VMEM Pallas kernel (``ops/pallas_matmul.py``) instead
    of relying on XLA fusing the convert into the dot. tp==1 scope — see
    the kernel module docstring and :func:`disable_pallas_matmul`."""
    import os

    if _PALLAS_DISABLED_REASON is not None:
        return False
    return os.environ.get("LLMQ_INT8_MATMUL", "").lower() == "pallas"


def _pallas_int4_enabled() -> bool:
    """``LLMQ_INT4_MATMUL=pallas``: route int4 matmuls through the
    group-dequantize-in-VMEM Pallas kernel. Shares the process-wide
    :func:`disable_pallas_matmul` kill switch with int8 — on tp>1
    meshes the opaque ``pallas_call`` would break GSPMD partitioning,
    but the ring chunks of ``ops/collective_matmul.py`` still use the
    kernel locally (they check the env directly, like int8)."""
    import os

    if _PALLAS_DISABLED_REASON is not None:
        return False
    return os.environ.get("LLMQ_INT4_MATMUL", "").lower() == "pallas"


def matmul(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """``x @ w`` for a plain array or an int8/int4-quantized weight."""
    if is_int4(w):
        if _pallas_int4_enabled() and w["q"].ndim == 2:
            from llmq_tpu.ops.pallas_matmul import int4_matmul_pallas

            lead = x.shape[:-1]
            out = int4_matmul_pallas(
                x.reshape(-1, x.shape[-1]),
                w["q"],
                w["scale"],
                w["zero"],
                interpret=not on_tpu(),
            )
            return out.reshape(*lead, out.shape[-1])
        # XLA fallback: affine zero-points do not commute with the dot,
        # so dequantize the (single layer slice of) weight first.
        return x @ dequantize_int4_parts(w["q"], w["scale"], w["zero"], x.dtype)
    if is_quantized(w):
        if _pallas_int8_enabled() and w["q"].ndim == 2:
            from llmq_tpu.ops.pallas_matmul import int8_matmul_pallas

            lead = x.shape[:-1]
            out = int8_matmul_pallas(
                x.reshape(-1, x.shape[-1]),
                w["q"],
                w["scale"],
                interpret=not on_tpu(),
            )
            return out.reshape(*lead, out.shape[-1])
        s = w["scale"].astype(x.dtype)
        if w["q"].ndim > 2:  # stacked weights: scale is [..., N], out [..., M, N]
            s = s[..., None, :]
        return (x @ w["q"].astype(x.dtype)) * s
    return x @ w


def dequantize(w: Any, dtype) -> jnp.ndarray:
    """Materialize the full-precision weight (grouped-matmul operands —
    ``lax.ragged_dot`` takes a real array). One layer's slice at a time
    inside the scan, so the transient stays small."""
    if is_int4(w):
        return dequantize_int4_parts(w["q"], w["scale"], w["zero"], dtype)
    if is_quantized(w):
        return w["q"].astype(dtype) * w["scale"].astype(dtype)[..., None, :]
    return w


def embed_lookup(w: Any, ids: jnp.ndarray) -> jnp.ndarray:
    """Embedding-table row lookup for plain or row-quantized tables. The
    scale's dtype IS the model compute dtype (set at quantize time), so
    the lookup result matches what a plain bf16 table would produce."""
    if is_quantized(w):
        dtype = w["scale"].dtype
        return w["q"][ids].astype(dtype) * w["scale"][ids][..., None]
    return w[ids]


def tied_head_matmul(h: jnp.ndarray, embed: Any) -> jnp.ndarray:
    """``h @ embed.T`` for tied-embedding LM heads. The embedding's
    per-row scale becomes the head's per-column scale."""
    if is_quantized(embed):
        return (h @ embed["q"].T.astype(h.dtype)) * embed["scale"].astype(h.dtype)
    return h @ embed.T


def quantize_params(
    params: Params,
    scale_dtype=jnp.float32,
    *,
    donate: bool = False,
    bits: int = 8,
    group_size: int = INT4_GROUP_SIZE,
) -> Params:
    """Quantize a loaded/initialized param tree (returns a new tree).
    Used by the preset / random-init path and tests; checkpoint loads
    quantize while streaming (``engine/weights.py``) so the bf16 copy
    never exists on device.

    ``bits=4`` puts the layer matmul weights on the int4 group rung;
    the embedding table and LM head stay int8 on either rung (per-row /
    per-column symmetric — logits are the precision-sensitive end and
    those two tensors are not the decode bandwidth term).

    ``donate=True`` frees each full-precision buffer as it is consumed —
    required when the bf16 tree alone nearly fills HBM (a 9B preset on a
    16 GB chip): peak HBM is then one tensor's bf16+int8, not two whole
    trees. The input tree's quantized leaves are unusable afterwards."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    donate_args = (0,) if donate else ()

    @partial(jax.jit, donate_argnums=donate_args)
    def _quant_w(w):
        return quantize_array(w, axis=-2, scale_dtype=scale_dtype)

    @partial(jax.jit, donate_argnums=donate_args)
    def _quant_rows(w):
        return quantize_array(w, axis=-1, scale_dtype=scale_dtype)

    @partial(jax.jit, donate_argnums=donate_args)
    def _quant_w4(w):
        return quantize_array_int4(w, group_size=group_size, scale_dtype=scale_dtype)

    quant_layer = _quant_w4 if bits == 4 else _quant_w
    out: Params = dict(params)
    layers = dict(params["layers"])
    for key in QUANTIZED_LAYER_KEYS:
        if key in layers:
            layers[key] = quant_layer(layers[key])
    out["layers"] = layers
    out["embed"] = _quant_rows(params["embed"])
    if "lm_head" in params:
        out["lm_head"] = _quant_w(params["lm_head"])
    return out


def quantized_specs(specs: Params, params: Params) -> Params:
    """Mirror a PartitionSpec tree onto a (possibly) quantized param
    tree: wherever the params hold ``{"q", "scale"}``, the weight's spec
    applies to ``q`` and the scale keeps the spec of the surviving axes
    (the reduced axis's entry is dropped)."""
    from jax.sharding import PartitionSpec as P

    def walk(spec_node, param_node, key):
        if is_quantized(param_node):
            spec = spec_node
            parts = list(spec) + [None] * (param_node["q"].ndim - len(spec))
            if is_int4(param_node):
                # Packed q keeps the weight spec (the packed axis IS the
                # contraction axis, halved). Scale/zero replicate their
                # group axis: group tensors are 1/group the weight bytes,
                # and groups need not divide tp.
                sz = P(*(parts[:-2] + [None] + parts[-1:]))
                return {"q": spec, "scale": sz, "zero": sz}
            # The reduced axis is structural, not inferable from shapes
            # (square weights are common): only "embed" quantizes per ROW
            # (last axis reduced); every weight reduces the contraction
            # (second-to-last) axis.
            if key == "embed":
                scale_parts = parts[:-1]
            else:
                scale_parts = parts[:-2] + parts[-1:]
            return {"q": spec, "scale": P(*scale_parts)}
        if isinstance(param_node, dict):
            return {
                k: walk(
                    spec_node[k] if isinstance(spec_node, dict) else spec_node,
                    v,
                    k,
                )
                for k, v in param_node.items()
            }
        return spec_node

    return walk(specs, params, "")
