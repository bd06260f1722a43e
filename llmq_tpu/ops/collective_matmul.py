"""Overlapped tensor-parallel collective matmuls (chunked ppermute rings).

Under plain GSPMD the Megatron row-parallel projections — ``o_proj``,
``down_proj``, and the MoE ``expert_down_proj`` — compile to a full
local matmul followed by one BLOCKING all-reduce per projection: two
serialized ICI collectives per layer in the decode step. At tp=8 decode
the per-chip matmul shrinks 8x but the ICI latency does not, so those
all-reduces dominate the per-step cost (Pope et al. 2022; Wang et al.
2023 "Overlap Communication with Dependent Computation via
Decomposition").

This module decomposes the matmul + reduce into a ``shard_map`` ring:
the output columns are split into chunks, each device computes the
partial product for ONE chunk per step while the accumulator for the
neighbouring chunk is in flight over ``lax.ppermute`` — every ICI hop
overlaps with the next chunk's MXU work. When the output dim splits
2*tp ways, TWO counter-rotating rings run per step (one ``ppermute``
each way), using both ICI directions per link. After tp-1 steps device
``i`` holds the fully reduced chunk(s) ``i``; a tiled ``all_gather``
reassembles the replicated output — the same dataflow GSPMD's
all-reduce produces, with the reduce hidden behind the matmul chunks.

Selection lives in ``ops/dispatch.resolve_tp_overlap`` (env
``LLMQ_TP_OVERLAP``, ``EngineConfig.tp_overlap``, autotuned ``auto``);
the model threads the resulting :class:`TpRingPlan` through its layer
functions. ``plan=None`` — or any shape the ring cannot split evenly —
falls back to the literal pre-existing ``qm.matmul`` call, so the
``off`` path traces byte-identical programs.

A deliberate side effect: each ring chunk matmul is a plain LOCAL call
that GSPMD never needs to partition, so the Pallas int8 matmul — which
the engine must disable process-wide for the GSPMD tp>1 path (an opaque
``pallas_call`` over sharded weights would replicate them) — stays
usable inside the ring. The chunk path therefore checks the
``LLMQ_INT8_MATMUL`` env var directly rather than
``quant._pallas_int8_enabled()``, which the process-wide disable gates.
int4 group-quantized weights ride the same rings: each device
affine-dequantizes its own contraction shard per chunk (zero-points
don't commute with the reduce the way int8's end-scale does, but the
per-device partials are an exact linear split of the contraction), with
``LLMQ_INT4_MATMUL=pallas`` routing chunks through the packed Pallas
kernel.

Every hand-written collective here names its axis via the
``parallel.mesh`` constants — enforced by the ``collective-axis`` lint
rule.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llmq_tpu.models import quant as qm
from llmq_tpu.parallel.mesh import DP_AXIS, TP_AXIS
from llmq_tpu.utils.platform import on_tpu


@dataclasses.dataclass(frozen=True)
class TpRingPlan:
    """Static ring description, resolved once per engine build.

    Frozen + hashable on purpose: it rides through jit closures and the
    layer ``lax.scan`` exactly like the kernel plans in ``ops/dispatch``
    — a pure function of the mesh, identical on every trace.
    """

    mesh: Mesh
    tp: int
    dp: int


def ring_plan(mesh: Optional[Mesh]) -> Optional[TpRingPlan]:
    """The tp-overlap plan for ``mesh``, or None when a ring cannot help
    (no mesh / tp degree 1 — GSPMD inserts no all-reduce to hide)."""
    if mesh is None:
        return None
    tp = int(mesh.shape.get(TP_AXIS, 1))
    if tp <= 1:
        return None
    return TpRingPlan(mesh=mesh, tp=tp, dp=int(mesh.shape.get(DP_AXIS, 1)))


def _shard_mapped(fn, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with the varying-type check off: the ring treats
    its ``all_gather`` output as replicated, which the checker cannot
    always prove."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _pallas_chunk_matmul() -> bool:
    """Route int8 ring chunks through the Pallas dequant matmul? Checked
    against the env var DIRECTLY (not ``qm._pallas_int8_enabled``): the
    engine's process-wide ``disable_pallas_matmul`` on tp>1 meshes exists
    to protect GSPMD-partitioned call sites, and ring chunks are local
    calls that restriction does not apply to."""
    return os.environ.get("LLMQ_INT8_MATMUL", "").lower() == "pallas"


def _pallas_chunk_matmul_int4() -> bool:
    """int4 counterpart of :func:`_pallas_chunk_matmul` — same direct env
    check, same local-call exemption from the process-wide disable."""
    return os.environ.get("LLMQ_INT4_MATMUL", "").lower() == "pallas"


def _splits(n_out: int, tp: int) -> Tuple[int, bool]:
    """(chunk count, bidirectional?) for an output dim of ``n_out``."""
    if n_out % (2 * tp) == 0:
        return 2 * tp, True
    return tp, False


def _ring_reduce_scatter(plan: TpRingPlan, chunk_fn, n_out: int):
    """Shared ring body for the row-parallel (matmul -> reduce) forms.

    ``chunk_fn(x_local, operands, start, size)`` returns the LOCAL
    partial product for output columns ``[start, start+size)``. The ring
    rotates partial accumulators so that after tp-1 ``ppermute`` hops
    device ``i`` holds the fully reduced chunk ``i`` (and ``2i``/``2i+1``
    in the bidirectional split); each hop overlaps the next chunk's
    matmul. A tiled ``all_gather`` reassembles the replicated output.
    """
    tp = plan.tp
    nsplit, bidir = _splits(n_out, tp)
    size = n_out // nsplit
    fwd = [(j, (j + 1) % tp) for j in range(tp)]
    bwd = [(j, (j - 1) % tp) for j in range(tp)]

    def body(x_local, *operands):
        i = jax.lax.axis_index(TP_AXIS)

        if bidir:
            # Two counter-rotating rings share the steps: the forward
            # ring reduces the even chunks, the backward ring the odd
            # ones — one ppermute each way per step, so both ICI
            # directions of every link carry an accumulator while the
            # two chunk matmuls run.
            def even(s):
                return 2 * ((i + tp - 1 - s) % tp)

            def odd(s):
                return 2 * ((i + 1 + s) % tp) + 1

            acc_f = chunk_fn(x_local, operands, even(0) * size, size)
            acc_b = chunk_fn(x_local, operands, odd(0) * size, size)

            def step(s, carry):
                af, ab = carry
                af = jax.lax.ppermute(af, TP_AXIS, fwd)
                ab = jax.lax.ppermute(ab, TP_AXIS, bwd)
                af = af + chunk_fn(x_local, operands, even(s) * size, size)
                ab = ab + chunk_fn(x_local, operands, odd(s) * size, size)
                return af, ab

            acc_f, acc_b = jax.lax.fori_loop(1, tp, step, (acc_f, acc_b))
            # Device i ends with chunks 2i and 2i+1 — a contiguous
            # column block, so the tiled gather below concatenates the
            # devices' blocks back in order.
            local = jnp.concatenate([acc_f, acc_b], axis=-1)
        else:

            def chunk_of(s):
                return (i + tp - 1 - s) % tp

            acc = chunk_fn(x_local, operands, chunk_of(0) * size, size)

            def step(s, acc):
                acc = jax.lax.ppermute(acc, TP_AXIS, fwd)
                return acc + chunk_fn(x_local, operands, chunk_of(s) * size, size)

            local = jax.lax.fori_loop(1, tp, step, acc)
        return jax.lax.all_gather(local, TP_AXIS, axis=local.ndim - 1, tiled=True)

    return body


def _lead_axis(plan: TpRingPlan, m: int) -> Optional[str]:
    """Shard the flattened token axis over dp when it divides evenly —
    each dp row then runs its own tp ring over its own tokens, matching
    how GSPMD partitions a dp-sharded decode batch. Anything else
    (prefill's replicated [B*T] rows, odd sizes) stays replicated."""
    return DP_AXIS if plan.dp > 1 and m % plan.dp == 0 else None


def row_parallel_matmul(
    x: jnp.ndarray, w: Any, plan: Optional[TpRingPlan], name: str = "row"
) -> jnp.ndarray:
    """``x @ w`` for a row-parallel weight ([K, N] per layer, K sharded
    on tp) as a chunked ppermute ring; falls back to the literal
    ``qm.matmul`` (GSPMD inserts the all-reduce) when ``plan`` is None
    or the static shapes don't split over the ring. ``name`` (``o_proj``,
    ``down_proj``) names the ring in a profile:
    ``llmq.tp.allreduce.<name>``; the all-reduce GSPMD inserts carries
    the caller's scope, and ``scopes_from_hlo_text`` gives it that name."""
    quantized = qm.is_quantized(w)
    int4 = qm.is_int4(w)
    arr = w["q"] if quantized else w
    # int4 packs two K rows per byte: the CONTRACTION length is twice the
    # stored axis, and the packed axis itself must still split over tp.
    k_eff = arr.shape[0] * 2 if int4 else arr.shape[0]
    if (
        plan is None
        or arr.ndim != 2
        or k_eff % plan.tp != 0
        or arr.shape[1] % plan.tp != 0
        or x.shape[-1] != k_eff
        or (int4 and (arr.shape[0] % plan.tp != 0
                      or w["scale"].shape[0] % plan.tp != 0))
    ):
        return qm.matmul(x, w)
    K, N = k_eff, arr.shape[1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    lead_axis = _lead_axis(plan, x2.shape[0])
    use_pallas = quantized and not int4 and _pallas_chunk_matmul()
    use_pallas4 = int4 and _pallas_chunk_matmul_int4()

    if int4:

        def chunk(x_local, operands, start, size):
            q, scale, zero = operands
            qc = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
            sc = jax.lax.dynamic_slice_in_dim(scale, start, size, axis=1)
            zc = jax.lax.dynamic_slice_in_dim(zero, start, size, axis=1)
            if use_pallas4:
                from llmq_tpu.ops.pallas_matmul import int4_matmul_pallas

                return int4_matmul_pallas(
                    x_local, qc, sc, zc,
                    interpret=not on_tpu(),
                )
            return x_local @ qm.dequantize_int4_parts(
                qc, sc, zc, x_local.dtype
            )

        operands = (w["q"], w["scale"], w["zero"])
        # The affine zero-point does NOT commute across devices like
        # int8's end-scale, but each device's partial product uses the
        # fully dequantized LOCAL K rows, so the ring's cross-device sum
        # is an exact linear split of the contraction. Scale/zero shard
        # their group axis alongside q's packed K axis (groups align
        # with K shards because G % tp == 0, guarded above); at rest
        # they are replicated, so the reshard is a local slice.
        operand_specs = (
            P(TP_AXIS, None),
            P(TP_AXIS, None),
            P(TP_AXIS, None),
        )
    elif quantized:

        def chunk(x_local, operands, start, size):
            q, scale = operands
            qc = jax.lax.dynamic_slice_in_dim(q, start, size, axis=1)
            sc = jax.lax.dynamic_slice_in_dim(scale, start, size, axis=0)
            if use_pallas:
                from llmq_tpu.ops.pallas_matmul import int8_matmul_pallas

                return int8_matmul_pallas(
                    x_local, qc, sc,
                    interpret=not on_tpu(),
                )
            return (x_local @ qc.astype(x_local.dtype)) * sc.astype(
                x_local.dtype
            )

        operands = (w["q"], w["scale"])
        # Per-output-channel scales commute with the contraction AND with
        # the cross-device partial sums, so each chunk dequantizes with
        # its own scale slice; the scale vector is replicated.
        operand_specs = (P(TP_AXIS, None), P(None))
    else:

        def chunk(x_local, operands, start, size):
            (wl,) = operands
            return x_local @ jax.lax.dynamic_slice_in_dim(
                wl, start, size, axis=1
            )

        operands = (w,)
        operand_specs = (P(TP_AXIS, None),)

    fn = _shard_mapped(
        _ring_reduce_scatter(plan, chunk, N),
        plan.mesh,
        in_specs=(P(lead_axis, TP_AXIS), *operand_specs),
        out_specs=P(lead_axis, None),
    )
    with jax.named_scope(f"llmq.tp.allreduce.{name}"):
        return fn(x2, *operands).reshape(*lead, N)


def row_parallel_ragged_matmul(
    x: jnp.ndarray,  # [M, Im] grouped rows (tokens sorted by expert)
    w: Any,  # [E, Im, H] expert stack (plain or int8 dict)
    group_sizes: jnp.ndarray,  # [E]
    dtype,
    plan: Optional[TpRingPlan],
) -> jnp.ndarray:
    """MoE expert-down projection (``lax.ragged_dot`` over the grouped
    rows) as the same reduce ring: the per-expert contraction dim Im is
    tp-sharded, so each device's ragged_dot produces a partial sum that
    the ring reduces chunk by chunk. The token axis stays REPLICATED —
    ragged group boundaries don't align with a dp split of the rows."""
    quantized = qm.is_quantized(w)
    int4 = qm.is_int4(w)
    arr = w["q"] if quantized else w
    im_eff = arr.shape[1] * 2 if int4 else arr.shape[1]
    if (
        plan is None
        or arr.ndim != 3
        or im_eff % plan.tp != 0
        or arr.shape[2] % plan.tp != 0
        or x.shape[-1] != im_eff
        or (int4 and (arr.shape[1] % plan.tp != 0
                      or w["scale"].shape[1] % plan.tp != 0))
    ):
        return jax.lax.ragged_dot(x, qm.dequantize(w, dtype), group_sizes)
    H = arr.shape[2]

    if int4:

        def chunk(x_local, operands, start, size):
            q, scale, zero, gs = operands
            qc = jax.lax.dynamic_slice_in_dim(q, start, size, axis=2)
            sc = jax.lax.dynamic_slice_in_dim(scale, start, size, axis=2)
            zc = jax.lax.dynamic_slice_in_dim(zero, start, size, axis=2)
            return jax.lax.ragged_dot(
                x_local, qm.dequantize_int4_parts(qc, sc, zc, dtype), gs
            )

        operands = (w["q"], w["scale"], w["zero"], group_sizes)
        # Packed Im axis and the matching group axis shard together (see
        # row_parallel_matmul); each device dequantizes its own expert
        # Im-rows per chunk, so the ring reduce is again an exact linear
        # split of the per-expert contraction.
        operand_specs = (
            P(None, TP_AXIS, None),
            P(None, TP_AXIS, None),
            P(None, TP_AXIS, None),
            P(None),
        )
    elif quantized:

        def chunk(x_local, operands, start, size):
            q, scale, gs = operands
            qc = jax.lax.dynamic_slice_in_dim(q, start, size, axis=2)
            sc = jax.lax.dynamic_slice_in_dim(scale, start, size, axis=1)
            deq = qc.astype(dtype) * sc.astype(dtype)[:, None, :]
            return jax.lax.ragged_dot(x_local, deq, gs)

        operands = (w["q"], w["scale"], group_sizes)
        operand_specs = (P(None, TP_AXIS, None), P(None, None), P(None))
    else:

        def chunk(x_local, operands, start, size):
            wl, gs = operands
            return jax.lax.ragged_dot(
                x_local,
                jax.lax.dynamic_slice_in_dim(wl, start, size, axis=2),
                gs,
            )

        operands = (w, group_sizes)
        operand_specs = (P(None, TP_AXIS, None), P(None))

    fn = _shard_mapped(
        _ring_reduce_scatter(plan, chunk, H),
        plan.mesh,
        in_specs=(P(None, TP_AXIS), *operand_specs),
        out_specs=P(None, None),
    )
    return fn(x, *operands)


def column_parallel_matmul(
    x: jnp.ndarray, w: Any, plan: Optional[TpRingPlan]
) -> jnp.ndarray:
    """all-gather -> matmul as a ring, for column-parallel weights fed by
    a FEATURE-SHARDED activation: each device starts with its x column
    chunk, rotates it around the ring, and multiplies each arriving
    chunk against the matching row block of its local [K, N/tp] weight
    shard — the gather rides the ring hops instead of one blocking
    all-gather up front. Output is [.., N] sharded on N, like GSPMD's
    column-parallel output.

    The engine's dataflow keeps activations replicated between layers
    (the row-parallel ring ends in a tiled all_gather), so the model
    does not call this today; it exists — and is unit-tested — as the
    column-parallel counterpart for a sequence-parallel dataflow that
    keeps activations reduce-scattered between the projections, and as
    the measured shape in ``tools/profile_collectives.py``."""
    quantized = qm.is_quantized(w)
    int4 = qm.is_int4(w)
    arr = w["q"] if quantized else w
    k_eff = arr.shape[0] * 2 if int4 else arr.shape[0]
    if (
        plan is None
        or arr.ndim != 2
        or k_eff % plan.tp != 0
        or arr.shape[1] % plan.tp != 0
        or x.shape[-1] != k_eff
    ):
        return qm.matmul(x, w)
    K, N = k_eff, arr.shape[1]
    tp = plan.tp
    size = K // tp
    lead = x.shape[:-1]
    x2 = x.reshape(-1, K)
    fwd = [(j, (j + 1) % tp) for j in range(tp)]

    def body(x_local, wl, *rest):
        i = jax.lax.axis_index(TP_AXIS)
        if int4:
            # The affine zero-point can't ride the int8 end-scale trick,
            # and the ring walks the FULL local K — dequantize this
            # device's [K, N/tp] column shard once up front (the weight
            # here is N-sharded, so packing and groups are untouched).
            scale_l, zero_l = rest
            wl = qm.dequantize_int4_parts(wl, scale_l, zero_l, x_local.dtype)
            rest = ()

        def partial_for(held, s):
            src = (i - s) % tp  # which x chunk `held` is, after s hops
            wr = jax.lax.dynamic_slice_in_dim(wl, src * size, size, axis=0)
            return held @ wr.astype(held.dtype)

        acc = partial_for(x_local, 0)

        def step(s, carry):
            held, acc = carry
            held = jax.lax.ppermute(held, TP_AXIS, fwd)
            return held, acc + partial_for(held, s)

        _, acc = jax.lax.fori_loop(1, tp, step, (x_local, acc))
        if rest:  # int8: per-column scale shard applies at the end
            (scale_local,) = rest
            acc = acc * scale_local.astype(acc.dtype)
        return acc

    if int4:
        operands = (w["q"], w["scale"], w["zero"])
        operand_specs = (P(None, TP_AXIS), P(None, TP_AXIS), P(None, TP_AXIS))
    elif quantized:
        operands = (w["q"], w["scale"])
        operand_specs = (P(None, TP_AXIS), P(TP_AXIS))
    else:
        operands = (w,)
        operand_specs = (P(None, TP_AXIS),)
    fn = _shard_mapped(
        body,
        plan.mesh,
        in_specs=(P(None, TP_AXIS), *operand_specs),
        out_specs=P(None, TP_AXIS),
    )
    return fn(x2, *operands).reshape(*lead, N)
