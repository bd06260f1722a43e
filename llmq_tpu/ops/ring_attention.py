"""Ring attention: causal prefill attention, context-parallel over ICI.

The reference has no sequence/context parallelism (SURVEY.md §2b — long
inputs were only capped by ``VLLM_MAX_MODEL_LEN``); this is a TPU-native
first-class capability: prompts longer than one chip's activation memory
are sharded over the mesh's ``sp`` axis and attention runs as a ring —
each device keeps its query block resident while the K/V blocks rotate
around the ring via ``lax.ppermute`` (neighbour hops on ICI), with
online-softmax accumulation so the full [T, T] score matrix never exists.

Memory per device: O(B * T/sp * H * d) activations — T scales linearly
with the ring size. Communication: (sp-1) neighbour hops of the local
K/V block per layer, fully overlappable with the block matmuls by XLA's
latency-hiding scheduler.

Composes with tensor parallelism: the head axes are sharded over ``tp``
in the same ``shard_map`` (attention is head-parallel; the ring only
moves the kv-head shard that lives with its tp rank).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmq_tpu.parallel.mesh import SP_AXIS, TP_AXIS

NEG_INF = -1e30


def _block_attend(
    q: jnp.ndarray,  # [B, Lq, H, d] f32
    k: jnp.ndarray,  # [B, Lk, n_kv, d] f32
    q_pos: jnp.ndarray,  # [Lq] global query positions
    k_pos: jnp.ndarray,  # [Lk] global key positions
    lengths: jnp.ndarray,  # [B]
    window: jnp.ndarray,  # [] int32 (huge = disabled)
    scale: float,
    softcap: Optional[float],
):
    """One (q-block, kv-block) interaction → masked scores [B, H, Lq, Lk]."""
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Lq, Lk]
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    mask = (k_pos[None, :] <= q_pos[:, None]) & (
        k_pos[None, :] > q_pos[:, None] - window
    )
    mask = mask[None, None] & (k_pos < lengths[:, None])[:, None, None, :]
    return jnp.where(mask, scores, NEG_INF)


def _ring_body(
    sp: int, scale: float, softcap: Optional[float], axes: tuple
):
    """Per-device ring loop (runs inside shard_map)."""

    def fn(q, k, v, lengths, window):
        # Local blocks: q/k/v [B, L, heads_local, d]; full f32 accumulation.
        B, L, H, d = q.shape
        r = jax.lax.axis_index(SP_AXIS)
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        q_pos = r * L + jnp.arange(L)
        # pcast: the accumulators become rank-varying inside the loop
        # (they depend on axis_index and the sharded q), so their initial
        # values must be marked varying over every manual mesh axis for
        # shard_map's type checker.
        m0, l0, acc0 = jax.lax.pcast(
            (
                jnp.full((B, H, L, 1), NEG_INF, jnp.float32),
                jnp.zeros((B, H, L, 1), jnp.float32),
                jnp.zeros((B, L, H, d), jnp.float32),
            ),
            axes,
            to="varying",
        )
        perm = [(j, (j + 1) % sp) for j in range(sp)]

        def body(i, carry):
            k_blk, v_blk, m, l, acc = carry
            src = (r - i) % sp  # rank whose block we currently hold
            k_pos = src * L + jnp.arange(L)
            scores = _block_attend(
                q32, k_blk, q_pos, k_pos, lengths, window, scale, softcap
            )
            m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            probs = jnp.exp(scores - m_new)
            l = alpha * l + jnp.sum(probs, -1, keepdims=True)
            n_rep = H // k_blk.shape[2]
            v_rep = (
                jnp.repeat(v_blk, n_rep, axis=2) if n_rep > 1 else v_blk
            )
            pv = jnp.einsum("bhqk,bkhd->bqhd", probs, v_rep)
            acc = acc * alpha.transpose(0, 2, 1, 3) + pv
            m = m_new
            # Rotate K/V one hop around the ring (skippable on the last
            # iteration, but a uniform body keeps the loop compact; XLA
            # overlaps the hop with the next block's matmul).
            k_blk = jax.lax.ppermute(k_blk, SP_AXIS, perm)
            v_blk = jax.lax.ppermute(v_blk, SP_AXIS, perm)
            return k_blk, v_blk, m, l, acc

        _, _, m, l, acc = jax.lax.fori_loop(
            0, sp, body, (k32, v32, m0, l0, acc0)
        )
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows stay finite
        out = acc / l.transpose(0, 2, 1, 3)
        return out.astype(q.dtype)

    return fn


def ring_prefill_attention(
    q: jnp.ndarray,  # [B, T, n_heads, d] (global shapes)
    k: jnp.ndarray,  # [B, T, n_kv, d]
    v: jnp.ndarray,
    *,
    scale: float,
    mesh: Mesh,
    lengths: Optional[jnp.ndarray] = None,  # [B]
    sliding_window=None,
    softcap: Optional[float] = None,
    shard_heads: bool = True,
) -> jnp.ndarray:
    """Causal (+ragged-length, +sliding-window, +softcap) attention with
    the sequence axis ring-sharded over the mesh's ``sp`` axis and —
    when ``shard_heads`` — the head axes over ``tp``.

    Requires T % sp == 0 (the engine's power-of-two prefill buckets
    guarantee it) and, for head sharding, head counts divisible by tp.
    """
    sp = int(mesh.shape.get(SP_AXIS, 1))
    B, T, n_heads, _ = q.shape
    n_kv = k.shape[2]
    if T % sp != 0:
        raise ValueError(f"T={T} not divisible by sp={sp}")
    if lengths is None:
        lengths = jnp.full((B,), T, jnp.int32)
    window = (
        jnp.asarray(1 << 30, jnp.int32)
        if sliding_window is None
        else jnp.asarray(sliding_window, jnp.int32).reshape(())
    )
    tp = int(mesh.shape.get(TP_AXIS, 1))
    head = (
        TP_AXIS
        if shard_heads and tp > 1 and n_heads % tp == 0 and n_kv % tp == 0
        else None
    )
    spec = P(None, SP_AXIS, head, None)
    # Defense in depth against the GSPMD back-propagation hazard class
    # (the MoE mixed-mesh bug): pin the operands to the ring layout
    # EXPLICITLY rather than letting the partitioner infer it from the
    # shard_map boundary. Downstream blocks whose preferred partitioning
    # differs (e.g. token-axis ops) then reshard HERE, visibly, instead
    # of silently repartitioning the ring inputs.
    ring_sharding = NamedSharding(mesh, spec)
    q, k, v = (
        jax.lax.with_sharding_constraint(x, ring_sharding)
        for x in (q, k, v)
    )
    varying = (SP_AXIS,) + ((TP_AXIS,) if head else ())
    fn = jax.shard_map(
        _ring_body(sp, scale, softcap, varying),
        mesh=mesh,
        in_specs=(spec, spec, spec, P(), P()),
        out_specs=spec,
    )
    return fn(q, k, v, lengths, window)
