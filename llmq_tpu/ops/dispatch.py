"""Attention backend dispatch: Pallas kernels on TPU, XLA on a CPU run.

The model code (``models/transformer.py``) calls these two functions; the
backend is resolved once at trace time:

- ``LLMQ_ATTN_BACKEND`` env var: ``auto`` (default) | ``pallas`` | ``xla``.
- ``auto`` → compiled Pallas on a TPU; the pure-XLA reference when the
  process was started with ``JAX_PLATFORMS=cpu``; an error on anything
  else (``utils/platform.on_tpu`` — a process that lost its chip must
  not carry on as a CPU run).
- ``pallas`` on such a CPU run runs the kernels in interpreter mode
  (slow, for numerics tests — tests/test_pallas_attention.py). On a TPU a
  kernel is never interpreted.

Tensor parallelism: under GSPMD a ``pallas_call`` is an opaque custom
call XLA cannot partition, so when a mesh with a >1 ``tp`` axis is
passed, the kernel is wrapped in ``jax.shard_map`` sharded over the
head axes (attention is embarrassingly parallel over heads). Head counts
that don't divide tp, or that leave a shard a single kv head, take the
XLA path, which GSPMD partitions however it likes — mirrors the
replication rule in ``parallel/sharding.py`` — and say so once in the
log (``_tp_heads_ok``).
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from llmq_tpu.ops import attention as xla_ops
from llmq_tpu.ops import pallas_attention as pk
from llmq_tpu.ops import ring_attention as ring
from llmq_tpu.parallel.mesh import SP_AXIS, TP_AXIS
from llmq_tpu.utils.platform import on_tpu

logger = logging.getLogger(__name__)

_WINDOW_DISABLED = 1 << 30


def resolve_backend() -> str:
    env = os.environ.get("LLMQ_ATTN_BACKEND", "auto").lower()
    if env == "auto":
        return "pallas" if on_tpu() else "xla"
    if env not in ("pallas", "xla"):
        raise ValueError(f"LLMQ_ATTN_BACKEND={env!r} (want auto|pallas|xla)")
    return env


def _interpret() -> bool:
    return not on_tpu()


@functools.lru_cache(maxsize=None)
def _tp_heads_ok(n_heads: int, n_kv: int, tp: int) -> bool:
    """The documented shape rules for the head-sharded kernels under tp:
    both head counts divide tp, and a shard keeps at least two kv heads.

    The second is the pool's layout: the kernels read pages as
    ``[page, n_kv, d]`` blocks with the kv heads on the sublanes, and one
    bf16 head does not fill a packed sublane pair. The compiler then pads
    the shard's pool to twice its bytes AND, preferring its own compact
    layout for the KV scatter, copies the whole pool into the kernel's
    layout in every layer (compiled for v5e, qwen2.5-7b at tp=4: a
    temporary the size of the pools; tests/test_tpu_compile.py). Such a
    model takes the XLA path with the pool in the compiler's layout.

    Cached per shape so giving way is logged once, not at every trace."""
    if tp == 1:
        return True
    if n_heads % tp or n_kv % tp:
        why = "do not divide"
    elif n_kv // tp < 2:
        why = "leave fewer than two kv heads a shard at"
    else:
        return True
    logger.warning(
        "attention: %d query / %d kv heads %s tp=%d; this model takes the "
        "XLA attention path under GSPMD, not the Pallas kernels",
        n_heads, n_kv, why, tp,
    )
    return False


def _shard_over_heads(call, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` for a head-sharded kernel call. The varying-type
    check is off: a ``pallas_call`` declares plain output shapes, and the
    checker (on by default since shard_map left ``jax.experimental``)
    refuses an output that does not say how it varies over the mesh."""
    return jax.shard_map(
        call, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _window_scalar(sliding_window) -> jnp.ndarray:
    if sliding_window is None:
        return jnp.asarray([_WINDOW_DISABLED], jnp.int32)
    return jnp.asarray(sliding_window, jnp.int32).reshape(1)


def _tp_degree(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get(TP_AXIS, 1))


def prefill_attention(
    q: jnp.ndarray,  # [B, T, n_heads, d]
    k: jnp.ndarray,  # [B, T, n_kv, d]
    v: jnp.ndarray,
    *,
    scale: float,
    lengths: Optional[jnp.ndarray] = None,  # [B]
    sliding_window=None,
    softcap: Optional[float] = None,
    mesh: Optional[Mesh] = None,
    backend: str = "auto",
) -> jnp.ndarray:
    backend = resolve_backend() if backend == "auto" else backend
    n_heads, n_kv = q.shape[2], k.shape[2]
    # Context parallelism: an sp>1 mesh axis ring-shards the sequence
    # (ops/ring_attention.py) — long-context prefill never materializes
    # full-T activations per device.
    sp = int(mesh.shape.get(SP_AXIS, 1)) if mesh is not None else 1
    if sp > 1 and q.shape[1] % sp == 0:
        with jax.named_scope("llmq.attn.ring"):
            return ring.ring_prefill_attention(
                q, k, v, scale=scale, mesh=mesh, lengths=lengths,
                sliding_window=sliding_window, softcap=softcap,
            )
    tp = _tp_degree(mesh)
    tp_ok = _tp_heads_ok(n_heads, n_kv, tp)
    if backend != "pallas" or not tp_ok:
        with jax.named_scope("llmq.attn.xla"):
            return xla_ops.full_prefill_attention(
                q, k, v, scale=scale, lengths=lengths,
                sliding_window=sliding_window, softcap=softcap,
            )
    if lengths is None:
        lengths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    window = _window_scalar(sliding_window)

    def call(q, k, v, lengths, window):
        return pk.flash_prefill_attention_pallas(
            q, k, v, lengths, window,
            scale=scale, softcap=softcap, interpret=_interpret(),
        )

    if tp > 1:
        assert mesh is not None
        head = P(None, None, TP_AXIS, None)
        call = _shard_over_heads(
            call,
            mesh=mesh,
            in_specs=(head, head, head, P(), P()),
            out_specs=head,
        )
    with jax.named_scope("llmq.attn.flash_prefill"):
        return call(q, k, v, lengths, window)


def chunked_prefill_attention(
    q: jnp.ndarray,  # [B, C, n_heads, d]
    k_pages: jnp.ndarray,  # [L, P, page, n_kv, d] (or unstacked)
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, pages_per_seq]
    q_positions: jnp.ndarray,  # [B, C] absolute (−1 = padding)
    *,
    scale: float,
    sliding_window=None,
    softcap: Optional[float] = None,
    mesh: Optional[Mesh] = None,
    backend: str = "auto",
    layer: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Chunk-of-queries attention against the paged cache (chunked
    prefill). Pallas on TPU (pages DMA'd through the block table, never
    gathered — the XLA path materializes the full context per layer),
    pure XLA elsewhere.

    CONTRACT: on the pallas path each row's valid positions must be a
    LEADING CONTIGUOUS run (``q_positions[b] = [s, s+1, ..., s+n−1, −1…]``
    — exactly how the engine's chunk loop builds them); the kernel takes
    the run as (start, count) and cannot represent gaps. Positions are
    traced values, so this is the caller's responsibility — callers with
    arbitrary position grids must pass ``backend="xla"``.
    """
    backend = resolve_backend() if backend == "auto" else backend
    n_heads, n_kv = q.shape[2], k_pages.shape[-2]
    tp = _tp_degree(mesh)
    tp_ok = _tp_heads_ok(n_heads, n_kv, tp)
    stacked = k_pages.ndim == 5
    if backend != "pallas" or not tp_ok:
        with jax.named_scope("llmq.attn.xla"):
            return xla_ops.paged_prefill_attention(
                q, k_pages, v_pages, block_tables, q_positions,
                scale=scale, sliding_window=sliding_window, softcap=softcap,
                layer=layer,
            )
    window = _window_scalar(sliding_window)
    li = (
        jnp.asarray(layer, jnp.int32).reshape(1)
        if layer is not None
        else jnp.zeros((1,), jnp.int32)
    )
    # Contiguous-run form: start = first valid position, count of valids.
    num_valid = (q_positions >= 0).sum(axis=1).astype(jnp.int32)
    chunk_start = jnp.where(num_valid > 0, q_positions[:, 0], 0)

    def call(q, kp, vp, bt, cs, nv, window, li):
        return pk.paged_prefill_attention_pallas(
            q, kp, vp, bt, cs, nv, window, li,
            scale=scale, softcap=softcap, interpret=_interpret(),
        )

    if tp > 1:
        assert mesh is not None
        kv_spec = (
            P(None, None, None, TP_AXIS, None)
            if stacked
            else P(None, None, TP_AXIS, None)
        )
        call = _shard_over_heads(
            call,
            mesh=mesh,
            in_specs=(
                P(None, None, TP_AXIS, None),
                kv_spec, kv_spec, P(), P(), P(), P(), P(),
            ),
            out_specs=P(None, None, TP_AXIS, None),
        )
    with jax.named_scope("llmq.attn.paged_prefill"):
        return call(
            q, k_pages, v_pages, block_tables, chunk_start, num_valid,
            window, li,
        )


def decode_kernel_plan(
    n_heads: int, n_kv: int, kv_dtype, mesh: Optional[Mesh] = None,
    backend: str = "auto",
) -> str:
    """The decode-attention schedule a pool of this shape runs, and the
    only place that chooses it: ``"xla"`` (the backend is not pallas, or
    ``_tp_heads_ok`` refuses the head counts), ``"v1"`` (pallas, and a
    shard's pool is padded on the chip, which only v1's BlockSpec pipeline
    can read), else ``"live"``.

    A pure function of its arguments and the backend: it is consulted at
    trace time from inside jitted step functions, including from every
    iteration of the fused decode-block ``lax.scan``, so it must resolve
    identically on every call within one process or the scan body would
    diverge between iterations."""
    backend = resolve_backend() if backend == "auto" else backend
    tp = _tp_degree(mesh)
    if backend != "pallas" or not _tp_heads_ok(n_heads, n_kv, tp):
        return "xla"
    return "v1" if pk.pool_rows_padded(n_kv // tp, kv_dtype) else "live"


def latent_decode_kernel_plan(
    rank: int, page_size: int, width: int, pool_dtype,
    mesh: Optional[Mesh] = None, backend: str = "auto",
) -> str:
    """The decode-attention schedule a LATENT pool of ``[page_size,
    width]`` pages (the first ``rank`` values of a row its latent part)
    runs, and the only place that chooses it: ``"latent_live"``
    (``pallas_attention.latent_paged_decode_attention_live``: each row's
    own live pages, copied once and used for scores and values) where the
    backend is pallas, one device holds the pool whole and the chip does
    not pad its pages; else ``"xla"``
    (``ops/attention.latent_paged_decode_attention``: the CPU, a mesh of
    several devices, a pool of one-byte values, rows or a rank that are
    not whole lane tiles). One kernel for every head count, ling's 32 as
    openpangu's 128: the pages a chunk copies and an update folds in come
    from the shapes (``pallas_attention._latent_decode_schedule``), and on the
    chip it was the faster at both (PERF.md section 6, PR 40), so the head
    count is no argument of the plan.

    The contract of :func:`decode_kernel_plan`: a pure function of its
    arguments and the backend, consulted at trace time."""
    backend = resolve_backend() if backend == "auto" else backend
    if (
        backend != "pallas"
        or (mesh is not None and mesh.size > 1)
        or jnp.dtype(pool_dtype).itemsize < 2
        or pk.latent_pool_padded(page_size, width, rank, pool_dtype)
    ):
        return "xla"
    return "latent_live"


def kda_decode_plan(
    rows, state_dtype, d_k: int, d_v: int,
    mesh: Optional[Mesh] = None, backend: str = "auto",
) -> str:
    """How a decode step updates the KDA state rows of a state pool
    ``[L, R, n, d_k, d_v]``, and the only place that chooses it:
    ``"inplace"`` (``pallas_delta_rule.kda_step_inplace``: each head's
    state read once and written once where it lies) where the backend is
    pallas, one device holds the pool whole, ``rows`` is an int (the first
    of one contiguous run, a slot a row: the engine's decode step), the
    state is float32 and a head's state is whole lane tiles; else
    ``"xla"`` (``delta_rule.kda_step`` between the read and the write of
    ``models/hybrid._state_row_access``: the CPU, an array of rows, a
    mesh of several devices, a state in another precision, the tiny test
    models' heads).

    The contract of :func:`decode_kernel_plan`: a pure function of its
    arguments and the backend, consulted at trace time."""
    backend = resolve_backend() if backend == "auto" else backend
    if (
        backend != "pallas"
        or (mesh is not None and mesh.size > 1)
        or not isinstance(rows, int)
        or jnp.dtype(state_dtype) != jnp.float32
        or d_k % 128
        or d_v % 128
    ):
        return "xla"
    return "inplace"


def grouped_experts_plan(
    rows: int, dense_rows: int, x_dtype, w_dtype, k: int, n: int,
    mesh: Optional[Mesh] = None, backend: str = "auto",
) -> str:
    """How the routed experts' products of ``rows`` rows (tokens) are
    computed for a group of layers whose expert matrices are ``[layers,
    held, k, n]`` and ``[layers, held, n, k]`` of ``w_dtype`` (``None``:
    no plain array, a quantised leaf), and the only place that chooses
    it: ``"stacked"`` (``pallas_grouped_matmul.grouped_matmul_stacked``:
    the whole stack and the layer's index, an expert's blocks fetched from
    where they lie) where the backend is pallas, one device holds the
    stack whole, the rows are more than the ``dense_rows`` the dense form
    takes (``models/hybrid.DENSE_EXPERT_ROWS``: a prefill), rows and
    weights are bfloat16 and ``k`` and ``n`` are whole lane tiles; else
    ``"xla"`` (the dense form, or ``lax.ragged_dot`` on each layer's own
    matrices: a decode step, the CPU, a mesh of several devices, another
    precision, the tiny test models' widths).

    The contract of :func:`decode_kernel_plan`: a pure function of its
    arguments and the backend, consulted at trace time."""
    backend = resolve_backend() if backend == "auto" else backend
    if (
        backend != "pallas"
        or (mesh is not None and mesh.size > 1)
        or rows <= dense_rows
        or jnp.dtype(x_dtype) != jnp.bfloat16
        or w_dtype is None
        or jnp.dtype(w_dtype) != jnp.bfloat16
        or k % 128
        or n % 128
    ):
        return "xla"
    return "stacked"


#: ``num_heads x T`` of a padded prefill bucket from which expanded latent
#: attention is the flash kernel. Measured on a v5e (PERF.md section 6, PRs
#: 53-57; ``tools/mla_prefill_bench.py``): from here the kernel saves a
#: twentieth or more of the shape's whole 1 x T prefill program in both
#: presets that have the layer (openpangu's 128 x 1,024: 23.5 %; ling's
#: 32 x 4,096: 4.1 %); a step under it (2**16) ling's 32 x 2,048 saves half
#: a percent, a kernel instance in every program a cell warms for nothing
#: a user sees (PR 54), and openpangu's 128 x 512 a tenth of 30 ms.
MLA_FLASH_HEAD_TOKENS = 2**17


def mla_prefill_plan(
    num_heads: int, tokens: int, dtype, d_content: int, d_rope: int, d_v: int,
    mesh: Optional[Mesh] = None, backend: str = "auto",
) -> str:
    """How a prefill of ``tokens`` padded positions a row computes the
    attention of expanded latent attention (``num_heads`` heads, scores
    over ``d_content + d_rope``, values over ``d_v``), and the only place
    that chooses it: ``"flash"``
    (``pallas_attention.mla_flash_prefill_attention``) where the backend is
    pallas, one device holds the rows whole, they are bfloat16, the content
    and value parts of a head are one size in whole lane tiles (the kernel
    reads them as blocks of lanes of one row) and the rotary part half
    tiles, AND ``num_heads x tokens`` reaches
    ``MLA_FLASH_HEAD_TOKENS``; else ``"xla"``
    (``ops/attention.blocked_prefill_attention``: the CPU, a mesh of
    several devices, another precision, the tiny test models' heads, and
    every shape in which the attention is too small a part of its program
    to pay for a kernel instance: ling's 32 heads under 4,096 positions).
    One algorithm engaged by size, not by model: the rows a program takes
    at a time do not enter.

    The contract of :func:`decode_kernel_plan`: a pure function of its
    arguments and the backend, consulted at trace time."""
    backend = resolve_backend() if backend == "auto" else backend
    if (
        backend != "pallas"
        or (mesh is not None and mesh.size > 1)
        or jnp.dtype(dtype) != jnp.bfloat16
        or d_content % 128
        or d_v != d_content
        or d_rope % 64
        or num_heads * tokens < MLA_FLASH_HEAD_TOKENS
    ):
        return "xla"
    return "flash"


def mla_prefill_attention(
    q_c: jnp.ndarray,  # [B, T, n, d_content]
    q_r: jnp.ndarray,  # [B, T, n, d_rope]
    kv: jnp.ndarray,  # [B, T, n, d_content + d_v]: a head's keys, then its values
    k_r: jnp.ndarray,  # [B, T, d_rope] the rotary key all heads share
    *,
    scale: float,
    lengths: jnp.ndarray,  # [B]
    plan: str,
) -> jnp.ndarray:
    """Causal attention of expanded latent attention over a right-padded
    prompt by the form :func:`mla_prefill_plan` named. No
    ``jax.named_scope`` of its own: the caller's
    (``llmq.attn.mla_prefill``) stays the kernel's innermost scope."""
    if plan == "flash":
        return pk.mla_flash_prefill_attention(
            q_c, q_r, kv, k_r, lengths, scale=scale, interpret=_interpret()
        )
    B, T, n, _ = kv.shape
    d_content = q_c.shape[-1]
    k_c, v = kv[..., :d_content], kv[..., d_content:]
    k = jnp.concatenate(
        [k_c, jnp.broadcast_to(k_r[:, :, None, :], (B, T, n, k_r.shape[-1]))],
        axis=-1,
    )
    return xla_ops.blocked_prefill_attention(
        jnp.concatenate([q_c, q_r], axis=-1), k, v, scale=scale, lengths=lengths
    )


def latent_decode_attention(
    q: jnp.ndarray,  # [S, n_heads, W] absorbed query
    pages: jnp.ndarray,  # [L, P, page_size, Wp] the latent pool
    block_tables: jnp.ndarray,  # [S, pages_per_seq]
    context_lens: jnp.ndarray,  # [S] INCLUDING the new token
    *,
    scale: float,
    rank: int,
    layer: jnp.ndarray,
    mesh: Optional[Mesh] = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Absorbed decode attention over the latent pool by the schedule
    :func:`latent_decode_kernel_plan` names. No ``jax.named_scope`` of its
    own: the caller's (``llmq.attn.mla_decode``) has to stay the
    innermost scope of the kernel, or the benchmark's ``decode_mla_ms``
    would lose the kernel's time."""
    plan = latent_decode_kernel_plan(
        rank, *pages.shape[2:], pages.dtype, mesh, backend
    )
    if plan == "xla":
        return xla_ops.latent_paged_decode_attention(
            q, pages, block_tables, context_lens,
            scale=scale, rank=rank, layer=layer,
        )
    return pk.latent_paged_decode_attention_live(
        q, pages, block_tables, context_lens, layer,
        scale=scale, rank=rank, interpret=_interpret(),
    )


def verify_kernel_plan(
    n_heads: int, n_kv: int, mesh: Optional[Mesh] = None,
    backend: str = "auto",
) -> str:
    """The kernel the speculative verify step resolves to for these
    shapes. Verify is multi-query decode — Q = spec_tokens+1 query
    positions per row against the paged cache — which is exactly the
    chunked-prefill shape, so the plan mirrors
    :func:`chunked_prefill_attention`'s resolution (pallas paged-prefill
    kernel on TPU, XLA reference elsewhere) rather than the single-query
    decode schedules.

    Same contract as :func:`decode_kernel_plan`: a pure function of
    (shapes, mesh, backend), consulted at trace time from every iteration
    of the fused verify ``lax.scan``."""
    backend = resolve_backend() if backend == "auto" else backend
    tp = _tp_degree(mesh)
    if backend != "pallas" or not _tp_heads_ok(n_heads, n_kv, tp):
        return "xla"
    return "chunked_prefill"


def mixed_kernel_plan(
    n_heads: int, n_kv: int, mesh: Optional[Mesh] = None,
    backend: str = "auto",
) -> str:
    """The kernel of the fused mixed prefill+decode step: one [S, C]
    query grid where every active decode row carries a single position (a
    one-element leading run at its context length) and the piggybacked
    prefill row carries its budgeted chunk segment (a leading contiguous
    run at the chunk offset) — BOTH forms satisfy the
    leading-contiguous-run contract of :func:`chunked_prefill_attention`,
    so the mixed step scores through the same paged path speculative
    ``verify`` already uses, and the plan is :func:`verify_kernel_plan`'s."""
    return verify_kernel_plan(n_heads, n_kv, mesh, backend)


def resolve_tp_overlap(
    mode: str,
    mesh: Optional[Mesh],
    *,
    hidden_size: Optional[int] = None,
    intermediate_size: Optional[int] = None,
    max_seqs: Optional[int] = None,
    logger=None,
) -> str:
    """Resolve ``EngineConfig.tp_overlap`` to the mode the engine will
    actually run: ``"on"`` (chunked ppermute rings from
    ``ops/collective_matmul.py`` replace GSPMD's per-layer all-reduces)
    or ``"off"`` (the literal pre-existing programs).

    Unlike the kernel plans above, this is resolved ONCE at engine build
    time and carried as a static field on the ``Transformer`` — so the
    ``auto`` branch is free to run a subprocess A/B (it never executes at
    trace time). The ``LLMQ_TP_OVERLAP`` env pin wins over the config
    value, and any mesh
    without a tp axis degenerates to ``off`` (there is no all-reduce to
    hide).
    """
    env = (os.environ.get("LLMQ_TP_OVERLAP") or "").lower()
    if env:
        if env not in ("off", "on", "auto"):
            raise ValueError(f"LLMQ_TP_OVERLAP={env!r} (want off|on|auto)")
        mode = env
    mode = (mode or "off").lower()
    if mode not in ("off", "on", "auto"):
        raise ValueError(f"tp_overlap={mode!r} (want off|on|auto)")
    if _tp_degree(mesh) <= 1:
        return "off"
    if mode != "auto":
        return mode
    if not on_tpu() or not (hidden_size and intermediate_size):
        # Nothing to measure on a CPU run (ICI overlap is the whole
        # point), and without shapes an A/B would be meaningless.
        return "off"
    from llmq_tpu.engine.kernel_autotune import autotune_tp_overlap

    choice = autotune_tp_overlap(
        hidden_size=hidden_size,
        intermediate_size=intermediate_size,
        max_seqs=max_seqs or 192,
        tp=_tp_degree(mesh),
        logger=logger,
    )
    return choice if choice in ("on", "off") else "off"


def decode_attention(
    q: jnp.ndarray,  # [S, n_heads, d]
    k_pages: jnp.ndarray,  # [Pg, page_size, n_kv, d] or [L, Pg, ...]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [S, pages_per_seq]
    context_lens: jnp.ndarray,  # [S] INCLUDING the new token
    *,
    scale: float,
    sliding_window=None,
    softcap: Optional[float] = None,
    mesh: Optional[Mesh] = None,
    backend: str = "auto",
    layer: Optional[jnp.ndarray] = None,  # required when pages are stacked
) -> jnp.ndarray:
    stacked = k_pages.ndim == 5
    plan = decode_kernel_plan(
        q.shape[1], k_pages.shape[-2], k_pages.dtype, mesh, backend
    )
    if plan == "xla":
        with jax.named_scope("llmq.attn.xla"):
            return xla_ops.paged_decode_attention(
                q, k_pages, v_pages, block_tables, context_lens,
                scale=scale, sliding_window=sliding_window, softcap=softcap,
                layer=layer,
            )
    window = _window_scalar(sliding_window)
    li = (
        jnp.asarray(layer, jnp.int32).reshape(1)
        if layer is not None
        else jnp.zeros((1,), jnp.int32)
    )

    kern = (
        pk.paged_decode_attention_pallas
        if plan == "v1"
        else pk.paged_decode_attention_live
    )

    def call(q, kp, vp, bt, cl, window, li):
        return kern(
            q, kp, vp, bt, cl, window, li,
            scale=scale, softcap=softcap, interpret=_interpret(),
        )

    tp = _tp_degree(mesh)
    if tp > 1:
        assert mesh is not None
        kv_spec = (
            P(None, None, None, TP_AXIS, None)
            if stacked
            else P(None, None, TP_AXIS, None)
        )
        call = _shard_over_heads(
            call,
            mesh=mesh,
            in_specs=(
                P(None, TP_AXIS, None),
                kv_spec,
                kv_spec,
                P(),
                P(),
                P(),
                P(),
            ),
            out_specs=P(None, TP_AXIS, None),
        )
    with jax.named_scope("llmq.attn.paged_decode"):
        return call(
            q, k_pages, v_pages, block_tables, context_lens, window, li
        )


# --- snapshot plane: whole-page KV movement ---------------------------------
#
# The snapshot codepaths (extract_request / insert_request / swap-to-host
# preemption) move request state page-at-a-time between the stacked device
# pools [L, Pg, page, n_kv, d] and host buffers. Pages are opaque here —
# fp8/int-quantized KV moves in its stored dtype, never dequantized.


def gather_kv_pages(pool: jnp.ndarray, page_idx: jnp.ndarray) -> jnp.ndarray:
    """Gather whole pages ``[L, n, page, n_kv, d]`` from a stacked pool by
    page index. Produces a fresh buffer, so the pool can be donated to a
    later dispatch while the host copy is still in flight."""
    return jnp.take(pool, page_idx, axis=1)


def insert_kv_pages(
    pool: jnp.ndarray, page_idx: jnp.ndarray, pages: jnp.ndarray
) -> jnp.ndarray:
    """Scatter whole pages back into a stacked pool at ``page_idx``. The
    caller jits this with the pool donated and the pool's layout/sharding
    pinned on the output, mirroring the decode-step KV plumbing."""
    return pool.at[:, page_idx].set(pages.astype(pool.dtype))


# --- numerics-integrity plane: on-device logit guards -----------------------


def logit_guard_stats(
    logits: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    max_abs: float,
    min_entropy: float,
):
    """Fold the cheap silent-corruption checks over one dispatch's logits.

    Returns ``(stats f32[3], bad bool[rows])`` where ``stats`` is
    ``[nonfinite_count, max_abs_logit, min_row_entropy_nats]`` reduced
    over the masked rows and ``bad`` flags each masked row that trips a
    check (any non-finite value; ``|logit| > max_abs`` when
    ``max_abs > 0``; softmax entropy below ``min_entropy`` nats when
    ``min_entropy > 0``). Thresholds are trace-time constants, so the
    whole guard is a handful of reductions fused into the step that
    already produced the logits — the verdict rides home with the
    sampled tokens at zero extra host syncs. Rows outside ``mask``
    contribute count 0 / max 0 / entropy +inf and are never flagged.
    """
    z = logits.astype(jnp.float32)
    row_mask = mask[:, None]
    finite = jnp.isfinite(z)
    nonfinite_rows = jnp.sum(
        jnp.logical_and(~finite, row_mask), axis=1
    ).astype(jnp.float32)
    zf = jnp.where(finite, z, 0.0)
    absmax_rows = jnp.max(jnp.where(row_mask, jnp.abs(zf), 0.0), axis=1)
    # Stable softmax entropy per row over the finite entries:
    # H = logsumexp(z) - sum(p * z). Non-finite entries get zero weight
    # so a single NaN cannot also poison the entropy lane.
    m = jnp.max(jnp.where(finite, zf, -jnp.inf), axis=1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    ez = jnp.where(finite, jnp.exp(zf - m), 0.0)
    sz = jnp.maximum(jnp.sum(ez, axis=1), 1e-30)
    ent = (jnp.log(sz) + m[:, 0]) - jnp.sum(ez * zf, axis=1) / sz
    ent_masked = jnp.where(mask, ent, jnp.inf)
    bad = jnp.logical_and(mask, nonfinite_rows > 0)
    if max_abs > 0:
        bad = jnp.logical_or(
            bad, jnp.logical_and(mask, absmax_rows > max_abs)
        )
    if min_entropy > 0:
        bad = jnp.logical_or(
            bad, jnp.logical_and(mask, ent_masked < min_entropy)
        )
    stats = jnp.stack(
        [
            jnp.sum(nonfinite_rows),
            jnp.max(jnp.where(mask, absmax_rows, 0.0)),
            jnp.min(ent_masked),
        ]
    )
    return stats, bad
