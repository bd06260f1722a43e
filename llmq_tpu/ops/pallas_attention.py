"""Pallas TPU attention kernels: paged decode + flash prefill.

These are the compiled-native counterparts of vLLM's CUDA PagedAttention
(consumed by the reference at ``llmq/workers/vllm_worker.py:183-195`` via
``engine.generate``) — written TPU-first with Pallas/Mosaic instead of a
CUDA translation. Numerics are validated against the pure-XLA references
in ``ops/attention.py`` (tests/test_pallas_attention.py, interpret mode).

Design notes
------------
* **Paged decode** (`paged_decode_attention_pallas`): grid
  ``(num_seqs, num_kv_heads, pages_per_seq)``. The block table and context
  lengths ride in scalar-prefetch SMEM so each K/V page is DMA'd straight
  from HBM by the BlockSpec index_map — the gather the XLA reference
  materializes (``attention.py:96-97``) never exists on-chip. Online
  (flash) softmax accumulates across pages in VMEM scratch; pages past a
  sequence's context (or below its sliding window) are skipped via
  ``pl.when`` — the DMA still runs (fixed schedule) but the FLOPs don't.
* **Flash prefill** (`flash_prefill_attention_pallas`): classic
  flash-attention tiling, grid ``(batch, q_heads, q_blocks, kv_blocks)``,
  causal + ragged-length + sliding-window masking in-kernel, with whole
  kv-blocks skipped when outside the causal/window/length frontier.
  GQA is handled by the K/V index_map (``h // n_rep``) — no
  ``repeat_kv`` materialization.
* Sliding windows arrive as a **traced scalar** (layers are scanned, the
  per-layer window is data — see ``models/transformer.py``), so both
  kernels take it as a scalar-prefetch operand rather than a static.
* Softcap/scale are static config; masks use a large negative instead of
  ``-inf`` to keep softmax NaN-free for inactive slots (garbage rows are
  discarded by the caller, they must not poison the batch).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # VPU lane count: scratch m/l are stored lane-replicated


def _apply_softcap(scores: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _mul_dtype(q_dtype, kv_dtype):
    """Dtype the attention dots multiply in: the wider of the query and
    KV-pool dtypes — a narrow pool (fp8 KV cache) upcasts to the query
    dtype, a pool WIDER than the compute dtype (kv_dtype=f32 with bf16
    compute) keeps its precision. Explicit because jnp.promote_types
    refuses implicit 8-bit-float promotion by design."""
    qd, kd = jnp.dtype(q_dtype), jnp.dtype(kv_dtype)
    if kd.itemsize == 1:
        return qd
    if qd.itemsize == 1:
        return kd
    return jnp.promote_types(qd, kd)


# ---------------------------------------------------------------------------
# Paged decode
# ---------------------------------------------------------------------------


def _paged_decode_kernel(
    # scalar prefetch
    li_ref,  # [1] int32 — layer index into the stacked page pool
    bt_ref,  # [S, pages_per_seq] int32
    cl_ref,  # [S] int32 — context length INCLUDING the new token
    w_ref,  # [1] int32 — sliding window (huge = disabled)
    # blocked inputs
    q_ref,  # [1, n_heads, d]
    k_ref,  # [1, 1, page_size, n_kv, d] — one whole page, all kv heads
    v_ref,  # [1, 1, page_size, n_kv, d]
    # output
    o_ref,  # [1, n_heads, d]
    # scratch
    m_ref,  # [n_heads, LANES] f32, lane-replicated running max
    l_ref,  # [n_heads, LANES] f32, lane-replicated running denom
    acc_ref,  # [n_heads, d] f32
    *,
    scale: float,
    page_size: int,
    pages_per_seq: int,
    n_kv: int,
    softcap: Optional[float],
):
    # Mosaic requires the trailing two block dims be tile-aligned or span
    # the whole array, so a page is loaded with ALL kv heads and the GQA
    # groups are walked with a static (unrolled) loop — n_kv is small.
    s = pl.program_id(0)
    p = pl.program_id(1)
    ctx = cl_ref[s]
    window = w_ref[0]
    start = p * page_size
    group = q_ref.shape[1] // n_kv

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Page contributes iff it overlaps [max(0, ctx-window), ctx).
    live = jnp.logical_and(start < ctx, start + page_size > ctx - window)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [H, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, n_kv, d]
        v = v_ref[0, 0].astype(jnp.float32)
        for g in range(n_kv):
            rows = slice(g * group, (g + 1) * group)
            scores = (
                jax.lax.dot_general(
                    q[rows], k[:, g, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [group, page]
            scores = _apply_softcap(scores, softcap)
            kpos = start + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1
            )
            mask = jnp.logical_and(kpos < ctx, kpos >= ctx - window)
            scores = jnp.where(mask, scores, NEG_INF)

            m_prev = m_ref[rows, :1]
            l_prev = l_ref[rows, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[rows, :] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(probs, axis=1, keepdims=True),
                (group, l_ref.shape[1]),
            )
            m_ref[rows, :] = jnp.broadcast_to(
                m_new, (group, m_ref.shape[1])
            )
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + jax.lax.dot_general(
                probs, v[:, g, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(p == pages_per_seq - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # inactive slot: defined output
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "interpret"),
)
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # [S, n_heads, d]
    k_pages: jnp.ndarray,  # [P, page_size, n_kv, d] or [L, P, page, n_kv, d]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [S, pages_per_seq] int32
    context_lens: jnp.ndarray,  # [S] int32, INCLUDING the new token
    sliding_window: jnp.ndarray,  # [] or [1] int32 (huge = disabled)
    layer: Optional[jnp.ndarray] = None,  # traced layer index when stacked
    *,
    scale: float,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode attention over a (possibly layer-stacked) page pool.

    The stacked form is the hot path: the model's layer scan passes the
    whole ``[L, P, page, n_kv, d]`` pool plus a traced layer index, and
    the kernel's BlockSpec index_map addresses ``(layer, bt[s, p])``
    directly in HBM. The alternative — slicing ``k_pages[layer]`` and
    feeding the slice to an opaque custom call — makes XLA materialize a
    full per-layer pool copy every layer (~12 ms/step at 3B/64 slots,
    measured round 2), dwarfing the kernel itself (~1 ms).
    """
    S, n_heads, d = q.shape
    if k_pages.ndim == 4:  # single-layer callers: view as a 1-layer stack
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        layer = jnp.zeros((), jnp.int32)
    assert layer is not None, "stacked pages need a layer index"
    _, _, page_size, n_kv, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]

    kernel = functools.partial(
        _paged_decode_kernel,
        scale=scale,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        n_kv=n_kv,
        softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, pages_per_seq),
        in_specs=[
            pl.BlockSpec(
                (1, n_heads, d), lambda s, p, li, bt, cl, w: (s, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, page_size, n_kv, d),
                lambda s, p, li, bt, cl, w: (li[0], bt[s, p], 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, page_size, n_kv, d),
                lambda s, p, li, bt, cl, w: (li[0], bt[s, p], 0, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, n_heads, d), lambda s, p, li, bt, cl, w: (s, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, n_heads, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        jnp.asarray(sliding_window, jnp.int32).reshape(1),
        q,
        k_pages,
        v_pages,
    )
    return out


# ---------------------------------------------------------------------------
# Paged decode, live pages only: the default decode kernel
# ---------------------------------------------------------------------------
#
# v1 walks a grid that spans every page PLACE of every slot
# (``S x pages_per_seq`` steps a layer; 128 x 64 at the worker's default
# ``max_model_len``), and ``pl.when`` only skips a dead step's arithmetic.
# On the chip a dead step still costs ~0.09 us, which at short caches is
# most of a decode step (PERF.md section 5). Here the grid is ``(S,)`` and
# everything inside a step has a trip count read from ``context_lens``:
# a sequence's live pages come in chunks of ``C`` pages, every copy of a
# chunk is started before the first is waited on, the next chunk (or the
# next live sequence's first chunk) is in flight while this one computes,
# and an empty slot is one step that writes zeros.
#
# Two things set the time of a live page, and neither is the copy
# (measured on v5e, PERF.md section 6 "PR 30"):
#
# * One online-softmax update is a chain of dependent steps (MXU round
#   trip, two cross-lane reductions, exp, MXU round trip) of ~0.5 us
#   whatever it folds in. So an update folds in ``G`` pages at once, not
#   one: 8 pages of qwen2.5-3b's, and the kernel runs at the speed of its
#   copies. The pages of a group past the sequence's last are masked like
#   positions past the context (the buffers are zeroed once, so what they
#   hold is finite).
# * A page is computed on as ONE 2-D matrix. Where the kv heads of a token
#   fill whole 32-bit words (``n_kv * itemsize`` a multiple of 4: two bf16
#   heads, four fp8 heads, any float32 pool) the pool's ``[page, n_kv, d]``
#   page is a row-major ``[page * n_kv, d]`` matrix on the chip, byte for
#   byte, so the launcher's reshape is free (compiled for v5e: a bitcast)
#   and the kernel never separates the heads: every query head is scored
#   against every row, and a row of another kv head is masked like a
#   position past the context. That costs the MXU nothing it would not
#   spend anyway, and saves the sublane gather ``k[:, g, :]`` that v1 pays
#   a page. A pool whose heads do not fill a word (one bf16 head, two fp8
#   heads) is padded on the chip, Mosaic refuses to slice a page of it for
#   a hand-issued copy, and it stays on v1's BlockSpec pipeline
#   (``pool_rows_padded``; ``ops/dispatch.decode_kernel_plan`` decides).
#
# The arithmetic is v1's: K, V and the queries upcast to float32, float32
# running max, sum and accumulator. On the chip the float32 dots cost
# 0.7 % over bf16 operands, so there was nothing to buy with them.

_DECODE_STEP_BYTES = 512 * 1024  # K bytes one softmax update folds in
_DECODE_CHUNK_BYTES = 1024 * 1024  # K bytes in flight (x2 for V, x2 buffers)
_NOT_A_POSITION = 1 << 29  # a column of another kv head: past any context


def pool_rows_padded(n_kv: int, kv_dtype) -> bool:
    """Whether the chip pads a pool's ``[page, n_kv, d]`` pages: the kv
    heads of one token do not fill whole 32-bit words."""
    return (n_kv * jnp.dtype(kv_dtype).itemsize) % 4 != 0


def _decode_schedule(page_bytes: int) -> tuple:
    """(C, G): pages a chunk copies and pages one softmax update folds in,
    from the bytes of one K page alone. More than 8 pages an update or 16
    in flight bought nothing on the chip; a page of 1 MiB gets (1, 1)."""
    G = max(1, min(8, _DECODE_STEP_BYTES // page_bytes))
    return G * max(1, min(2, _DECODE_CHUNK_BYTES // (G * page_bytes))), G


def _walk_live_pages(
    s,  # this grid step's sequence
    S,  # the grid's
    bt_ref,  # [S, pages_per_seq] int32
    cl_ref,  # [S] int32 — context length INCLUDING the new token; 0: empty
    slot_ref,  # SMEM [1] int32: parity of the chunks consumed so far
    *,
    span,  # seq -> (first, last + 1) page places the sequence attends to
    chunk: int,  # C: page places a chunk copies into one of the two buffers
    group: int,  # G: page places one softmax update folds in; C = G or 2 G
    page_copies,  # (page id, buffer, place in it) -> the copies of one page
    clear,  # zero the buffers: a dead place beside a live one is finite
    prepare,  # context -> fold(first page place, buffer, place in it)
):
    """The schedule both live decode kernels run a grid step ``s`` of
    ``(S,)`` by, written once: THIS row's live page places in chunks of
    ``C``, every copy of a chunk started before the first wait, the next
    chunk (or the next live row's first) in flight into the other buffer
    meanwhile, ``G`` places an update, places past the row's last neither
    copied nor waited for. A kernel brings what differs: which places are
    live, one page's copies, its buffers, and the arithmetic of an update
    (``prepare`` is given the row's context once the first row's copies
    are in flight, sets the running softmax up and returns it). Nothing
    here asks which kernel called."""
    C, G = chunk, group

    def next_live(t):
        """The first sequence at or after ``t`` with a context, or S."""
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < S, cl_ref[jnp.minimum(t, S - 1)] == 0
            ),
            lambda t: t + 1,
            t,
        )

    def issue_chunk(seq, first_page, last_page, slot):
        """Start the copies of up to C pages from ``first_page``: all of
        them before anything waits."""

        def start(i, _):
            for copy in page_copies(bt_ref[seq, first_page + i], slot, i):
                copy.start()
            return 0

        jax.lax.fori_loop(0, jnp.minimum(C, last_page - first_page), start, 0)

    def issue_first_chunk(seq, slot):
        @pl.when(seq < S)
        def _go():
            first, last = span(seq)
            issue_chunk(seq, first, last, slot)

    @pl.when(s == 0)
    def _prime():
        if G > 1:
            clear()
        slot_ref[0] = 0
        issue_first_chunk(next_live(0), 0)

    ctx = cl_ref[s]
    first, last = span(s)
    n_chunks = (last - first + C - 1) // C
    slot0 = slot_ref[0]
    # The sequence whose first chunk rides behind this one's last. An
    # empty slot starts nothing: the live step before it already did.
    successor = next_live(jnp.where(n_chunks > 0, s + 1, S))
    fold = prepare(ctx)

    def attend_chunk(j, _):
        slot = jax.lax.rem(slot0 + j, 2)
        base = first + j * C
        n_here = jnp.minimum(C, last - base)

        # Keep the other buffer busy: this sequence's next chunk, or after
        # its last the successor's first.
        @pl.when(j + 1 < n_chunks)
        def _ahead():
            issue_chunk(s, base + C, last, 1 - slot)

        @pl.when(j + 1 == n_chunks)
        def _successor():
            issue_first_chunk(successor, 1 - slot)

        def attend(g, _):
            for i in range(G):

                @pl.when(g * G + i < n_here)
                def _wait(i=i):
                    for copy in page_copies(0, slot, g * G + i):
                        copy.wait()

            fold(base + g * G, slot, g * G)
            return 0

        if C == G:  # a chunk is one update
            attend(0, 0)
        else:
            jax.lax.fori_loop(0, (n_here + G - 1) // G, attend, 0)
        return 0

    jax.lax.fori_loop(0, n_chunks, attend_chunk, 0)
    slot_ref[0] = jax.lax.rem(slot0 + n_chunks, 2)


def _paged_decode_live_kernel(
    # scalar prefetch
    li_ref,  # [1] int32 — layer index into the stacked page pool
    bt_ref,  # [S, pages_per_seq] int32
    cl_ref,  # [S] int32 — context length INCLUDING the new token
    w_ref,  # [1] int32 — sliding window (huge = disabled)
    # inputs
    q_ref,  # [1, n_heads, d]
    k_hbm,  # [L, P, page * n_kv, d], left in HBM
    v_hbm,
    # output
    o_ref,  # [1, n_heads, d]
    # scratch
    m_ref,  # [n_heads, LANES] f32, lane-replicated running max
    l_ref,  # [n_heads, LANES] f32, lane-replicated running denom
    acc_ref,  # [n_heads, d] f32
    k_buf,  # [2, C * page * n_kv, d] pool dtype: two chunks of pages
    v_buf,
    k_sem,  # DMA [2, C]
    v_sem,
    slot_ref,  # SMEM [1] int32: parity of the chunks consumed so far
    *,
    scale: float,
    page_size: int,
    chunk: int,
    group: int,
    n_kv: int,
    softcap: Optional[float],
):
    C, G = chunk, group
    s = pl.program_id(0)
    S = pl.num_programs(0)
    li = li_ref[0]
    window = w_ref[0]
    H = q_ref.shape[1]
    rows = page_size * n_kv  # buffer rows a page takes

    def live_span(seq):
        """(first, last + 1) page places of ``seq`` that overlap the
        attended span [ctx - window, ctx); empty for an inactive slot."""
        ctx = cl_ref[seq]
        first = jnp.maximum(ctx - window, 0) // page_size
        return first, (ctx + page_size - 1) // page_size

    def page_copies(pid, slot, i):
        dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
        return (
            pltpu.make_async_copy(
                k_hbm.at[li, pid], k_buf.at[slot, dst], k_sem.at[slot, i]
            ),
            pltpu.make_async_copy(
                v_hbm.at[li, pid], v_buf.at[slot, dst], v_sem.at[slot, i]
            ),
        )

    def clear():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    def prepare(ctx):
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0].astype(jnp.float32)  # [H, d]
        # Column -> token offset from the group's first page, or "not a
        # position" where the column's kv head is not the row's.
        col = jax.lax.broadcasted_iota(jnp.int32, (H, G * rows), 1)
        row_kv = jax.lax.broadcasted_iota(jnp.int32, (H, G * rows), 0) // (
            H // n_kv
        )
        col_pos = jnp.where(col % n_kv == row_kv, col // n_kv, _NOT_A_POSITION)

        def attend_group(page, slot, i):
            """Fold the G page places from ``page`` (in buffer ``slot``
            from ``i``) into the running softmax of all heads."""
            at = pl.ds(pl.multiple_of(i * rows, rows), G * rows)
            k = k_buf[slot, at].astype(jnp.float32)  # [G * page * n_kv, d]
            v = v_buf[slot, at].astype(jnp.float32)
            scores = (
                jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )
            scores = _apply_softcap(scores, softcap)
            kpos = page * page_size + col_pos
            mask = jnp.logical_and(kpos < ctx, kpos >= ctx - window)
            scores = jnp.where(mask, scores, NEG_INF)

            m_prev = m_ref[:, :1]
            l_prev = l_ref[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[...] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(probs, axis=1, keepdims=True),
                l_ref.shape,
            )
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                probs, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        return attend_group

    _walk_live_pages(
        s, S, bt_ref, cl_ref, slot_ref,
        span=live_span, chunk=C, group=G,
        page_copies=page_copies, clear=clear, prepare=prepare,
    )

    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)  # inactive slot: zeros, never NaN
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "interpret"),
)
def paged_decode_attention_live(
    q: jnp.ndarray,  # [S, n_heads, d]
    k_pages: jnp.ndarray,  # [P, page_size, n_kv, d] or [L, P, page, n_kv, d]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [S, pages_per_seq] int32
    context_lens: jnp.ndarray,  # [S] int32, INCLUDING the new token
    sliding_window: jnp.ndarray,  # [] or [1] int32 (huge = disabled)
    layer: Optional[jnp.ndarray] = None,  # traced layer index when stacked
    *,
    scale: float,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged decode attention whose work follows the live cache (see the
    notes above). Same contract as :func:`paged_decode_attention_pallas`,
    except that a pool padded on the chip is refused: this schedule cannot
    copy its pages by hand. Nothing in the schedule depends on
    ``block_tables.shape[1]``."""
    n_kv, d = k_pages.shape[-2:]
    if pool_rows_padded(n_kv, k_pages.dtype):
        raise ValueError(
            f"a {k_pages.dtype} pool of {n_kv} kv head(s) is padded on the "
            "chip: paged_decode_attention_pallas reads it"
        )
    itemsize = jnp.dtype(k_pages.dtype).itemsize
    S, n_heads, _ = q.shape
    if k_pages.ndim == 4:  # single-layer callers: view as a 1-layer stack
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        layer = jnp.zeros((), jnp.int32)
    assert layer is not None, "stacked pages need a layer index"
    L, P, page_size = k_pages.shape[:3]
    rows = page_size * n_kv
    C, G = _decode_schedule(rows * d * itemsize)

    kernel = functools.partial(
        _paged_decode_live_kernel,
        scale=scale,
        page_size=page_size,
        chunk=C,
        group=G,
        n_kv=n_kv,
        softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, n_heads, d), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_heads, d), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, d), jnp.float32),
            pltpu.VMEM((2, C * rows, d), k_pages.dtype),
            pltpu.VMEM((2, C * rows, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, C)),
            pltpu.SemaphoreType.DMA((2, C)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, n_heads, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        jnp.asarray(sliding_window, jnp.int32).reshape(1),
        q,
        k_pages.reshape(L, P, rows, d),
        v_pages.reshape(L, P, rows, d),
    )


# ---------------------------------------------------------------------------
# Latent (MLA) paged decode, live pages only
# ---------------------------------------------------------------------------
#
# Absorbed decode attention over the latent pool, by the schedule of the
# live kernel above (``_walk_live_pages``, written once for both: grid
# ``(S,)``, a loop over THAT row's live pages, every copy of a chunk
# started before the first wait, the next chunk or the next live row's
# first in flight meanwhile). What this kernel brings to it differs in
# four ways.
#
# * There is one pool and a page of it is read ONCE and used twice: every
#   head scores the same cached row ``[c ; r]`` and the value is the row's
#   latent part, a lane-aligned slice of the same buffer. All heads share
#   the row, so the MXU sees ``M = n_heads`` with no repeat and no mask of
#   another head's columns.
# * The operands go into the MXU in the dtype the XLA loop multiplies in
#   (``_mul_dtype``: bf16 for a bf16 pool), with float32 accumulation and
#   a float32 softmax. The K/V kernel upcasts to float32 because at 16
#   heads it is bound by memory; at 128 heads absorbed decode is 242 FLOP a
#   cached byte against the chip's ridge of 240, and float32 dots would
#   make the MXU the wall.
# * Pages an update folds in follow the score tile ``[n_heads, G * page]``
#   float32 as well as the page's bytes: 8 pages at 128 heads, 16 at 32.
#   Measured on v5e (PERF.md section 6 "PR 40"; ``tools/decode_kernel_bench.py
#   --case latent``): at 128 heads the copies alone take 0.24 us a live page
#   and the arithmetic 0.37 (4 pages an update: 0.41, 16: 0.38, more of
#   them masked); at 32 heads the copies are the time.
# * A chunk IS one update's pages: two updates a chunk (16 x 8, 8 x 4) read
#   within 2 % of one (8 x 8, 4 x 4) on the chip, so there is one number.
#
# The copies are the row's own pages, one a page; the arithmetic folds G
# page places an update and masks the places past the row's last like
# positions past its context (the buffers are zeroed once, so what a dead
# place holds is finite).

_LATENT_SCORE_BYTES = 512 * 1024  # float32 score tile of one softmax update
_LATENT_STEP_BYTES = 2560 * 1024  # pool bytes one softmax update folds in


def latent_pool_padded(page_size: int, width: int, rank: int, dtype) -> bool:
    """Whether the chip pads a latent pool's ``[page, width]`` pages or
    the ``[page, rank]`` value part the kernel slices out of them: rows or
    a rank that are not whole lane tiles, or a page that is not whole
    packed sublane tiles (8 rows of 32 bits)."""
    return bool(
        width % _LANES
        or rank % _LANES
        or page_size % (8 * 4 // jnp.dtype(dtype).itemsize)
    )


def _latent_decode_schedule(page_bytes: int, n_heads: int, page_size: int) -> int:
    """Pages a chunk copies and one softmax update folds in, from the
    bytes of one page and the head count alone."""
    return max(
        1,
        min(
            16,
            _LATENT_STEP_BYTES // page_bytes,
            _LATENT_SCORE_BYTES // (n_heads * page_size * 4),
        ),
    )


def _latent_fold(q, lat_ref, kpos, ctx, m_ref, l_ref, acc_ref, *, scale, rank):
    """Fold the chunk of page places in ``lat_ref`` into the running
    softmax of all heads: scores against the whole rows, values from
    their first ``rank`` lanes."""
    mul = q.dtype
    scores = (
        jax.lax.dot_general(
            q, lat_ref[...].astype(mul), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [H, G * page]
    scores = jnp.where(kpos < ctx, scores, NEG_INF)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    probs = jnp.exp(scores - m_new)
    l_ref[...] = jnp.broadcast_to(
        alpha * l_ref[:, :1] + jnp.sum(probs, axis=1, keepdims=True),
        l_ref.shape,
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        probs.astype(mul), lat_ref[:, pl.ds(0, rank)].astype(mul),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _latent_decode_live_kernel(
    # scalar prefetch
    li_ref,  # [1] int32 — layer index into the stacked pool
    bt_ref,  # [S, pages_per_seq] int32
    cl_ref,  # [S] int32 — context length INCLUDING the new token
    # inputs
    q_ref,  # [1, n_heads, Wp] absorbed query, zero beyond its width
    lat_hbm,  # [L, P, page, Wp], left in HBM
    # output
    o_ref,  # [1, n_heads, rank]
    # scratch
    m_ref,  # [n_heads, LANES] f32, lane-replicated running max
    l_ref,  # [n_heads, LANES] f32, lane-replicated running denom
    acc_ref,  # [n_heads, rank] f32
    buf,  # [2, G * page, Wp] pool dtype: two chunks of pages
    sem,  # DMA [2, G]
    slot_ref,  # SMEM [1] int32: parity of the chunks consumed so far
    *,
    scale: float,
    rank: int,
    page_size: int,
    group: int,
):
    G = group
    s = pl.program_id(0)
    S = pl.num_programs(0)
    li = li_ref[0]
    H = q_ref.shape[1]

    def live_span(seq):
        return 0, (cl_ref[seq] + page_size - 1) // page_size

    def page_copies(pid, slot, i):
        return (
            pltpu.make_async_copy(
                lat_hbm.at[li, pid],
                buf.at[slot, pl.ds(pl.multiple_of(i * page_size, page_size), page_size)],
                sem.at[slot, i],
            ),
        )

    def clear():
        buf[...] = jnp.zeros_like(buf)

    def prepare(ctx):
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0]  # [H, Wp], already in the dtype the dots multiply in
        col = jax.lax.broadcasted_iota(jnp.int32, (H, G * page_size), 1)

        def fold(page, slot, i):  # a chunk is one update: i is 0
            _latent_fold(
                q, buf.at[slot], page * page_size + col, ctx,
                m_ref, l_ref, acc_ref, scale=scale, rank=rank,
            )

        return fold

    _walk_live_pages(
        s, S, bt_ref, cl_ref, slot_ref,
        span=live_span, chunk=G, group=G,
        page_copies=page_copies, clear=clear, prepare=prepare,
    )

    l = l_ref[:, :1]
    l = jnp.where(l == 0.0, 1.0, l)  # inactive slot: zeros, never NaN
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "rank", "interpret"),
)
def latent_paged_decode_attention_live(
    q: jnp.ndarray,  # [S, n_heads, W] absorbed query [W_uk^T q^C ; q^R]
    pages: jnp.ndarray,  # [L, P, page_size, Wp], Wp >= W
    block_tables: jnp.ndarray,  # [S, pages_per_seq] int32
    context_lens: jnp.ndarray,  # [S] int32, INCLUDING the new token
    layer: jnp.ndarray,  # traced layer index into the stacked pool
    *,
    scale: float,
    rank: int,  # the first ``rank`` values of a row are the latent c
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed latent decode attention whose work follows the live cache
    (see the notes above). The contract of
    ``ops/attention.latent_paged_decode_attention``, and its precision;
    returns ``[S, n_heads, rank]``. A pool padded on the chip is refused.
    Nothing in the schedule depends on ``block_tables.shape[1]`` or on the
    longest row."""
    S, n_heads, W = q.shape
    L, P, page_size, Wp = pages.shape
    if latent_pool_padded(page_size, Wp, rank, pages.dtype):
        raise ValueError(
            f"a {pages.dtype} latent pool of [{page_size}, {Wp}] pages (rank "
            f"{rank}) is padded on the chip: "
            "ops/attention.latent_paged_decode_attention reads it"
        )
    mul = _mul_dtype(q.dtype, pages.dtype)
    G = _latent_decode_schedule(
        page_size * Wp * jnp.dtype(pages.dtype).itemsize, n_heads, page_size
    )
    kernel = functools.partial(
        _latent_decode_live_kernel,
        scale=scale, rank=rank, page_size=page_size, group=G,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, n_heads, Wp), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_heads, rank), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, _LANES), jnp.float32),
            pltpu.VMEM((n_heads, rank), jnp.float32),
            pltpu.VMEM((2, G * page_size, Wp), pages.dtype),
            pltpu.SemaphoreType.DMA((2, G)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, n_heads, rank), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        context_lens.astype(jnp.int32),
        jnp.pad(q, ((0, 0), (0, 0), (0, Wp - W))).astype(mul),
        pages,
    )


# ---------------------------------------------------------------------------
# Paged chunked prefill
# ---------------------------------------------------------------------------


def _paged_prefill_kernel(
    # scalar prefetch
    li_ref,  # [1] int32 — layer index into the stacked page pool
    bt_ref,  # [B, pages_per_seq] int32
    start_ref,  # [B] int32 — absolute position of the chunk's first query
    nvalid_ref,  # [B] int32 — valid query positions in this row's chunk
    w_ref,  # [1] int32 — sliding window (huge = disabled)
    # blocked inputs
    q_ref,  # [1, bq, n_heads, d]
    k_ref,  # [1, 1, page_size, n_kv, d] — one whole page, all kv heads
    v_ref,
    # output
    o_ref,  # [1, bq, n_heads, d]
    # scratch
    m_ref,  # [bq * n_heads, LANES] f32
    l_ref,
    acc_ref,  # [bq * n_heads, d] f32
    *,
    scale: float,
    page_size: int,
    pages_per_seq: int,
    block_q: int,
    n_kv: int,
    softcap: Optional[float],
):
    """Chunk-of-queries attention against the paged KV cache.

    Grid ``(B, nq, pages_per_seq)``: one q-block of ``block_q`` chunk
    positions for row ``b`` against one cached page per step, online
    softmax across pages. The causal frontier is per-token and ABSOLUTE
    (query at position p attends cached keys ≤ p), so earlier chunks'
    pages and the chunk's own freshly-written page both mask correctly.
    """
    b = pl.program_id(0)
    iq = pl.program_id(1)
    p = pl.program_id(2)
    window = w_ref[0]
    start = start_ref[b] + iq * block_q  # absolute pos of q row 0
    nvalid = nvalid_ref[b] - iq * block_q  # valid q rows in this block
    page_start = p * page_size
    group = q_ref.shape[2] // n_kv

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block is live iff some (q, k) pair is in the causal+window frontier:
    # highest q position in THIS q-block = start + min(nvalid, bq) - 1
    # (the whole-chunk frontier would drag ~C/page extra pages through
    # every early block); lowest = start.
    nhere = jnp.minimum(nvalid, block_q)
    live = jnp.logical_and(
        nhere > 0,
        jnp.logical_and(
            page_start <= start + nhere - 1,  # causal frontier
            page_start + page_size > start - window,  # window frontier
        ),
    )

    @pl.when(live)
    def _accumulate():
        # Multiply in the PROMOTED operand dtype with f32 accumulation:
        # chunked prefill is attention-compute-bound for long contexts
        # and an f32 multiply runs the MXU at a fraction of its bf16
        # rate. Promotion means a narrow pool (fp8 KV cache) upcasts to
        # the query dtype, while a pool WIDER than the compute dtype
        # (kv_dtype=f32 with bf16 compute) keeps its full precision.
        target = _mul_dtype(q_ref.dtype, k_ref.dtype)
        q = q_ref[0].astype(target)  # [bq, H, d]
        bq, H, d = q.shape
        k = k_ref[0, 0].astype(target)  # [page, n_kv, d]
        v = v_ref[0, 0].astype(target)
        for g in range(n_kv):
            rows = slice(g * group, (g + 1) * group)
            qg = q[:, rows, :].reshape(bq * group, d)
            scores = (
                jax.lax.dot_general(
                    qg, k[:, g, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                * scale
            )  # [bq*group, page]
            scores = _apply_softcap(scores, softcap)
            qrow = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            qpos = start + qrow // group
            kpos = page_start + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1
            )
            mask = jnp.logical_and(
                qrow // group < nvalid,
                jnp.logical_and(kpos <= qpos, kpos > qpos - window),
            )
            scores = jnp.where(mask, scores, NEG_INF)

            srows = slice(g * group * bq, (g + 1) * group * bq)
            # scratch rows are laid out [bq*group per kv head]; scores
            # rows are (q-position major, group minor) within the head.
            m_prev = m_ref[srows, :1]
            l_prev = l_ref[srows, :1]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[srows, :] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(probs, axis=1, keepdims=True),
                (bq * group, l_ref.shape[1]),
            )
            m_ref[srows, :] = jnp.broadcast_to(
                m_new, (bq * group, m_ref.shape[1])
            )
            acc_ref[srows, :] = acc_ref[srows, :] * alpha + (
                jax.lax.dot_general(
                    probs.astype(v.dtype), v[:, g, :],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )

    @pl.when(p == pages_per_seq - 1)
    def _finish():
        bq = q_ref.shape[1]
        d = q_ref.shape[3]
        # Per-kv-group writes invert the scratch layout without a 4-D
        # transpose (same sliced-sublane idiom as the decode kernel).
        for g in range(n_kv):
            srows = slice(g * group * bq, (g + 1) * group * bq)
            l = l_ref[srows, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            out = (acc_ref[srows, :] / l).reshape(bq, group, d)
            o_ref[0, :, g * group : (g + 1) * group, :] = out.astype(
                o_ref.dtype
            )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_q", "interpret"),
)
def paged_prefill_attention_pallas(
    q: jnp.ndarray,  # [B, C, n_heads, d]
    k_pages: jnp.ndarray,  # [P, page, n_kv, d] or [L, P, page, n_kv, d]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    chunk_start: jnp.ndarray,  # [B] int32 absolute first-query position
    num_valid: jnp.ndarray,  # [B] int32 valid query count (≤ C)
    sliding_window: jnp.ndarray,  # [] or [1] int32 (huge = disabled)
    layer: Optional[jnp.ndarray] = None,
    *,
    scale: float,
    softcap: Optional[float] = None,
    block_q: int = 32,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas chunked-prefill attention (see `_paged_prefill_kernel`).

    Contract mirrors ``ops/attention.py::paged_prefill_attention`` with
    (chunk_start, num_valid) instead of a full positions grid: positions
    are ``chunk_start[b] .. chunk_start[b]+num_valid[b)−1``, contiguous —
    which is how the engine's chunk loop builds them. Rows past
    ``num_valid`` produce garbage (finite) output the caller ignores.
    """
    B, C, n_heads, d = q.shape
    if k_pages.ndim == 4:
        k_pages = k_pages[None]
        v_pages = v_pages[None]
        layer = jnp.zeros((), jnp.int32)
    assert layer is not None, "stacked pages need a layer index"
    _, _, page_size, n_kv, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    block_q = min(block_q, C)
    c_pad = -(-C // block_q) * block_q
    if c_pad != C:
        q = jnp.pad(q, ((0, 0), (0, c_pad - C), (0, 0), (0, 0)))
    nq = c_pad // block_q

    kernel = functools.partial(
        _paged_prefill_kernel,
        scale=scale,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        block_q=block_q,
        n_kv=n_kv,
        softcap=softcap,
    )
    # K/V page index, clamped to the [window, causal] live frontier of
    # this (row, q-block). Dead grid steps resolve to the same block index
    # as the nearest live one, and Mosaic's pipeline skips the re-DMA when
    # consecutive steps fetch the same block — so per-chunk attention
    # bandwidth scales with the LIVE context, not max_model_len (compute
    # over dead pages was already masked; this kills their DMAs too).
    def _kv_index(b, iq, p, li, bt, st, nv, w):
        start = st[b] + iq * block_q  # absolute pos of q row 0
        nhere = jnp.minimum(nv[b] - iq * block_q, block_q)
        hi = start + jnp.maximum(nhere, 1) - 1  # highest live key pos
        # Clamp to the table width too: for DEAD q-blocks in the padded
        # tail, `start` (and with sliding windows `first_live`) can land
        # past max_model_len — the raw frontier would then index
        # block_tables out of bounds.
        last_live = jnp.clip(hi // page_size, 0, pages_per_seq - 1)
        first_live = jnp.clip((start - w[0]) // page_size, 0, pages_per_seq - 1)
        pc = jnp.clip(p, jnp.minimum(first_live, last_live), last_live)
        return (li[0], bt[b, pc], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, nq, pages_per_seq),
        in_specs=[
            pl.BlockSpec(
                (1, block_q, n_heads, d),
                lambda b, iq, p, li, bt, st, nv, w: (b, iq, 0, 0),
            ),
            pl.BlockSpec((1, 1, page_size, n_kv, d), _kv_index),
            pl.BlockSpec((1, 1, page_size, n_kv, d), _kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, n_heads, d),
            lambda b, iq, p, li, bt, st, nv, w: (b, iq, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q * n_heads, _LANES), jnp.float32),
            pltpu.VMEM((block_q * n_heads, _LANES), jnp.float32),
            pltpu.VMEM((block_q * n_heads, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, c_pad, n_heads, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        chunk_start.astype(jnp.int32),
        num_valid.astype(jnp.int32),
        jnp.asarray(sliding_window, jnp.int32).reshape(1),
        q,
        k_pages,
        v_pages,
    )
    return out[:, :C]


# ---------------------------------------------------------------------------
# Flash prefill
# ---------------------------------------------------------------------------


def _flash_prefill_kernel(
    # scalar prefetch
    len_ref,  # [B] int32 — valid prompt lengths
    w_ref,  # [1] int32 — sliding window
    # blocked inputs ([B, H, T, d] layouts)
    q_ref,  # [1, 1, bq, d]
    k_ref,  # [1, 1, bk, d]
    v_ref,  # [1, 1, bk, d]
    # output
    o_ref,  # [1, 1, bq, d]
    # scratch
    m_ref,  # [bq, LANES] f32
    l_ref,  # [bq, LANES] f32
    acc_ref,  # [bq, d] f32
    *,
    scale: float,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    softcap: Optional[float],
):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    length = len_ref[b]
    window = w_ref[0]
    q_start = iq * block_q
    k_start = ik * block_kv

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block is live iff some (q, k) pair satisfies causal + length + window.
    live = jnp.logical_and(
        k_start <= q_start + block_q - 1,  # causal frontier
        jnp.logical_and(
            k_start < length,  # ragged length
            k_start + block_kv - 1 > q_start - window,  # window frontier
        ),
    )

    @pl.when(live)
    def _accumulate():
        # Dots multiply in the PROMOTED input dtype with f32
        # accumulation: long prefill is attention-compute-bound
        # (FLOPs ~ T^2) and an f32 multiply runs the MXU at a fraction
        # of its bf16 rate. This also matches the XLA reference, whose
        # einsums multiply bf16 inputs in bf16. Softmax statistics stay
        # f32 throughout; promotion keeps mixed-dtype callers working.
        target = _mul_dtype(q_ref.dtype, k_ref.dtype)
        q = q_ref[0, 0].astype(target)  # [bq, d]
        k = k_ref[0, 0].astype(target)  # [bk, d]
        v = v_ref[0, 0].astype(target)
        scores = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [bq, bk] f32
        scores = _apply_softcap(scores, softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        mask = jnp.logical_and(
            kpos <= qpos,
            jnp.logical_and(kpos < length, kpos > qpos - window),
        )
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(probs, axis=1, keepdims=True), l_ref.shape
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "block_q", "block_kv", "interpret"),
)
def flash_prefill_attention_pallas(
    q: jnp.ndarray,  # [B, T, n_heads, d]
    k: jnp.ndarray,  # [B, T, n_kv, d]
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # [B] int32
    sliding_window: jnp.ndarray,  # [] or [1] int32 (huge = disabled)
    *,
    scale: float,
    softcap: Optional[float] = None,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, n_heads, d = q.shape
    n_kv = k.shape[2]
    n_rep = n_heads // n_kv
    block_q = min(block_q, max(T, 8))
    block_kv = min(block_kv, max(T, 8))
    t_pad = -(-T // max(block_q, block_kv)) * max(block_q, block_kv)

    # [B, H, T, d] layout: T on sublanes, d on lanes, contiguous DMA tiles.
    qt = jnp.pad(
        q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, t_pad - T), (0, 0))
    )
    kt = jnp.pad(
        k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, t_pad - T), (0, 0))
    )
    vt = jnp.pad(
        v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, t_pad - T), (0, 0))
    )
    nq = t_pad // block_q
    nk = t_pad // block_kv

    kernel = functools.partial(
        _flash_prefill_kernel,
        scale=scale,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nk,
        softcap=softcap,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_heads, nq, nk),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d),
                lambda b, h, iq, ik, ln, w: (b, h, iq, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda b, h, iq, ik, ln, w: (b, h // n_rep, ik, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_kv, d),
                lambda b, h, iq, ik, ln, w: (b, h // n_rep, ik, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b, h, iq, ik, ln, w: (b, h, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, n_heads, t_pad, d), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "arbitrary",
                "arbitrary",
                "arbitrary",
                "arbitrary",
            ),
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        jnp.asarray(sliding_window, jnp.int32).reshape(1),
        qt,
        kt,
        vt,
    )
    return out[:, :, :T, :].transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# Flash prefill of latent attention's expanded form
# ---------------------------------------------------------------------------
#
# Expanded MLA: every head has its own content keys and values, raised from
# the latent, and all heads share one rotary key. Scores run over
# ``d_c + d_r`` (128 + 64), values over ``d_v`` (128). A sibling of
# ``_flash_prefill_kernel``, not that kernel given a second head size: the
# programs that run that one lower as they did.


def _mla_flash_prefill_kernel(
    len_ref,  # scalar prefetch: [B] int32, valid prompt lengths
    qc_ref,  # [1, 1, bq, d_c] content part of a head's queries
    qr_ref,  # [1, 1, bq, d_r] rotary part
    kc_ref,  # [1, bk, d_c] a head's content keys, where ``W_kvb`` put them
    kr_ref,  # [1, bk, d_r] the rotary key all heads share
    v_ref,  # [1, bk, d_v] its values, beside the keys
    o_ref,  # [1, bq, d_v] into the row ``o_proj`` takes
    m_ref,  # [bq, LANES] f32
    l_ref,  # [bq, LANES] f32
    acc_ref,  # [bq, d_v] f32
    *,
    scale: float,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
):
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    length = len_ref[b]
    q_start, k_start = iq * block_q, ik * block_kv

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(masked: bool):
        q = jnp.concatenate([qc_ref[0, 0], qr_ref[0, 0]], axis=-1)
        k = jnp.concatenate([kc_ref[0], kr_ref[0]], axis=-1)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk] f32
        if masked:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(
                jnp.logical_and(kpos <= qpos, kpos < length), scores, NEG_INF
            )
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(probs, axis=1, keepdims=True), l_ref.shape
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            probs.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # A block some query of a live row attends; rows past the prompt are
    # padding and read as zeros. A block wholly under the diagonal and
    # inside the prompt needs no mask: the softmax, not the MXU, is what a
    # block costs at these head sizes.
    live = jnp.logical_and(
        k_start <= q_start + block_q - 1,
        jnp.logical_and(k_start < length, q_start < length),
    )
    whole = jnp.logical_and(
        k_start + block_kv - 1 <= q_start, k_start + block_kv <= length
    )
    pl.when(jnp.logical_and(live, whole))(functools.partial(fold, False))
    pl.when(jnp.logical_and(live, jnp.logical_not(whole)))(
        functools.partial(fold, True)
    )

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _mla_last_key_block(iq, length, block_q: int, block_kv: int):
    """The last key block that query block ``iq`` of a prompt of ``length``
    positions attends: under the diagonal and inside the prompt (block 0
    for an empty row)."""
    causal = (iq * block_q + block_q - 1) // block_kv
    return jnp.minimum(causal, jnp.maximum(length - 1, 0) // block_kv)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_kv", "interpret")
)
def mla_flash_prefill_attention(
    q_c: jnp.ndarray,  # [B, T, n, d_c]
    q_r: jnp.ndarray,  # [B, T, n, d_r]
    kv: jnp.ndarray,  # [B, T, n, d_c + d_v]: a head's content keys, then its values
    k_r: jnp.ndarray,  # [B, T, d_r]
    lengths: jnp.ndarray,  # [B] int32
    *,
    scale: float,
    block_q: int = 512,
    block_kv: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Causal flash attention over a right-padded prompt for expanded
    latent attention: scores over a head's content part and the shared
    rotary part (never concatenated in HBM), values over ``d_v``. Products
    in the rows' own dtype into the MXU, float32 running max, sum and
    accumulator. Key blocks past a query block's diagonal or past
    ``lengths[b]`` are skipped, their arithmetic and (the index map stays
    on the last block that is attended) their copies; query rows past
    ``lengths[b]`` read as zeros where their whole block is past it.
    ``[B, T, n, d_v]``.

    Keys and values are read where ``W_kvb``'s product left them, a
    head's ``d_c`` keys then its ``d_v`` values in a row of ``n (d_c +
    d_v)``, a block of lanes each (``d_c == d_v``: one lane tile apiece),
    and the output is written into the row ``o_proj`` multiplies: no
    transposed copy of either. The queries
    go heads first; their slices and rotary are copies anyway. The blocks
    are the chip's reading (``tools/mla_prefill_bench.py --blocks``,
    PERF.md section 6, PRs 53-57): 512 x 1,024 is the fastest over prompts
    that fill three quarters of a 2,048 bucket (query blocks past the
    prompt are skipped, which 1,024 rows a block seldom are), 2.3 MB of a
    step's 16."""
    B, T, n, d_c = q_c.shape
    d_r, d_v = q_r.shape[-1], kv.shape[-1] - d_c
    assert d_c == d_v, (d_c, d_v)  # keys and values a block of lanes each
    block_q = min(block_q, max(T, 8))
    block_kv = min(block_kv, max(T, 8))
    step = max(block_q, block_kv)
    t_pad = -(-T // step) * step
    nq, nk = t_pad // block_q, t_pad // block_kv

    def heads_first(a):  # [B, n, t_pad, d]: T on sublanes, contiguous tiles
        return jnp.pad(
            a.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, t_pad - T), (0, 0))
        )

    def rows(a):  # [B, t_pad, width]
        return jnp.pad(a.reshape(B, T, -1), ((0, 0), (0, t_pad - T), (0, 0)))

    kv = rows(kv)

    def q_map(b, h, iq, ik, ln):
        return (b, h, iq, 0)

    def key_block(b, iq, ik, ln):
        # a skipped step stays on the last block its query block attends,
        # so that nothing is copied for it
        return jnp.minimum(ik, _mla_last_key_block(iq, ln[b], block_q, block_kv))

    kernel = functools.partial(
        _mla_flash_prefill_kernel,
        scale=scale, block_q=block_q, block_kv=block_kv, num_kv_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        name="mla_flash_prefill_attention",
        out_shape=jax.ShapeDtypeStruct((B, t_pad, n * d_v), q_c.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, n, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d_c), q_map),
                pl.BlockSpec((1, 1, block_q, d_r), q_map),
                pl.BlockSpec(  # head h's keys: lanes [2 h d, 2 h d + d)
                    (1, block_kv, d_c),
                    lambda b, h, iq, ik, ln: (b, key_block(b, iq, ik, ln), 2 * h),
                ),
                pl.BlockSpec(
                    (1, block_kv, d_r),
                    lambda b, h, iq, ik, ln: (b, key_block(b, iq, ik, ln), 0),
                ),
                pl.BlockSpec(  # its values: the next block of lanes
                    (1, block_kv, d_v),
                    lambda b, h, iq, ik, ln: (b, key_block(b, iq, ik, ln), 2 * h + 1),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d_v), lambda b, h, iq, ik, ln: (b, iq, h)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d_v), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4
        ),
        interpret=interpret,
    )(
        lengths.astype(jnp.int32),
        heads_first(q_c), heads_first(q_r), kv, rows(k_r), kv,
    )
    return out[:, :T].reshape(B, T, n, d_v)
