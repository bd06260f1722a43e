"""Attention + paged-KV reference implementations (pure XLA).

These are the numerical ground truth the Pallas kernels are tested against,
and the fallback path on non-TPU backends. Replaces what the reference
outsourced to vLLM's CUDA PagedAttention (SURVEY.md §2b).

KV cache layout (paged):
    k_pages, v_pages: [num_pages, page_size, num_kv_heads, head_dim]
    block_tables:     [num_seqs, pages_per_seq] int32 — logical→physical page
    context_lens:     [num_seqs] int32 — tokens already in cache per sequence

All functions are shape-polymorphic only in ways XLA can specialize once:
fixed page_size, fixed pages_per_seq, bucketed sequence lengths.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free


def _softcap(scores: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _compute_dtype(q_dtype, kv_dtype):
    """Dtype the attention math runs in, given the query dtype and the
    KV *storage* dtype. Narrow pools (fp8/int8: itemsize 1) only STORE
    narrow — they upcast to the query dtype. But a pool WIDER than the
    query (f32 pages under a bf16 query) must not be silently downcast:
    promote instead, so the extra precision the operator paid HBM for
    actually reaches the matmuls. Mirrors ``_mul_dtype`` in
    ``ops/pallas_attention.py`` so the XLA reference and the Pallas
    kernels agree numerically."""
    qd, kd = jnp.dtype(q_dtype), jnp.dtype(kv_dtype)
    if kd.itemsize == 1:
        return qd
    if qd.itemsize == 1:
        return kd
    return jnp.promote_types(qd, kd)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[..., n_kv, d] → [..., n_kv*n_rep, d] (GQA key/value head expansion)."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=-2)


def full_prefill_attention(
    q: jnp.ndarray,  # [B, T, n_heads, head_dim]
    k: jnp.ndarray,  # [B, T, n_kv_heads, head_dim]
    v: jnp.ndarray,  # [B, T, n_kv_heads, head_dim]
    *,
    scale: float,
    lengths: Optional[jnp.ndarray] = None,  # [B] valid prompt lengths
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
) -> jnp.ndarray:
    """Causal self-attention over a full (possibly right-padded) prompt."""
    B, T, n_heads, _ = q.shape
    n_rep = n_heads // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = _softcap(scores, softcap)
    q_pos = jnp.arange(T)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = k_pos <= q_pos
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    if lengths is not None:
        mask = mask[None, :, :] & (k_pos[None, :, :] < lengths[:, None, None])
        mask = mask[:, None, :, :]
    else:
        mask = mask[None, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def paged_decode_attention(
    q: jnp.ndarray,  # [S, n_heads, head_dim] — one new token per sequence
    k_pages: jnp.ndarray,  # [P, page_size, n_kv, head_dim] or [L, P, ...]
    v_pages: jnp.ndarray,  # same shape as k_pages
    block_tables: jnp.ndarray,  # [S, pages_per_seq] int32
    context_lens: jnp.ndarray,  # [S] int32 — INCLUDING the new token
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    layer: Optional[jnp.ndarray] = None,  # required when pages are stacked
) -> jnp.ndarray:
    """Decode-step attention reading K/V through the page table.

    Reference implementation: gathers each sequence's pages into a
    contiguous [S, max_ctx] view and does a masked softmax. The Pallas
    kernel computes the same thing without materializing the gather.

    Pages may arrive stacked over layers ([L, P, page, n_kv, d], with a
    traced ``layer`` index) so the model's layer scan never slices the
    pool; this XLA reference simply indexes (the Pallas kernel addresses
    the stack directly in its DMA index_map — that is the whole point).
    """
    if k_pages.ndim == 5:
        assert layer is not None, "stacked pages need a layer index"
        k_pages = k_pages[layer]
        v_pages = v_pages[layer]
    S, n_heads, head_dim = q.shape
    page_size = k_pages.shape[1]
    pages_per_seq = block_tables.shape[1]
    max_ctx = pages_per_seq * page_size
    n_kv = k_pages.shape[2]
    n_rep = n_heads // n_kv

    # [S, pages_per_seq, page_size, n_kv, d] → [S, max_ctx, n_kv, d].
    # The cast covers reduced-precision pools (fp8 KV cache): compute
    # happens in _compute_dtype — the query dtype for narrow pools
    # (pages only STORE narrow), the promoted dtype for wide ones (an
    # f32 pool under a bf16 query keeps its f32 precision).
    out_dtype = q.dtype  # kernels return q.dtype whatever they compute in
    mul = _compute_dtype(q.dtype, k_pages.dtype)
    k = k_pages[block_tables].reshape(S, max_ctx, n_kv, head_dim)
    v = v_pages[block_tables].reshape(S, max_ctx, n_kv, head_dim)
    k = repeat_kv(k, n_rep).astype(mul)
    v = repeat_kv(v, n_rep).astype(mul)
    q = q.astype(mul)

    scores = jnp.einsum("shd,skhd->shk", q, k) * scale
    scores = _softcap(scores, softcap)
    k_pos = jnp.arange(max_ctx)[None, :]
    mask = k_pos < context_lens[:, None]
    if sliding_window is not None:
        mask &= k_pos >= context_lens[:, None] - sliding_window
    scores = jnp.where(mask[:, None, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(mul)
    return jnp.einsum("shk,skhd->shd", weights, v).astype(out_dtype)


def paged_prefill_attention(
    q: jnp.ndarray,  # [B, C, n_heads, d] — a chunk of query positions
    k_pages: jnp.ndarray,  # [P, page_size, n_kv, d] or [L, P, ...]
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    q_positions: jnp.ndarray,  # [B, C] absolute positions (−1 = padding)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    softcap: Optional[float] = None,
    layer: Optional[jnp.ndarray] = None,  # required when pages are stacked
) -> jnp.ndarray:
    """Chunked-prefill attention: C query positions per row against the
    paged KV cache (which must already hold the chunk's own K/V — same
    write-then-attend order as the decode step).

    The causal frontier is per-token: query at absolute position ``p``
    attends cached keys ``[max(0, p+1−window), p]``. Generalizes
    :func:`paged_decode_attention` (C == 1, position == ctx−1); this is
    what lets prefill run in fixed-size chunks instead of whole-prompt
    buckets — any prompt length, one compiled executable.
    """
    if k_pages.ndim == 5:
        assert layer is not None, "stacked pages need a layer index"
        k_pages = k_pages[layer]
        v_pages = v_pages[layer]
    B, C, n_heads, head_dim = q.shape
    page_size = k_pages.shape[1]
    pages_per_seq = block_tables.shape[1]
    max_ctx = pages_per_seq * page_size
    n_kv = k_pages.shape[2]
    n_rep = n_heads // n_kv

    out_dtype = q.dtype
    mul = _compute_dtype(q.dtype, k_pages.dtype)  # narrow pools upcast,
    k = k_pages[block_tables].reshape(B, max_ctx, n_kv, head_dim)  # wide
    v = v_pages[block_tables].reshape(B, max_ctx, n_kv, head_dim)  # promote
    k = repeat_kv(k, n_rep).astype(mul)
    v = repeat_kv(v, n_rep).astype(mul)
    q = q.astype(mul)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = _softcap(scores, softcap)
    k_pos = jnp.arange(max_ctx)[None, None, :]  # [1, 1, max_ctx]
    q_pos = q_positions[:, :, None]  # [B, C, 1]
    mask = (k_pos <= q_pos) & (q_pos >= 0)
    if sliding_window is not None:
        mask &= k_pos > q_pos - sliding_window
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(mul)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v).astype(out_dtype)


def mixed_query_grid(
    tokens: jnp.ndarray,  # [S] current decode token per slot
    ctx: jnp.ndarray,  # [S] context length − 1 per slot
    active: jnp.ndarray,  # [S] bool — slot is decoding
    chunk_tokens: jnp.ndarray,  # [C] piggybacked prefill segment tokens
    chunk_positions: jnp.ndarray,  # [C] absolute positions (−1 = padding)
    slot: jnp.ndarray,  # scalar int — the piggy sequence's slot
    max_kv_pos: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Query grids for a mixed (decode + piggybacked prefill) dispatch.

    Builds the ``[S, C]`` token/position grids one fused model call
    consumes: every decodable row becomes a single-query row
    ``[ctx, -1, ...]`` (exactly the decode step's position, padded to the
    chunk width), and the piggy sequence's slot — while it is still
    mid-prefill, i.e. inactive — carries the prefill segment instead.
    Once the piggy activates (its final segment sampled), ``is_chunk``
    goes False for its slot and it decodes like any other row.

    Every row satisfies the chunked-prefill kernel contract (a LEADING
    CONTIGUOUS run of valid positions, then −1 padding): decode rows are
    a run of length 1 (or empty when inactive / past the page map, which
    routes their write to the scratch page), and the caller builds the
    segment as ``[s .. s+n−1, −1, ...]``. Returns
    ``(q_tokens [S, C], q_positions [S, C], is_chunk [S])``."""
    S = tokens.shape[0]
    base_tok = jnp.zeros((S, chunk_tokens.shape[0]), tokens.dtype)
    base_tok = base_tok.at[:, 0].set(tokens)
    base_pos = jnp.full(base_tok.shape, -1, ctx.dtype)
    base_pos = base_pos.at[:, 0].set(
        jnp.where(active & (ctx < max_kv_pos), ctx, -1)
    )
    is_chunk = (jnp.arange(S) == slot) & ~active
    q_tokens = jnp.where(is_chunk[:, None], chunk_tokens[None, :], base_tok)
    q_positions = jnp.where(
        is_chunk[:, None], chunk_positions[None, :], base_pos
    )
    return q_tokens, q_positions, is_chunk


def write_prompt_kv_pages(
    k_pages: jnp.ndarray,  # [L, P, page_size, n_kv, d] (stacked only)
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, T, n_kv, d] — positions 0..T-1 per row
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, pages_per_seq]
    layer: jnp.ndarray,
    mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Page-granular prefill KV write (whole pages, not token rows).

    Prefill always covers positions ``[0, T)`` of each row, so when the
    bucket ``T`` is a page multiple each page is one contiguous
    ``[page_size, n_kv, d]`` block — one write per *page* instead of per
    *token* (the token scatter measured ~10.5 ms per 8x256 chunk at 3B on
    v5e: 2048 rows x 512 B).

    The pages go in with a loop of ``dynamic_update_slice``, not one
    block scatter: for a scatter whose window spans the page axis the TPU
    compiler re-tiles the whole pool (tokens, not kv heads, on the
    sublanes) for the duration of the layer scan, i.e. a transposed copy
    of BOTH pools before and after it — a temporary as large as the
    pools, which at the worker's 90%-of-HBM pool size fails to compile
    (``RESOURCE_EXHAUSTED``; tests/test_tpu_compile.py guards the temp
    size). The slice update is in place in the pool's own layout, and
    GSPMD partitions it over a kv-head-sharded pool without a gather.

    Rows shorter than ``T`` write garbage into the tail of their last
    page(s); that space is never read (attention masks by context length)
    and is overwritten token-by-token as decode extends the sequence.
    Padded rows carry an all-zero block table and land on the reserved
    scratch page 0 (same convention as ``write_kv_pages``). Block-table
    entries are allocator page ids, always inside the pool.
    """
    B, T, n_kv, d = k_new.shape
    page_size = k_pages.shape[-3]
    assert T % page_size == 0, "bucket must be page-aligned for page writes"
    n_lp = T // page_size
    phys = block_tables[:, :n_lp].reshape(B * n_lp)
    if mesh is not None:
        # The pool is replicated over every mesh axis but the kv heads',
        # so each device writes every page: under sequence parallelism
        # gather the token axis once, here, instead of paying a
        # collective per page inside the loop. The other axes stay
        # GSPMD's to place.
        free = PartitionSpec.UNCONSTRAINED
        whole_prompt = NamedSharding(mesh, PartitionSpec(free, None, free, free))
        k_new = jax.lax.with_sharding_constraint(k_new, whole_prompt)
        v_new = jax.lax.with_sharding_constraint(v_new, whole_prompt)
    # Cast to the pool dtype (fp8 KV caches quantize on write).
    k_blocks = k_new.astype(k_pages.dtype).reshape(
        B * n_lp, 1, 1, page_size, n_kv, d
    )
    v_blocks = v_new.astype(v_pages.dtype).reshape(
        B * n_lp, 1, 1, page_size, n_kv, d
    )

    def write_page(pools, page):
        kp, vp = pools
        where, k_block, v_block = page
        at = (layer, where, 0, 0, 0)
        return (
            jax.lax.dynamic_update_slice(kp, k_block, at),
            jax.lax.dynamic_update_slice(vp, v_block, at),
        ), None

    (k_pages, v_pages), _ = jax.lax.scan(
        write_page, (k_pages, v_pages), (phys, k_blocks, v_blocks)
    )
    return k_pages, v_pages


def write_kv_pages(
    k_pages: jnp.ndarray,  # [P, page_size, n_kv, d] or [L, P, ...]
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, T, n_kv, d]
    v_new: jnp.ndarray,
    block_tables: jnp.ndarray,  # [B, pages_per_seq]
    positions: jnp.ndarray,  # [B, T] absolute token positions (−1 = skip)
    layer: Optional[jnp.ndarray] = None,  # required when pages are stacked
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter fresh K/V into their pages.

    Padded/inactive entries use position −1 and are routed to a reserved
    scratch page (physical page 0 by convention) so the scatter stays
    fixed-shape with no conditionals. The allocator never hands out page 0.

    With layer-stacked pages ([L, P, page, n_kv, d]) the scatter targets
    ``[layer, page, offset]`` directly — the layer scan never slices out
    and re-inserts the per-layer pool (which XLA materializes as two
    full-pool copies per layer around any opaque consumer).
    """
    B, T, n_kv, d = k_new.shape
    page_size = k_pages.shape[-3]
    pos = positions.reshape(B * T)
    valid = pos >= 0
    logical_page = jnp.where(valid, pos // page_size, 0)
    offset = jnp.where(valid, pos % page_size, 0)
    batch_idx = jnp.repeat(jnp.arange(B), T)
    physical_page = block_tables[batch_idx, logical_page]
    physical_page = jnp.where(valid, physical_page, 0)  # scratch page
    # Cast to the pool dtype (fp8 KV caches quantize on write).
    k_flat = k_new.reshape(B * T, n_kv, d).astype(k_pages.dtype)
    v_flat = v_new.reshape(B * T, n_kv, d).astype(v_pages.dtype)
    if k_pages.ndim == 5:
        assert layer is not None, "stacked pages need a layer index"
        k_pages = k_pages.at[layer, physical_page, offset].set(
            k_flat, mode="drop"
        )
        v_pages = v_pages.at[layer, physical_page, offset].set(
            v_flat, mode="drop"
        )
    else:
        k_pages = k_pages.at[physical_page, offset].set(k_flat, mode="drop")
        v_pages = v_pages.at[physical_page, offset].set(v_flat, mode="drop")
    return k_pages, v_pages


# ---------------------------------------------------------------------------
# Latent (MLA) attention: one pool of [c ; r] rows, no V pool
# ---------------------------------------------------------------------------


def write_latent_pages(
    pages: jnp.ndarray,  # [L, P, page_size, Wp], Wp >= W
    rows: jnp.ndarray,  # [B, T, W] one latent row a token
    block_tables: jnp.ndarray,  # [B, pages_per_seq]
    positions: jnp.ndarray,  # [B, T] absolute positions (−1 = skip)
    layer: jnp.ndarray,
) -> jnp.ndarray:
    """Scatter latent rows into their pages; padded or inactive entries
    (position −1) go to the scratch page 0, as in :func:`write_kv_pages`.
    A pool wider than the rows (whole lane tiles) keeps zeros beyond."""
    B, T, W = rows.shape
    page_size, Wp = pages.shape[2], pages.shape[3]
    pos = positions.reshape(B * T)
    valid = pos >= 0
    logical = jnp.where(valid, pos // page_size, 0)
    offset = jnp.where(valid, pos % page_size, 0)
    physical = block_tables[jnp.repeat(jnp.arange(B), T), logical]
    physical = jnp.where(valid, physical, 0)
    flat = jnp.pad(rows.reshape(B * T, W), ((0, 0), (0, Wp - W))).astype(pages.dtype)
    return pages.at[layer, physical, offset].set(flat, mode="drop")


#: Pages of a sequence that one pass of the latent decode attention folds
#: into its running softmax (4 x 128 tokens).
LATENT_DECODE_CHUNK_PAGES = 4


def latent_paged_decode_attention(
    q: jnp.ndarray,  # [S, n_heads, W] absorbed query [W_uk^T q^C ; q^R]
    pages: jnp.ndarray,  # [L, P, page_size, Wp], Wp >= W
    block_tables: jnp.ndarray,  # [S, pages_per_seq]
    context_lens: jnp.ndarray,  # [S] INCLUDING the new token
    *,
    scale: float,
    rank: int,  # the first ``rank`` values of a row are the latent c
    layer: jnp.ndarray,
) -> jnp.ndarray:
    """Absorbed decode attention over the latent pool: every head scores
    the same cached row, and the value is the row's latent part. Returns
    ``[S, n_heads, rank]`` (``W_uv`` is applied by the caller).

    A loop over chunks of ``LATENT_DECODE_CHUNK_PAGES`` pages with a
    running softmax, as many passes as the longest live context needs: a
    step gathers what is live, not ``pages_per_seq`` pages a row, so its
    cost does not grow with ``max_model_len``."""
    S, n, W = q.shape
    page, Wp = pages.shape[2], pages.shape[3]
    pps = block_tables.shape[1]
    C, passes = _latent_decode_chunks(pps)
    bt = jnp.pad(block_tables, ((0, 0), (0, passes * C - pps)))  # page 0: scratch
    mul = _compute_dtype(q.dtype, pages.dtype)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, Wp - W))).astype(mul)
    span = C * page
    live = latent_decode_passes(jnp.max(context_lens), pps, page)

    def one_pass(j, carry):
        m, l, acc = carry
        cols = jax.lax.dynamic_slice_in_dim(bt, j * C, C, axis=1)
        lat = pages[layer, cols].reshape(S, span, Wp).astype(mul)
        scores = jnp.einsum(
            "shw,skw->shk", qp, lat, preferred_element_type=jnp.float32
        ) * scale
        k_pos = j * span + jnp.arange(span)
        mask = k_pos[None, :] < context_lens[:, None]
        scores = jnp.where(mask[:, None, :], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        shrink = jnp.exp(m - m_new)
        acc = acc * shrink[..., None] + jnp.einsum(
            "shk,skc->shc", p.astype(mul), lat[:, :, :rank],
            preferred_element_type=jnp.float32,
        )
        return m_new, l * shrink + p.sum(axis=-1), acc

    init = (
        jnp.full((S, n), NEG_INF, jnp.float32),
        jnp.zeros((S, n), jnp.float32),
        jnp.zeros((S, n, rank), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(0, live, one_pass, init)
    return (acc / l[..., None]).astype(q.dtype)


def _latent_decode_chunks(pages_per_seq: int):
    """Pages a pass, and the passes that cover a whole row of the block
    table."""
    C = min(LATENT_DECODE_CHUNK_PAGES, pages_per_seq)
    return C, -(-pages_per_seq // C)


def latent_decode_passes(longest, pages_per_seq: int, page_size: int):
    """The passes a step of :func:`latent_paged_decode_attention` takes:
    the longest context's (new token included), at least one. Written
    once: ``longest`` is the traced maximum of the step's ``context_lens``
    inside the program, or a Python int where the engine's dispatch span
    counts the same passes on the host."""
    C, passes = _latent_decode_chunks(pages_per_seq)
    need = -(-longest // (C * page_size))
    if isinstance(longest, int):
        return min(max(need, 1), passes)
    return jnp.clip(need, 1, passes)


def latent_decode_pages_visited(
    plan: str, contexts, slots: int, pages_per_seq: int, page_size: int
) -> int:
    """Latent pages a decode step reads out of the pool a layer under the
    schedule ``plan`` (``ops/dispatch.latent_decode_kernel_plan``'s name),
    for rows of ``contexts`` tokens (new token included) in a step of
    ``slots`` slots. ``"xla"`` (:func:`latent_paged_decode_attention`):
    every slot takes the longest row's passes, a chunk of pages a pass. A
    kernel that follows the live cache
    (``pallas_attention.latent_paged_decode_attention_live``) copies each
    row's own pages, one a copy, and nothing for an empty slot (its
    arithmetic folds several page places an update and masks the dead
    ones: no pool bytes)."""
    if plan != "xla":
        return sum(-(-int(n) // page_size) for n in contexts)
    C, _ = _latent_decode_chunks(pages_per_seq)
    longest = max((int(n) for n in contexts), default=0)
    return slots * latent_decode_passes(longest, pages_per_seq, page_size) * C


def blocked_prefill_attention(
    q: jnp.ndarray,  # [B, T, n_heads, d_qk]
    k: jnp.ndarray,  # [B, T, n_heads, d_qk]
    v: jnp.ndarray,  # [B, T, n_heads, d_v]
    *,
    scale: float,
    lengths: jnp.ndarray,  # [B]
    block: int = 512,
) -> jnp.ndarray:
    """Causal self-attention over a right-padded prompt, a block of query
    rows at a time (``lax.map``), so that the score matrix in flight is
    ``[B, n, block, T]`` whatever the bucket, and the block halves until
    that matrix is at most 2**27 float32 values (a 4 x 8,192 bucket:
    128 rows). Head sizes of q/k and of v may differ (expanded MLA: 192
    and 128)."""
    B, T, n, _ = q.shape
    while block > 32 and B * n * block * T > 2**27:
        block //= 2
    size = T if T <= block else next(
        (b for b in (512, 256, 128, 64, 32) if b <= block and T % b == 0), T
    )
    k_pos = jnp.arange(T)
    in_row = k_pos[None, :] < lengths[:, None]  # [B, T]

    def one_block(args):
        q_blk, q_pos = args  # [B, size, n, d], [size]
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q_blk, k, preferred_element_type=jnp.float32
        ) * scale
        mask = (k_pos[None, :] <= q_pos[:, None])[None] & in_row[:, None, :]
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    if size == T:
        return one_block((q, k_pos))
    nb = T // size
    q_blocks = jnp.moveaxis(q.reshape(B, nb, size, n, q.shape[-1]), 1, 0)
    out = jax.lax.map(one_block, (q_blocks, k_pos.reshape(nb, size)))
    return jnp.moveaxis(out, 0, 1).reshape(B, T, n, v.shape[-1])


# ---------------------------------------------------------------------------
# EVA attention: exact keys inside the query's own window, one learned
# summary a chunk for every earlier window. Its cache rows are not positions
# ---------------------------------------------------------------------------
#
# With window ``W`` and chunk ``c``, ``S = W / c`` summaries a window. The
# token at ``pos`` (window ``w = pos // W``, offset ``o = pos % W``) is
# written to row ``S w + o`` of its sequence; the step that writes a
# window's LAST byte (``o = W - 1``) then reads the window's ``W`` exact rows
# ``[S w, S w + W)`` and overwrites rows ``[S w, S w + S)`` with its ``S``
# summary rows, so the next byte lands on row ``S (w + 1)``, straight after
# them. The rows a query attends are ``[0, row]``, contiguous: earlier
# windows' summaries, then its own window's exact rows. A block table only
# ever grows. The three functions below are the one place that knows this;
# the model, the scheduler and the engine ask them.


def eva_row(pos, window: int, chunk: int):
    """The cache row of the token at position ``pos`` (an int or an
    array; a negative position, a skipped entry, stays -1)."""
    row = (window // chunk) * (pos // window) + pos % window
    if isinstance(pos, int):
        return row if pos >= 0 else -1
    return jnp.where(pos >= 0, row, -1)


def eva_context(n, window: int, chunk: int):
    """Rows the query at position ``n - 1`` attends, its own included
    (what a decode kernel takes for a context length); 0 for ``n`` 0."""
    return eva_row(n - 1, window, chunk) + 1


def eva_table_pages(
    start: int, stop: int, window: int, chunk: int, page_size: int
) -> int:
    """Places of a block table that hold the row of every position in
    ``[start, stop)`` (ints). A window's exact rows reach ``W - S`` rows
    past where the next window starts, so the largest row is the last
    position's or that of the last byte of the window before it."""
    last = stop - 1
    if last < 0:
        return 0
    start = min(max(start, 0), last)
    top = eva_row(last, window, chunk)
    if last // window > start // window:
        top = max(top, eva_row(last // window * window - 1, window, chunk))
    return top // page_size + 1


def eva_summaries(
    k: jnp.ndarray,  # [..., T, n, d] rotated keys, T whole chunks
    v: jnp.ndarray,  # [..., T, n, d]
    mu: jnp.ndarray,  # [n, d]
    phi: jnp.ndarray,  # [n, d]
    *,
    scale: float,
    chunk: int,
):
    """One summary key and value a chunk of ``chunk`` consecutive
    positions: ``k~ = sum_j softmax_j(s k_j . mu) k_j`` and ``v~ = sum_j
    softmax_j(s k_j . phi) v_j``, each softmax over the chunk's positions,
    float32. Returns ``[..., T / chunk, n, d]`` twice, float32."""
    *lead, T, n, d = k.shape
    f32 = jnp.float32
    kc = k.reshape(*lead, T // chunk, chunk, n, d).astype(f32)
    vc = v.reshape(*lead, T // chunk, chunk, n, d).astype(f32)

    def weights(by):
        logits = jnp.einsum("...cnd,nd->...cn", kc, by.astype(f32)) * scale
        return jax.nn.softmax(logits, axis=-2)

    k_sum = jnp.einsum("...cn,...cnd->...nd", weights(mu), kc)
    v_sum = jnp.einsum("...cn,...cnd->...nd", weights(phi), vc)
    return k_sum, v_sum


def eva_prefill_attention(
    q: jnp.ndarray,  # [B, T, n, d]
    k: jnp.ndarray,  # [B, T, n, d]
    v: jnp.ndarray,  # [B, T, n, d]
    k_sum: jnp.ndarray,  # [B, T / chunk, n, d] a summary a chunk
    v_sum: jnp.ndarray,
    *,
    scale: float,
    lengths: jnp.ndarray,  # [B]
    window: int,
    chunk: int,
    block: int = 512,
) -> jnp.ndarray:
    """EVA self-attention over a right-padded prompt: the query at ``i``
    takes ONE softmax over the exact keys of its own window up to itself
    and the summaries of every chunk of an earlier window. ``T`` is whole
    windows, or no longer than one. A block of query rows at a time
    (``lax.map``), against its window's ``W`` keys and all ``T / chunk``
    summaries; the block halves until that score matrix is at most 2**27
    float32 values."""
    B, T, n, d = q.shape
    W = min(window, T)
    G = k_sum.shape[1]
    while block > 32 and B * n * block * (W + G) > 2**27:
        block //= 2
    size = next(
        (b for b in (512, 256, 128, 64, 32) if b <= block and W % b == 0), W
    )
    in_row = jnp.arange(T)[None, :] < lengths[:, None]  # [B, T]
    chunk_window = (jnp.arange(G) * chunk) // window  # [G]

    def one_block(q_pos):  # [size] absolute positions, inside one window
        first = q_pos[0] // W * W
        q_blk = jax.lax.dynamic_slice_in_dim(q, q_pos[0], size, axis=1)
        k_win = jax.lax.dynamic_slice_in_dim(k, first, W, axis=1)
        v_win = jax.lax.dynamic_slice_in_dim(v, first, W, axis=1)
        k_pos = first + jnp.arange(W)
        exact = jnp.einsum(
            "bqhd,bkhd->bhqk", q_blk, k_win, preferred_element_type=jnp.float32
        ) * scale
        mask = (k_pos[None, :] <= q_pos[:, None])[None] & jax.lax.dynamic_slice_in_dim(
            in_row, first, W, axis=1
        )[:, None, :]
        exact = jnp.where(mask[:, None], exact, NEG_INF)
        earlier = jnp.einsum(
            "bqhd,bghd->bhqg", q_blk, k_sum, preferred_element_type=jnp.float32
        ) * scale
        earlier = jnp.where(
            (chunk_window < q_pos[0] // window)[None, None, None, :], earlier, NEG_INF
        )
        weights = jax.nn.softmax(
            jnp.concatenate([exact, earlier], axis=-1), axis=-1
        ).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", weights[..., :W], v_win) + jnp.einsum(
            "bhqg,bghd->bqhd", weights[..., W:], v_sum
        )

    positions = jnp.arange(T).reshape(T // size, size)
    if T == size:
        return one_block(positions[0])
    out = jax.lax.map(one_block, positions)  # [T / size, B, size, n, d]
    return jnp.moveaxis(out, 0, 1).reshape(B, T, n, d)
