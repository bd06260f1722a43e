"""What one decode step's grouped-query attention has to move and compute
in a model whose layers are not all attention, from its shapes and the
live cache alone (see ``kernel_cost.py``: kept with the benchmark so that
no later PR can make a layer look better by counting more for it). It
counts what runs under the program's scope ``llmq.attn.gqa_decode`` and
nothing beside it: the q, k and v projections and attention over the
cached keys and values; never the per-head norms (``llmq.attn.qk_norm``),
the page write nor ``o_proj``. Everything is the least the algorithm
needs: nothing is rounded up to pages, lane tiles, chunks or slots, a kv
head's keys are read once for all its query heads, and a kernel that
multiplies more (zeros beside a head's own keys, say) is credited with
none of it."""

from __future__ import annotations

from typing import Any, Dict


def attention_layers(cfg: Dict[str, Any]) -> int:
    """How many of the configuration's layers are softmax attention, from
    its ``layer_types`` over the kept layers (``kept_layers``: published
    indices; default all); a file without ``layer_types``: every layer."""
    kept = cfg.get("kept_layers")
    if kept is None:
        kept = range(int(cfg["num_hidden_layers"]))
    kinds = cfg.get("layer_types")
    if not kinds:
        return len(kept)
    return sum(1 for i in kept if kinds[int(i)] == "full_attention")


def _scope_weights(*, hidden: int, heads: int, kv_heads: int, head_dim: int) -> float:
    """Values of the matrices the scope streams a layer: W_q, W_k, W_v."""
    return float(hidden * (heads + 2 * kv_heads) * head_dim)


def gqa_decode_bytes(
    *, live_tokens: float, rows: float, layers: int, hidden: int, heads: int,
    kv_heads: int, head_dim: int,
    weight_bytes: int = 2, cache_bytes: int = 2, act_bytes: int = 2,
) -> float:
    """Bytes over the attention layers of one step: every live token's
    keys and values once a layer (all kv heads), the scope's matrices
    once, each row's hidden input in and its heads' outputs out."""
    cache = 2.0 * live_tokens * kv_heads * head_dim * cache_bytes
    weights = _scope_weights(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim
    ) * weight_bytes
    acts = rows * (hidden + heads * head_dim) * act_bytes
    return layers * (cache + weights + acts)


def gqa_decode_flops(
    *, live_tokens: float, rows: float, layers: int, hidden: int, heads: int,
    kv_heads: int, head_dim: int,
) -> float:
    """Scores and the weighted sum, ``head_dim`` values each, for every
    (query head, live token), and each row through the scope's matrices
    (2 a multiply-add)."""
    attention = 4.0 * live_tokens * heads * head_dim
    projections = 2.0 * rows * _scope_weights(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim
    )
    return layers * (attention + projections)
