"""What one decode step's latent (MLA) attention has to move and compute,
from its shapes and the live cache alone (see ``kernel_cost.py``: kept
with the benchmark so that no later PR can make a layer look better by
counting more for it). It counts what runs under the program's scope
``llmq.attn.mla_decode`` and nothing beside it: the latent projection, the
absorbed up-projections and attention over the cached latent rows; the
query projections only where the model has no query LoRA (with one they
run under ``llmq.attn.mla.q_lora``); never the page write nor ``o_proj``.
Everything is the least the algorithm needs: nothing is rounded up to
pages, lane tiles, chunks or slots, and a row that every head shares is
read once."""

from __future__ import annotations

from typing import Any, Dict, Optional


def latent_layers(cfg: Dict[str, Any]) -> int:
    """How many of the configuration's layers are latent attention, from
    its layer pattern: every ``layer_group_size``-th of the kept layers
    where the file has that key (``bailing_hybrid``: the others are
    delta-rule layers), else every layer (``pangu_ultra_moe``)."""
    kept = cfg.get("kept_layers") or range(int(cfg["num_hidden_layers"]))
    group = cfg.get("layer_group_size")
    if not group:
        return len(kept)
    return sum(1 for i in kept if (i + 1) % int(group) == 0)


def _scope_weights(
    *, hidden: int, heads: int, kv_rank: int, nope: int, rope: int, v_dim: int,
    q_lora: bool,
) -> float:
    """Values of the matrices the scope streams a layer: W_kva, the
    absorbed W_uk and W_uv, and W_q where there is no query LoRA."""
    w = hidden * (kv_rank + rope) + kv_rank * heads * (nope + v_dim)
    if not q_lora:
        w += hidden * heads * (nope + rope)
    return float(w)


def mla_decode_bytes(
    *, live_tokens: float, rows: float, layers: int, hidden: int, heads: int,
    kv_rank: int, nope: int, rope: int, v_dim: int, q_lora: bool,
    row_values: Optional[int] = None,
    weight_bytes: int = 2, cache_bytes: int = 2, act_bytes: int = 2,
) -> float:
    """Bytes over the latent layers of one step: every live token's
    latent row ``[c ; k_r]`` once a layer as stored (all heads share it;
    ``row_values`` where a pool stores another count than ``kv_rank +
    rope`` a token), the scope's matrices once, each row's hidden input
    in and its heads' values out."""
    cache = live_tokens * (row_values or kv_rank + rope) * cache_bytes
    weights = _scope_weights(
        hidden=hidden, heads=heads, kv_rank=kv_rank, nope=nope, rope=rope,
        v_dim=v_dim, q_lora=q_lora,
    ) * weight_bytes
    acts = rows * (hidden + heads * v_dim) * act_bytes
    return layers * (cache + weights + acts)


def mla_decode_flops(
    *, live_tokens: float, rows: float, layers: int, hidden: int, heads: int,
    kv_rank: int, nope: int, rope: int, v_dim: int, q_lora: bool,
) -> float:
    """Scores over ``kv_rank + rope`` values and the weighted sum over
    ``kv_rank`` for every (head, live token), and each row through the
    scope's matrices (2 a multiply-add)."""
    attention = 2.0 * live_tokens * heads * (2 * kv_rank + rope)
    projections = 2.0 * rows * _scope_weights(
        hidden=hidden, heads=heads, kv_rank=kv_rank, nope=nope, rope=rope,
        v_dim=v_dim, q_lora=q_lora,
    )
    return layers * (attention + projections)
