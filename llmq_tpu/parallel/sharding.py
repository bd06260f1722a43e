"""NamedSharding specs for the stacked param pytree + paged KV cache.

Megatron-style tensor parallelism expressed as weight shardings only —
GSPMD propagates them through the jitted prefill/decode programs and
inserts the ICI collectives (all-gather on the column-parallel outputs,
reduce-scatter/psum after the row-parallel matmuls). No hand-written
collectives in the model code, with ONE deliberate exception: when
``EngineConfig.tp_overlap`` resolves to "on", the row-parallel
projections route through the chunked ``lax.ppermute`` rings in
``ops/collective_matmul.py`` (shard_map over the same tp axis and the
same weight shardings below), hiding each ICI hop behind the next chunk's
matmul instead of paying GSPMD's blocking per-layer all-reduces.

Layout (matches ``models/transformer.py::init_params``):

    embed        [V, H]        vocab-sharded on tp (XLA lowers the token
                               gather to a masked local lookup + psum)
    lm_head      [H, V]        column-parallel → logits sharded on vocab
    q/k/v_proj   [L, H, n*d]   column-parallel (heads split across tp)
    o_proj       [L, n*d, H]   row-parallel
    gate/up_proj [L, H, I]     column-parallel
    down_proj    [L, I, H]     row-parallel
    norms/bias   replicated (biases follow their projection's split)
    kv pages     [L, P, page, n_kv, d]  sharded on the kv-head axis

Any axis that doesn't divide the tp degree falls back to replication for
that tensor (e.g. GQA models with fewer kv heads than tp shards keep the
KV cache replicated; attention math still shards over query heads).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmq_tpu.models.config import ModelConfig
from llmq_tpu.parallel.mesh import TP_AXIS

Params = Dict[str, Any]


def _tp_dim(size: int, tp: int) -> Optional[str]:
    """Shard a dimension on tp only when it divides evenly."""
    return TP_AXIS if tp > 1 and size % tp == 0 else None


def param_pspecs(config: ModelConfig, tp: int) -> Params:
    """PartitionSpec pytree matching the param layout."""
    if config.layer_pattern is not None:
        # Held by share, not split: every leaf whole on every device (the
        # engine refuses tp > 1 for a layer pattern).
        from llmq_tpu.models import hybrid

        return jax.tree.map(
            lambda shape: P(), hybrid.param_shapes(config),
            is_leaf=lambda x: isinstance(x, tuple),
        )
    d = config.head_dim_
    nh_d = config.num_heads * d
    nkv_d = config.num_kv_heads * d
    col_q = _tp_dim(nh_d, tp)
    col_kv = _tp_dim(nkv_d, tp)
    col_mlp = _tp_dim(config.intermediate_size, tp)
    vocab = _tp_dim(config.vocab_size, tp)

    layers: Params = {
        "ln1": P(),
        "ln2": P(),
        "q_proj": P(None, None, col_q),
        "k_proj": P(None, None, col_kv),
        "v_proj": P(None, None, col_kv),
        "o_proj": P(None, col_q, None),
    }
    if config.num_experts:
        # MoE: column/row-parallel INSIDE each expert (same Megatron
        # pattern as the dense MLP, applied to the grouped matmuls); the
        # router and tiny shared-expert gate stay replicated. Sharding
        # the expert axis instead (classic EP) would need all_to_all
        # token exchange — the per-expert split needs none.
        col_moe = _tp_dim(config.moe_intermediate_size or 0, tp)
        layers["router"] = P()
        layers["expert_gate_proj"] = P(None, None, None, col_moe)
        layers["expert_up_proj"] = P(None, None, None, col_moe)
        layers["expert_down_proj"] = P(None, None, col_moe, None)
        if config.shared_expert_intermediate_size:
            col_sh = _tp_dim(config.shared_expert_intermediate_size, tp)
            layers["shared_gate_proj"] = P(None, None, col_sh)
            layers["shared_up_proj"] = P(None, None, col_sh)
            layers["shared_down_proj"] = P(None, col_sh, None)
            layers["shared_expert_gate"] = P()
    else:
        layers["gate_proj"] = P(None, None, col_mlp)
        layers["up_proj"] = P(None, None, col_mlp)
        layers["down_proj"] = P(None, col_mlp, None)
    if config.attention_bias:
        layers["q_bias"] = P(None, col_q)
        layers["k_bias"] = P(None, col_kv)
        layers["v_bias"] = P(None, col_kv)
    if config.qk_norm:
        layers["q_norm"] = P()
        layers["k_norm"] = P()
    if config.post_norms:
        layers["post_attn_norm"] = P()
        layers["post_mlp_norm"] = P()
    specs: Params = {
        "embed": P(vocab, None),
        "final_norm": P(),
        "layers": layers,
    }
    if not config.tie_word_embeddings:
        specs["lm_head"] = P(None, vocab)
    return specs


def kv_page_pspec(config: ModelConfig, tp: int) -> P:
    """KV pages [L, P, page, n_kv, d]: shard the kv-head axis on tp."""
    return P(None, None, None, _tp_dim(config.num_kv_heads, tp), None)


def param_shardings(
    mesh: Mesh, config: ModelConfig, *, params: Optional[Params] = None
) -> Params:
    """NamedSharding pytree for the full param tree.

    When ``params`` is given, the spec tree is pruned to exactly the keys
    present (e.g. a tied-embedding checkpoint without ``lm_head``) and
    int8-quantized weights (``models/quant.py`` dicts) expand into
    matching {q, scale} spec nodes.
    """
    from llmq_tpu.models import quant as qm

    tp = mesh.shape[TP_AXIS]
    specs = param_pspecs(config, tp)
    if params is not None:
        specs = _prune_like(specs, params)
        specs = qm.quantized_specs(specs, params)
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _prune_like(specs: Params, params: Params) -> Params:
    from llmq_tpu.models import quant as qm

    out: Params = {}
    for key, value in params.items():
        spec = specs[key]
        if isinstance(value, dict) and not qm.is_quantized(value):
            out[key] = _prune_like(spec, value)
        else:
            out[key] = spec  # quantized leaves expanded by quantized_specs
    return out


def shard_params(params: Params, mesh: Mesh, config: ModelConfig) -> Params:
    """Place an already-loaded param tree onto the mesh."""
    shardings = param_shardings(mesh, config, params=params)
    return jax.tree.map(jax.device_put, params, shardings)


