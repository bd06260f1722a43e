"""Models with a declared layer pattern (``ModelConfig.layer_pattern``),
three families: delta-rule (KDA) layers with a per-sequence state beside
latent (MLA) layers over a paged latent cache (``bailing_hybrid``:
Ling-3.0); latent layers alone, every one with a query LoRA and sandwich
norms, no state layer at all (``pangu_ultra_moe``: openPangu-Ultra-MoE);
and gated short-convolution layers with a per-sequence tail beside
grouped-query softmax layers over a paged K/V cache (``lfm2_moe``:
LFM2-24B-A2B). Dense or routed-expert MLPs, the experts held by share or
all of them.

Every other family is one uniform stack and stays on
``models/transformer.py``; nothing here is on its path.

Layout. Consecutive equal layers are one *group*, stacked on a leading
layer axis under ``params["stack<i>"]`` and run as one ``lax.scan``. Two
kinds of cache ride through the scans, in the places the uniform model
has its K and V pools, so that the engine's step programs pass, donate
and return them as they do those. The FIRST holds what the pattern's
paged layers (``PAGED_KINDS``) need, the SECOND what its state layers
(``STATE_KINDS``) need; a pattern has one kind of each at most:

- ``k_pages``: the paged pool ``[L_paged, P, page, width]``, one row a
  token, addressed through the block table (no V pool). ``mla``: the
  latent row, ``kv_lora_rank + rope`` values in whole lane tiles (576 in
  640). ``gqa``: the token's V then its K, every kv head side by side
  (``2 * n_kv * d``: 1,024 at 8 heads of 64, whole lane tiles where a
  ``[page, 8, 64]`` page would be padded on the chip); the first
  ``paged_rank`` values of a row are the part decode attention sums;
- ``v_pages``: the state pool, ``{"S": [L_kda, R, heads, d, d] float32,
  "conv": [L_state, R, K-1, tail_width]}``: one row a sequence, given by
  ``state_rows`` (the engine: slot + 1). A caller that passes none gets the
  row of the sequence's first page (``block_tables[:, 0]``). Row 0 is
  scratch, as page 0 is: padded prefill rows and inactive decode rows
  write there. A prefill overwrites its row whole, so a row needs no
  clearing between sequences. ``kda``: the state matrix and the tails of
  its q|k|v convolution; ``conv``: the tails alone, K-1 = 2 rows of
  ``hidden_size`` values, and ``S`` has no layers. A pattern with no
  state layer has both leaves empty: they cost no HBM and ride along
  untouched.

The block (published; what the configuration does not settle is listed
under ``assumed`` in ``benchmark/configs/ling-3.0-flash-ep4.json``):
pre-norm residual layers; KDA with SiLU short convolutions, L2-normalised
q and k, the bounded per-channel gate, a per-head output RMSNorm and a
sigmoid output gate, no rotary; MLA without a query LoRA, expanded in
prefill and absorbed in decode, with a head-wise sigmoid gate before
``o_proj``; a ``noaux_tc`` sigmoid router over all experts (groups, a
selection bias, ``routed_scaling_factor``) whose chosen experts are
computed where they are held here (:func:`moe_held`), plus a shared
expert.

``pangu_ultra_moe`` (``benchmark/configs/openpangu-ultra-moe-718b-ep16.json``
lists what is assumed) differs by static branches on the configuration,
so that the other family's programs lower as they did: the query is
``W_qb RMSNorm(W_qa x)`` (``q_lora_rank``; scope ``llmq.attn.mla.q_lora``
inside the attention scopes), there is no head-wise gate
(``mla_head_gate``), each sub-layer's output is normed before the
residual add (``post_norms``: ``h + N2(MLA(N1 h))``, ``x + N4(F(N3 x))``;
scope ``llmq.norm.sandwich``), and the router is one group with no
selection bias (``n_group`` 1, ``router_bias``).

``lfm2_moe`` (``benchmark/configs/lfm2-24b-a2b-pp5.json``) brings the
kinds ``conv`` and ``gqa``, again by static branches: ``conv`` is ``[B ;
C ; u] = W_in x``, ``z`` the depth-wise causal convolution of ``B * u``
over ``short_conv_kernel_size`` taps, ``W_out (C * z)``, no activation
(scopes ``llmq.attn.shortconv`` and, for the taps and the tail,
``llmq.attn.shortconv.conv``); ``gqa`` norms q and k a head
(``llmq.attn.qk_norm``), rotates halves, and in decode reads the pool
with the latent pool's kernel (scopes ``llmq.attn.gqa_prefill``,
``llmq.attn.gqa_decode``; see the comment above ``_gqa_inputs``). Its
router chooses by score + bias and weighs by the score over the chosen
scores' sum + ``router_norm_eps`` (1e-6 here, 1e-20 in the other two),
has no shared expert (``shared_expert_intermediate_size`` None: no
shared leaves, no ``llmq.moe.shared``) and every expert is held.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models import quant as qm
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import (
    Transformer, _mlp, apply_rope, compute_rope_inv_freq, rms_norm,
)
from llmq_tpu.ops import attention as attn_ops
from llmq_tpu.ops import delta_rule, dispatch

Params = Dict[str, Any]
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    """Consecutive layers of one kind: a stacked subtree and one scan."""

    name: str  # key of its subtree in the params
    attn: str  # "kda" | "mla" | "conv" | "gqa"
    mlp: str  # "dense" | "moe"
    count: int
    first: int  # index of its first layer in its attention kind's pool


#: What a pattern's kinds keep a sequence: "paged" kinds a row a token in
#: the first cache place, "state" kinds a row a sequence in the second.
PAGED_KINDS = ("mla", "gqa")
STATE_KINDS = ("kda", "conv")


def layer_groups(config: ModelConfig) -> Tuple[LayerGroup, ...]:
    groups = []
    seen = dict.fromkeys(PAGED_KINDS + STATE_KINDS, 0)
    kinds = {attn for attn, _ in config.layer_pattern}
    if set(PAGED_KINDS) <= kinds or set(STATE_KINDS) <= kinds:
        raise ValueError(
            f"a layer pattern has one paged kind and one state kind: {sorted(kinds)}"
        )
    for attn, mlp in config.layer_pattern:
        if attn not in seen or mlp not in ("dense", "moe"):
            raise ValueError(f"unknown layer kind ({attn!r}, {mlp!r})")
        last = groups[-1] if groups else None
        if last is not None and (last.attn, last.mlp) == (attn, mlp):
            groups[-1] = dataclasses.replace(last, count=last.count + 1)
        else:
            groups.append(
                LayerGroup(f"stack{len(groups)}", attn, mlp, 1, seen[attn])
            )
        seen[attn] += 1
    return tuple(groups)


def count_layers(config: ModelConfig, *attn: str) -> int:
    return sum(1 for a, _ in config.layer_pattern if a in attn)


def latent_width(config: ModelConfig) -> int:
    return config.kv_lora_rank + config.qk_rope_head_dim


def kv_width(config: ModelConfig) -> int:
    """Values of a token's keys (or values) in a "gqa" layer: every kv
    head's, side by side."""
    return config.num_kv_heads * config.head_dim_


def paged_rank(config: ModelConfig) -> int:
    """The first values of a pool row that are the row's VALUE part, which
    decode attention sums: MLA's latent ``c``; a "gqa" layer's V, all kv
    heads of it (its K follows)."""
    if count_layers(config, "gqa"):
        return kv_width(config)
    return config.kv_lora_rank


def latent_pool_width(config: ModelConfig) -> int:
    """A pool row: the latent row (a "gqa" pattern's: a token's V then K,
    see :meth:`HybridTransformer._gqa_decode`) in whole lane tiles of 128
    (576 -> 640, zeros beyond; 2 x 8 x 64 = 1,024 as it is). A row-major
    pool takes that room on the chip anyway, and for a width that is not
    whole tiles the TPU runtime's default layout puts the tokens minor
    instead: every step then copied the whole pool into row-major order
    and back (2.8 ms of a 28.7 ms decode step at 2,305 pages, my chip run,
    PR 33)."""
    if count_layers(config, "mla"):
        width = latent_width(config)
    else:  # with no paged layer at all the pool has no layers
        width = 2 * kv_width(config)
    return -(-width // 128) * 128


def tail_width(config: ModelConfig) -> int:
    """Values of one row of a sequence's convolution tail: KDA's q|k|v
    before the convolution, a "conv" layer's gated input ``B * u``."""
    if count_layers(config, "conv"):
        return config.hidden_size
    return 3 * config.num_heads * config.head_dim_


def group_shapes(config: ModelConfig, group: LayerGroup) -> Dict[str, tuple]:
    """Leaf shapes of one group's subtree (leading axis: its layers)."""
    cfg = config
    H, n, d, L = cfg.hidden_size, cfg.num_heads, cfg.head_dim_, group.count
    D = n * d
    shapes: Dict[str, tuple] = {"ln1": (L, H), "ln2": (L, H)}
    if group.attn == "kda":
        shapes.update(
            kda_q_proj=(L, H, D), kda_k_proj=(L, H, D), kda_v_proj=(L, H, D),
            kda_conv=(L, cfg.short_conv_kernel_size, 3 * D),
            kda_f_proj=(L, H, D), kda_a_log=(L, n), kda_dt_bias=(L, D),
            kda_b_proj=(L, H, n), kda_g_proj=(L, H, D), kda_o_norm=(L, d),
            o_proj=(L, D, H),
        )
    elif group.attn == "conv":
        shapes.update(
            conv_in_proj=(L, H, 3 * H), conv_w=(L, cfg.short_conv_kernel_size, H),
            o_proj=(L, H, H),
        )
    elif group.attn == "gqa":
        kv = kv_width(cfg)
        shapes.update(
            q_proj=(L, H, D), k_proj=(L, H, kv), v_proj=(L, H, kv),
            q_norm=(L, d), k_norm=(L, d), o_proj=(L, D, H),
        )
    else:
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            shapes.update(
                mla_qa_proj=(L, H, cfg.q_lora_rank),
                mla_q_norm=(L, cfg.q_lora_rank),
                mla_qb_proj=(L, cfg.q_lora_rank, n * qk),
            )
        else:
            shapes.update(mla_q_proj=(L, H, n * qk))
        shapes.update(
            mla_kva_proj=(L, H, latent_width(cfg)),
            mla_kv_norm=(L, cfg.kv_lora_rank),
            mla_kvb_proj=(L, cfg.kv_lora_rank, n * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            o_proj=(L, n * cfg.v_head_dim, H),
        )
        if cfg.mla_head_gate:
            shapes.update(mla_g_proj=(L, H, n))
    if cfg.post_norms:
        shapes.update(post_attn_norm=(L, H), post_mlp_norm=(L, H))
    if group.mlp == "dense":
        I = cfg.intermediate_size
        shapes.update(gate_proj=(L, H, I), up_proj=(L, H, I), down_proj=(L, I, H))
    else:
        E, Im = cfg.num_experts, cfg.moe_intermediate_size
        held, Is = cfg.experts_held_[1], cfg.shared_expert_intermediate_size
        if cfg.router_bias:
            shapes.update(router_bias=(L, E))
        shapes.update(
            router=(L, H, E),
            expert_gate_proj=(L, held, H, Im), expert_up_proj=(L, held, H, Im),
            expert_down_proj=(L, held, Im, H),
        )
        if Is:
            shapes.update(
                shared_gate_proj=(L, H, Is), shared_up_proj=(L, H, Is),
                shared_down_proj=(L, Is, H),
            )
    return shapes


def param_shapes(config: ModelConfig) -> Dict[str, Any]:
    """Leaf shapes of the whole tree: top-level leaves, and one subtree a
    group."""
    H, V = config.hidden_size, config.vocab_size
    shapes: Dict[str, Any] = {"embed": (V, H), "final_norm": (H,)}
    if not config.tie_word_embeddings:
        shapes["lm_head"] = (H, V)
    for group in layer_groups(config):
        shapes[group.name] = group_shapes(config, group)
    return shapes


_ONES = (
    "ln1", "ln2", "final_norm", "kda_o_norm", "mla_kv_norm", "mla_q_norm",
    "post_attn_norm", "post_mlp_norm", "q_norm", "k_norm",
)
_ZEROS = ("router_bias", "kda_a_log", "kda_dt_bias")


def init_params(config: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    """Random init (tests, ``preset://``): norms 1, biases 0, matrices
    normal over sqrt(fan-in), the fan-in the axis before the last."""
    shapes = param_shapes(config)
    flat, treedef = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    leaves = []
    for k, (path, shape) in zip(jax.random.split(key, len(flat)), flat):
        name = path[-1].key
        if name in _ONES:
            leaves.append(jnp.ones(shape, dtype))
        elif name in _ZEROS:
            leaves.append(jnp.zeros(shape, dtype))
        else:
            fan_in = shape[-1] if name == "embed" else shape[-2]
            leaves.append(
                (jax.random.normal(k, shape, F32) / math.sqrt(fan_in)).astype(dtype)
            )
    return jax.tree.unflatten(treedef, leaves)


def make_state_pools(
    config: ModelConfig,
    num_pages: int,
    page_size: int,
    dtype=jnp.bfloat16,
    *,
    placement: Any = None,
    state_rows: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """(paged pool, state pool) in the places of the K and V pools. A
    caller that gives no ``state_rows`` gets one state row a page, so
    that a sequence's first page can name its row."""
    n, d = config.num_heads, config.head_dim_
    R = num_pages if state_rows is None else state_rows
    paged = count_layers(config, *PAGED_KINDS)
    shapes = (
        ((paged, num_pages, page_size, latent_pool_width(config)), dtype),
        ((count_layers(config, "kda"), R, n, d, d), F32),
        (
            (
                count_layers(config, *STATE_KINDS), R,
                config.short_conv_kernel_size - 1, tail_width(config),
            ),
            dtype,
        ),
    )

    def alloc():
        latent, S, conv = (jnp.zeros(shape, dt) for shape, dt in shapes)
        return latent, {"S": S, "conv": conv}

    if placement is None:
        return alloc()
    return jax.jit(alloc, out_shardings=placement)()


def latent_page_bytes_per_device(
    config: ModelConfig, page_size: int, dtype, placement
) -> int:
    """HBM one page of the paged pool (all MLA layers' latent rows, or all
    "gqa" layers' V and K) takes as the compiler lays the pool out: a row
    of 576 values is padded to whole lane tiles."""
    probe = 8
    shape = (
        count_layers(config, *PAGED_KINDS), probe, page_size,
        latent_pool_width(config),
    )
    alloc = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=placement)
    return alloc.lower().compile().memory_analysis().output_size_in_bytes // probe


def state_pool_bytes(config: ModelConfig, rows: int, dtype) -> int:
    """Bytes of ``rows`` sequences' KDA state and convolution tails (a
    "conv" layer has the tail alone)."""
    n, d = config.num_heads, config.head_dim_
    tail = (
        (config.short_conv_kernel_size - 1) * tail_width(config)
        * jnp.dtype(dtype).itemsize
    )
    return rows * (
        count_layers(config, "kda") * n * d * d * 4
        + count_layers(config, *STATE_KINDS) * tail
    )


# ---------------------------------------------------------------------------
# Experts held by share
# ---------------------------------------------------------------------------


#: Rows (tokens) up to which every held expert is computed on every row.
#: Measured on a v5e at the published widths (PERF.md, PR 33): at 128 rows
#: the dense form streams the experts' weights at two thirds of the HBM
#: rate (2.7 ms a layer), the grouped form takes 5 ms a layer; beyond a
#: few hundred rows the dense form's arithmetic (rows x experts) loses.
DENSE_EXPERT_ROWS = 256

#: Rows an expert layer takes at a time (see :func:`moe_held`), at the
#: hidden size it was measured at; a wider model takes fewer.
MOE_BLOCK_ROWS = 4096
_MOE_BLOCK_HIDDEN = 2560

#: Tokens of a padded prefill batch above which a KDA layer takes its
#: rows one at a time (see ``HybridTransformer._kda_prefill``).
KDA_PREFILL_TOKENS = 8192

#: Tokens x heads of a padded prefill batch above which an MLA layer
#: expands its rows one at a time (see ``HybridTransformer._mla_prefill``).
MLA_PREFILL_HEAD_TOKENS = 2**20


def moe_held(
    h: jnp.ndarray, lp: Params, config: ModelConfig
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The routed-expert MLP as this chip computes it: the router scores
    all ``num_experts``, and of the ``num_experts_per_tok`` chosen a token
    only those held here (``experts_held``) are computed, each weighted
    by its share of ALL the chosen scores; plus the shared expert. What
    experts elsewhere would add is left out: no code stands in for them.

    Two forms of the same sum, chosen by the number of rows: up to
    ``DENSE_EXPERT_ROWS`` every held expert on every row, weighted by a
    ``[rows, held]`` matrix that is zero where an expert was not chosen
    (a decode step: the weights stream once, nothing is sorted); above,
    the assignments that land here sorted by expert and
    ``lax.ragged_dot`` over the held experts (a prefill).

    Returns the output and ``[assignments held, experts hit]`` (int32),
    the counters a decode step reports. Above twice ``MOE_BLOCK_ROWS`` rows
    the layer runs a block of rows at a time (``lax.map``), so that what
    is in flight (``rows x 8`` assignments of float32 hidden rows) stays
    one block's whatever the bucket: a 4 x 8,192 prefill would hold 2 x
    2.5 GB otherwise (compiled for a v5e, PR 33); the counters are then
    sums over blocks. The block halves while its rows are wider in all
    than ``MOE_BLOCK_ROWS`` rows of 2,560 (7,680 wide: 1,024 rows; a 4 x
    2,048 prefill held 4.2 GB otherwise, compiled for a v5e, PR 39)."""
    *lead, H = h.shape
    x = h.reshape(-1, H)
    block = MOE_BLOCK_ROWS
    while block > 256 and block * H > MOE_BLOCK_ROWS * _MOE_BLOCK_HIDDEN:
        block //= 2
    if x.shape[0] > 2 * block and x.shape[0] % block == 0:
        out, counts = jax.lax.map(
            lambda rows: _moe_rows(rows, lp, config), x.reshape(-1, block, H)
        )
        counts = counts.sum(axis=0)
    else:
        out, counts = _moe_rows(x, lp, config)
    return out.reshape(*lead, H), counts


def _moe_rows(x: jnp.ndarray, lp: Params, config: ModelConfig):
    """:func:`moe_held` for rows ``[N, H]``."""
    cfg = config
    N, E, k = x.shape[0], cfg.num_experts, cfg.num_experts_per_tok
    first, held = cfg.experts_held_
    with jax.named_scope("llmq.moe.router"):
        scores = jax.nn.sigmoid(
            jnp.dot(x, lp["router"], preferred_element_type=F32)
        )  # [N, E]
        choice = scores
        if cfg.router_bias:
            choice = scores + lp["router_bias"].astype(F32)  # selection only
        G = cfg.n_group
        if G > 1:
            grouped = choice.reshape(N, G, E // G)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)  # [N, G]
            kept_groups = jax.lax.top_k(group_score, cfg.topk_group)[1]
            keep = jnp.zeros((N, G), bool).at[
                jnp.arange(N)[:, None], kept_groups
            ].set(True)
            choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(N, E)
        top_e = jax.lax.top_k(choice, k)[1]  # [N, k]
        top_w = jnp.take_along_axis(scores, top_e, axis=1)
        if cfg.norm_topk_prob:
            top_w = top_w / (top_w.sum(axis=-1, keepdims=True) + cfg.router_norm_eps)
        top_w = top_w * cfg.routed_scaling_factor
        local = top_e - first
        here = (local >= 0) & (local < held)  # [N, k]
    with jax.named_scope("llmq.moe.experts"):
        if N <= DENSE_EXPERT_ROWS:
            out, hit = _experts_dense(x, lp, local, here, top_w, held)
        else:
            out, hit = _experts_grouped(x, lp, local, here, top_w, held)
    if cfg.shared_expert_intermediate_size:
        with jax.named_scope("llmq.moe.shared"):
            act = jax.nn.silu(qm.matmul(x, lp["shared_gate_proj"])) * qm.matmul(
                x, lp["shared_up_proj"]
            )
            out = out + qm.matmul(act, lp["shared_down_proj"]).astype(F32)
    counts = jnp.stack([here.sum(dtype=jnp.int32), hit])
    return out.astype(x.dtype), counts


def _experts_dense(x, lp, local, here, top_w, held):
    """Every held expert on every row; a row's weight for an expert it
    did not choose is zero. ``[N, H]`` float32, and the experts hit."""
    N = x.shape[0]
    weight = jnp.zeros((N, held + 1), F32).at[
        jnp.arange(N)[:, None], jnp.where(here, local, held)
    ].add(jnp.where(here, top_w, 0.0))[:, :held]
    gate = jnp.einsum("nh,ehi->eni", x, lp["expert_gate_proj"])
    up = jnp.einsum("nh,ehi->eni", x, lp["expert_up_proj"])
    act = (jax.nn.silu(gate) * up).astype(F32) * weight.T[:, :, None]
    out = jnp.einsum(
        "eni,eih->nh", act.astype(x.dtype), lp["expert_down_proj"],
        preferred_element_type=F32,
    )
    return out, (weight > 0).any(axis=0).sum(dtype=jnp.int32)


def _experts_grouped(x, lp, local, here, top_w, held):
    """Only the assignments that land here, sorted by expert, one
    ``lax.ragged_dot`` group an expert. (``ragged_dot`` takes its matrices
    as a whole buffer, so inside a layer scan each layer's three are
    copied out of their stack first: 3 x 250 MB a layer at the published
    widths, a third of a 512-token prefill. Handing it the whole stack
    with empty groups for the other layers avoids the copy and runs
    alone, but a 4 x 1,024 prefill step built so never came back from the
    chip: PERF.md, PR 33.) The lint's repartition pins do not apply: the
    engine refuses tp > 1 for a layer pattern, so nothing here is split."""
    N, k = local.shape
    slot = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(slot)  # stable; what is held elsewhere sorts last  # llmq: ignore[unconstrained-repartition]
    token_of = order // k
    group_sizes = jnp.bincount(slot, length=held + 1)[:held].astype(jnp.int32)  # llmq: ignore[unconstrained-repartition]
    xs = x[token_of]  # [N*k, H]
    gate = jax.lax.ragged_dot(xs, lp["expert_gate_proj"], group_sizes)  # llmq: ignore[unconstrained-repartition]
    up = jax.lax.ragged_dot(xs, lp["expert_up_proj"], group_sizes)  # llmq: ignore[unconstrained-repartition]
    down = jax.lax.ragged_dot(  # llmq: ignore[unconstrained-repartition]
        jax.nn.silu(gate) * up, lp["expert_down_proj"], group_sizes
    )
    here_sorted = here.reshape(-1)[order]
    w_sorted = jnp.where(here_sorted, top_w.reshape(-1)[order], 0.0)
    down = jnp.where(here_sorted[:, None], down.astype(F32), 0.0)
    out = jax.ops.segment_sum(down * w_sorted[:, None], token_of, num_segments=N)  # llmq: ignore[unconstrained-repartition]
    return out, (group_sizes > 0).sum(dtype=jnp.int32)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


def _rope_interleaved(x, positions, inv_freq):
    """``rope_interleave``: the pairs to rotate are (0, 1), (2, 3), ...:
    bring them to the halves :func:`apply_rope` rotates."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return apply_rope(x, positions, inv_freq)


@dataclasses.dataclass(frozen=True)
class HybridTransformer(Transformer):
    """``prefill`` and ``decode`` of a model with a layer pattern, with the
    signatures of :class:`Transformer`'s plus ``state_rows``. The paths
    that need a cache which can be cut or rewound by a length (chunked
    prefill, verify, the mixed step) are not built for a layer pattern,
    and the engine refuses their options at build: a per-sequence state
    cannot be cut so; a latent cache alone could be, and is not yet."""

    def __post_init__(self):
        if self.config.layer_pattern is None:
            raise ValueError("HybridTransformer needs a layer_pattern")
        if self.stage is not None:
            raise ValueError("a layer pattern is not split into pipeline stages")

    # --- attention kinds ----------------------------------------------------
    def _kda_inputs(self, lp: Params, x: jnp.ndarray):
        """Projections of the normed input shared by prefill and decode:
        pre-convolution q|k|v, decay ``alpha``, write strength ``beta``
        and the output gate, all but the first in float32."""
        cfg = self.config
        n, d = cfg.num_heads, cfg.head_dim_
        *lead, _ = x.shape
        u = jnp.concatenate(
            [qm.matmul(x, lp[f"kda_{name}_proj"]) for name in "qkv"], axis=-1
        )
        f = qm.matmul(x, lp["kda_f_proj"]).astype(F32) + lp["kda_dt_bias"].astype(F32)
        rate = jnp.exp(lp["kda_a_log"].astype(F32))[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * f.reshape(*lead, n, d))
        beta = jax.nn.sigmoid(qm.matmul(x, lp["kda_b_proj"]).astype(F32))
        out_gate = jax.nn.sigmoid(
            qm.matmul(x, lp["kda_g_proj"]).astype(F32)
        ).reshape(*lead, n, d)
        return u, jnp.exp(g), beta, out_gate

    def _kda_qkv(self, conv_out: jnp.ndarray):
        """SiLU of the convolution, split into heads; q and k
        L2-normalised, q scaled by d^-1/2. Float32."""
        cfg = self.config
        n, d = cfg.num_heads, cfg.head_dim_
        *lead, _ = conv_out.shape
        q, k, v = (
            part.reshape(*lead, n, d)
            for part in jnp.split(jax.nn.silu(conv_out), 3, axis=-1)
        )
        q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
        return q * (d**-0.5), k, v

    def _kda_out(self, lp: Params, o: jnp.ndarray, out_gate: jnp.ndarray, dtype):
        cfg = self.config
        *lead, n, d = o.shape
        o = rms_norm(o, lp["kda_o_norm"], cfg.rms_norm_eps) * out_gate
        with jax.named_scope("llmq.o_proj"):
            return qm.matmul(o.astype(dtype).reshape(*lead, n * d), lp["o_proj"])

    @jax.named_scope("llmq.attn.kda")
    def _kda_prefill(self, lp, x, lengths, state, rows, li):
        def run(x, lengths):
            u, alpha, beta, out_gate = self._kda_inputs(lp, x)
            with jax.named_scope("llmq.attn.kda.conv"):
                conv_out, tail = delta_rule.causal_conv(u, lp["kda_conv"], lengths)
            q, k, v = self._kda_qkv(conv_out)
            valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
            S, o = delta_rule.kda_scan(q, k, v, alpha, beta, valid)
            return self._kda_out(lp, o, out_gate, x.dtype), S, tail

        B, T, _ = x.shape
        if B > 1 and B * T > KDA_PREFILL_TOKENS:
            # A row at a time: the float32 projections in flight are one
            # row's (a 4 x 8,192 bucket holds 4.5 GB of them otherwise:
            # compiled for a v5e, PR 33). The scan's steps are as many.
            y, S, tail = (
                out[:, 0] for out in jax.lax.map(
                    lambda row: run(row[0][None], row[1][None]), (x, lengths)
                )
            )
        else:
            y, S, tail = run(x, lengths)
        state = {
            "S": state["S"].at[li, rows].set(S),
            "conv": state["conv"].at[li, rows].set(tail.astype(state["conv"].dtype)),
        }
        return y, state

    @jax.named_scope("llmq.attn.kda")
    def _kda_decode(self, lp, x, state, rows, li, active):
        """``rows``: an array of state rows or an int
        (:func:`_state_row_access`)."""
        u, alpha, beta, out_gate = self._kda_inputs(lp, x)
        read, write = _state_row_access(rows, x.shape[0], li, active)
        old_tail, old_S = read(state["conv"]), read(state["S"])
        with jax.named_scope("llmq.attn.kda.conv"):
            conv_out, tail = delta_rule.conv_step(old_tail, u, lp["kda_conv"])
        q, k, v = self._kda_qkv(conv_out)
        S, o = delta_rule.kda_step(old_S, q, k, v, alpha, beta)
        state = {
            "S": write(state["S"], S, old_S),
            "conv": write(state["conv"], tail, old_tail),
        }
        return self._kda_out(lp, o, out_gate, x.dtype), state

    # A gated short convolution (LFM2's "conv" operator): ``[B ; C ; u] =
    # W_in x``, a depth-wise causal convolution of ``B * u`` over
    # ``short_conv_kernel_size`` taps, ``y = W_out (C * z)``; no activation.
    # What a sequence keeps is the last taps - 1 rows of ``B * u``.
    def _conv_gates(self, lp: Params, x: jnp.ndarray):
        b, c, u = jnp.split(qm.matmul(x, lp["conv_in_proj"]), 3, axis=-1)
        return b * u, c

    def _conv_out(self, lp: Params, c: jnp.ndarray, z: jnp.ndarray):
        with jax.named_scope("llmq.o_proj"):
            return qm.matmul((c.astype(F32) * z).astype(c.dtype), lp["o_proj"])

    @jax.named_scope("llmq.attn.shortconv")
    def _conv_prefill(self, lp, x, lengths, state, rows, li):
        bu, c = self._conv_gates(lp, x)
        with jax.named_scope("llmq.attn.shortconv.conv"):
            z, tail = delta_rule.causal_conv(bu, lp["conv_w"], lengths)
            tails = state["conv"].at[li, rows].set(tail.astype(state["conv"].dtype))
        return self._conv_out(lp, c, z), dict(state, conv=tails)

    @jax.named_scope("llmq.attn.shortconv")
    def _conv_decode(self, lp, x, state, rows, li, active):
        """``rows`` as in :meth:`_kda_decode`."""
        bu, c = self._conv_gates(lp, x)
        read, write = _state_row_access(rows, x.shape[0], li, active)
        with jax.named_scope("llmq.attn.shortconv.conv"):
            old = read(state["conv"])
            z, tail = delta_rule.conv_step(old, bu, lp["conv_w"])
            tails = write(state["conv"], tail, old)
        return self._conv_out(lp, c, z), dict(state, conv=tails)

    # Grouped-query softmax attention inside a pattern ("gqa"). Its cache
    # is the pattern's paged pool, a row a token: ``[V ; K]``, every kv
    # head's values and then every kv head's keys side by side (8 x 64 +
    # 8 x 64 = 1,024 values: whole lane tiles where ``[page, 8, 64]``
    # would leave half of each tile to padding). Decode reads it with the
    # latent pool's kernel (``dispatch.latent_decode_attention``: one copy
    # of a page serves scores and values; every query head scores whole
    # rows), by giving each query head a row-wide query that is zero
    # outside its own kv head's keys: the dot with a whole row is then the
    # head's own score, and of the row-wide weighted sum of V it keeps its
    # own kv head's slice. The MXU multiplies the zeros too (2 n_kv times
    # the least arithmetic, 48 FLOP a cached byte at 32 / 8 heads of 64
    # against the chip's ridge of 240): the step stays bound by the bytes.
    def _gqa_inputs(self, lp: Params, x: jnp.ndarray, positions: jnp.ndarray):
        """q ``[B, T, n, d]``, k and v ``[B, T, n_kv, d]`` (q and k normed
        a head, then rotated), and the pool's row ``[V ; K]`` a token."""
        cfg = self.config
        n, n_kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        B, T, _ = x.shape
        q = qm.matmul(x, lp["q_proj"]).reshape(B, T, n, d)
        k = qm.matmul(x, lp["k_proj"]).reshape(B, T, n_kv, d)
        v = qm.matmul(x, lp["v_proj"]).reshape(B, T, n_kv, d)
        with jax.named_scope("llmq.attn.qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        inv_freq = compute_rope_inv_freq(cfg)
        q = apply_rope(q, positions, inv_freq)
        k = apply_rope(k, positions, inv_freq)
        row = jnp.concatenate([v.reshape(B, T, -1), k.reshape(B, T, -1)], axis=-1)
        return q, k, v, row

    def _gqa_out(self, lp: Params, o: jnp.ndarray):
        *lead, n, d = o.shape
        with jax.named_scope("llmq.o_proj"):
            return qm.matmul(o.reshape(*lead, n * d), lp["o_proj"])

    @jax.named_scope("llmq.attn.gqa_prefill")
    def _gqa_prefill(self, lp, x, positions, lengths, pool, block_tables, li):
        q, k, v, row = self._gqa_inputs(lp, x, positions)
        with jax.named_scope("llmq.kv_write"):
            pool = attn_ops.write_latent_pages(pool, row, block_tables, positions, li)
        o = dispatch.prefill_attention(
            q, k, v, scale=self.config.attn_scale, lengths=lengths,
            mesh=self.mesh, backend=self.attn_backend,
        )
        return self._gqa_out(lp, o), pool

    @jax.named_scope("llmq.attn.gqa_decode")
    def _gqa_decode(self, lp, x, positions, pool, block_tables, ctx_incl, li):
        cfg = self.config
        n, n_kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        S = x.shape[0]
        q, _, _, row = self._gqa_inputs(lp, x[:, None, :], positions[:, None])
        with jax.named_scope("llmq.kv_write"):
            pool = attn_ops.write_latent_pages(
                pool, row, block_tables, positions[:, None], li
            )
        # [S, n_kv, n / n_kv, n_kv, d]: head (g, r) has its query at kv
        # head g's keys and zeros at the others'.
        own = jnp.eye(n_kv, dtype=q.dtype)[None, :, None, :, None]
        q_rows = (q[:, 0].reshape(S, n_kv, n // n_kv, 1, d) * own).reshape(S, n, -1)
        o_rows = dispatch.latent_decode_attention(
            jnp.concatenate([jnp.zeros_like(q_rows), q_rows], axis=-1),
            pool, block_tables, ctx_incl,
            scale=cfg.attn_scale, rank=n_kv * d, layer=li,
            mesh=self.mesh, backend=self.attn_backend,
        )  # [S, n, n_kv * d]: every head's weighted sum of whole V rows
        o = jnp.einsum(
            "sgrgd->sgrd", o_rows.reshape(S, n_kv, n // n_kv, n_kv, d)
        ).reshape(S, n, d)
        return self._gqa_out(lp, o), pool

    def _mla_inputs(self, lp: Params, x: jnp.ndarray, positions: jnp.ndarray):
        """q split into its content and rotary parts, and the latent row
        ``[RMSNorm(c) ; RoPE(r)]`` that the cache holds. ``x`` is
        ``[B, T, H]``, ``positions`` ``[B, T]``."""
        cfg = self.config
        n, rope = cfg.num_heads, cfg.qk_rope_head_dim
        B, T, _ = x.shape
        inv_freq = 1.0 / (
            cfg.rope_theta ** (jnp.arange(0, rope, 2, dtype=F32) / rope)
        )
        if cfg.q_lora_rank:
            with jax.named_scope("llmq.attn.mla.q_lora"):
                c_q = rms_norm(
                    qm.matmul(x, lp["mla_qa_proj"]), lp["mla_q_norm"], cfg.rms_norm_eps
                )
                q = qm.matmul(c_q, lp["mla_qb_proj"])
        else:
            q = qm.matmul(x, lp["mla_q_proj"])
        q = q.reshape(B, T, n, -1)
        q_c, q_r = q[..., : cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim :]
        q_r = _rope_interleaved(q_r, positions, inv_freq)
        kva = qm.matmul(x, lp["mla_kva_proj"])
        c = rms_norm(kva[..., : cfg.kv_lora_rank], lp["mla_kv_norm"], cfg.rms_norm_eps)
        r = _rope_interleaved(
            kva[..., cfg.kv_lora_rank :][:, :, None, :], positions, inv_freq
        )[:, :, 0]
        return q_c, q_r, jnp.concatenate([c, r], axis=-1)

    def _mla_out(self, lp: Params, x: jnp.ndarray, o: jnp.ndarray):
        """Head-wise sigmoid gate where the family has one, then
        ``o_proj``. ``o``: [..., n, d_v]."""
        if self.config.mla_head_gate:
            gate = jax.nn.sigmoid(qm.matmul(x, lp["mla_g_proj"]).astype(F32))
            o = (o.astype(F32) * gate[..., None]).astype(x.dtype)
        *lead, n, dv = o.shape
        with jax.named_scope("llmq.o_proj"):
            return qm.matmul(o.reshape(*lead, n * dv), lp["o_proj"])

    def _mla_scale(self) -> float:
        cfg = self.config
        return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5

    @jax.named_scope("llmq.attn.mla_prefill")
    def _mla_prefill(self, lp, x, positions, lengths, latent, block_tables, li):
        """Expanded: keys and values raised from the latent, every head
        its own. Above ``MLA_PREFILL_HEAD_TOKENS`` a row of the batch at a
        time: the expanded q, k and v in flight are one row's (a 4 x 4,096
        bucket at 128 heads holds 3.2 GB of them otherwise: compiled for a
        v5e, PR 39)."""

        def write(latent, row):
            with jax.named_scope("llmq.kv_write"):
                return attn_ops.write_latent_pages(
                    latent, row, block_tables, positions, li
                )

        B, T, _ = x.shape
        if B > 1 and B * T * self.config.num_heads > MLA_PREFILL_HEAD_TOKENS:
            def one_row(args):
                x, positions, lengths = (a[None] for a in args)
                q_c, q_r, row = self._mla_inputs(lp, x, positions)
                return self._mla_expanded(lp, x, q_c, q_r, row, lengths), row

            y, row = (
                out[:, 0] for out in jax.lax.map(one_row, (x, positions, lengths))
            )
            return y, write(latent, row)
        q_c, q_r, row = self._mla_inputs(lp, x, positions)
        latent = write(latent, row)
        return self._mla_expanded(lp, x, q_c, q_r, row, lengths), latent

    def _mla_expanded(self, lp, x, q_c, q_r, row, lengths):
        """Attention of a prefill over keys and values raised from the
        latent rows of the batch itself, and the output projection."""
        cfg = self.config
        n = cfg.num_heads
        B, T, _ = x.shape
        c, r = row[..., : cfg.kv_lora_rank], row[..., cfg.kv_lora_rank :]
        kv = qm.matmul(c, lp["mla_kvb_proj"]).reshape(B, T, n, -1)
        k_c, v = kv[..., : cfg.qk_nope_head_dim], kv[..., cfg.qk_nope_head_dim :]
        k = jnp.concatenate(
            [k_c, jnp.broadcast_to(r[:, :, None, :], (B, T, n, r.shape[-1]))],
            axis=-1,
        )
        o = attn_ops.blocked_prefill_attention(
            jnp.concatenate([q_c, q_r], axis=-1), k, v,
            scale=self._mla_scale(), lengths=lengths,
        )
        return self._mla_out(lp, x, o)

    @jax.named_scope("llmq.attn.mla_decode")
    def _mla_decode(self, lp, x, positions, latent, block_tables, ctx_incl, li):
        """Absorbed: the query goes to the latent (``W_uk^T q``), attention
        runs over the cached rows themselves, and ``W_uv`` raises the
        result. ``x``: [S, H]."""
        cfg = self.config
        n, rank = cfg.num_heads, cfg.kv_lora_rank
        q_c, q_r, row = self._mla_inputs(lp, x[:, None, :], positions[:, None])
        with jax.named_scope("llmq.kv_write"):
            latent = attn_ops.write_latent_pages(
                latent, row, block_tables, positions[:, None], li
            )
        w_kvb = lp["mla_kvb_proj"].reshape(rank, n, -1)
        w_uk, w_uv = w_kvb[..., : cfg.qk_nope_head_dim], w_kvb[..., cfg.qk_nope_head_dim :]
        q_lat = jnp.einsum("shd,chd->shc", q_c[:, 0], w_uk)
        o_lat = dispatch.latent_decode_attention(
            jnp.concatenate([q_lat, q_r[:, 0]], axis=-1),
            latent, block_tables, ctx_incl,
            scale=self._mla_scale(), rank=rank, layer=li,
            mesh=self.mesh, backend=self.attn_backend,
        )
        o = jnp.einsum("shc,chd->shd", o_lat, w_uv)
        return self._mla_out(lp, x, o), latent

    # --- the layer scans ----------------------------------------------------
    def _run_groups(self, params, h, latent, state, attend):
        """Every group of equal layers as one scan. ``attend(group, lp, x,
        latent, state, li)`` returns the attention output and the two
        caches."""
        cfg = self.config
        moe = jnp.zeros((2,), jnp.int32)
        for group in layer_groups(cfg):

            def layer_fn(carry, xs, group=group):
                h, latent, state, moe = carry
                lp, li = xs
                x = rms_norm(h, lp["ln1"], cfg.rms_norm_eps)
                a, latent, state = attend(group, lp, x, latent, state, li)
                if cfg.post_norms:
                    with jax.named_scope("llmq.norm.sandwich"):
                        a = rms_norm(a, lp["post_attn_norm"], cfg.rms_norm_eps)
                h = h + a
                x = rms_norm(h, lp["ln2"], cfg.rms_norm_eps)
                if group.mlp == "dense":
                    m = _mlp(x, lp, cfg.activation)
                else:
                    with jax.named_scope("llmq.moe"):
                        m, counts = moe_held(x, lp, cfg)
                    moe = moe + counts
                if cfg.post_norms:
                    with jax.named_scope("llmq.norm.sandwich"):
                        m = rms_norm(m, lp["post_mlp_norm"], cfg.rms_norm_eps)
                return (h + m, latent, state, moe), None

            carry = (h, latent, state, moe)
            if group.count == 1:
                # No scan of one: its slice of a stack of one layer is
                # compiled as a copy of every weight (measured: 3 ms of a
                # 29 ms decode step), the index 0 as a view.
                only = jax.tree.map(lambda w: w[0], params[group.name])
                carry, _ = layer_fn(carry, (only, jnp.int32(group.first)))
            else:
                carry, _ = jax.lax.scan(
                    layer_fn,
                    carry,
                    (
                        params[group.name],
                        group.first + jnp.arange(group.count, dtype=jnp.int32),
                    ),
                )
            h, latent, state, moe = carry
        return h, latent, state, moe

    def prefill(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [B, T] right-padded prompt bucket
        lengths: jnp.ndarray,  # [B] true lengths (0: a padded row)
        k_pages: jnp.ndarray,  # the latent pool
        v_pages: Dict[str, jnp.ndarray],  # the state pool
        block_tables: jnp.ndarray,  # [B, pages_per_seq]
        state_rows: Optional[jnp.ndarray] = None,  # [B]
        **unsupported,
    ):
        """Full-prompt forward: (last-token logits [B, V], latent pool,
        state pool), the prompt's latent rows written to its pages and
        its final state and convolution tails to its state row."""
        _refuse(unsupported)
        B, T = tokens.shape
        pos_grid = jnp.arange(T, dtype=jnp.int32)[None, :]
        positions = jnp.where(
            pos_grid < lengths[:, None], jnp.broadcast_to(pos_grid, (B, T)), -1
        )
        rows = block_tables[:, 0] if state_rows is None else state_rows
        rows = jnp.where(lengths > 0, rows, 0)

        def attend(group, lp, x, latent, state, li):
            op = getattr(self, f"_{group.attn}_prefill")
            if group.attn in STATE_KINDS:
                a, state = op(lp, x, lengths, state, rows, li)
            else:
                a, latent = op(lp, x, positions, lengths, latent, block_tables, li)
            return a, latent, state

        h, k_pages, v_pages, _ = self._run_groups(
            params, self._embed(params, tokens), k_pages, v_pages, attend
        )
        last = jnp.maximum(lengths - 1, 0)
        last_h = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        return self._logits(params, last_h), k_pages, v_pages

    def decode(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [S]
        context_lens: jnp.ndarray,  # [S] tokens already cached
        k_pages: jnp.ndarray,
        v_pages: Dict[str, jnp.ndarray],
        block_tables: jnp.ndarray,  # [S, pages_per_seq]
        active: jnp.ndarray,  # [S] bool
        state_rows: Any = None,  # [S], or an int: rows first .. first + S - 1
        *,
        counters: bool = False,
        **unsupported,
    ):
        """One decode step for every active slot: (logits [S, V], latent
        pool, state pool). An inactive slot writes the scratch page and
        the scratch state row. With ``counters`` a fourth value: [expert
        assignments held here, held experts hit], summed over layers."""
        _refuse(unsupported)
        positions = jnp.where(active, context_lens, -1).astype(jnp.int32)
        ctx_incl = jnp.where(active, context_lens + 1, 0)
        rows = block_tables[:, 0] if state_rows is None else state_rows
        if not isinstance(rows, int):
            rows = jnp.where(active, rows, 0)

        def attend(group, lp, x, latent, state, li):
            op = getattr(self, f"_{group.attn}_decode")
            if group.attn in STATE_KINDS:
                a, state = op(lp, x, state, rows, li, active)
            else:
                a, latent = op(lp, x, positions, latent, block_tables, ctx_incl, li)
            return a, latent, state

        h, k_pages, v_pages, moe = self._run_groups(
            params, self._embed(params, tokens), k_pages, v_pages, attend
        )
        logits = self._logits(params, h)
        if counters:
            return logits, k_pages, v_pages, moe
        return logits, k_pages, v_pages

    def prefill_chunk(self, *args, **kwargs):
        raise NotImplementedError(
            "chunked prefill, verify and the mixed step are not built for a "
            "layer pattern"
        )

    mixed = verify = prefill_chunk


def _state_row_access(rows, n: int, li, active):
    """(read, write) of ``n`` sequences' rows of layer ``li`` of a state
    pool leaf ``[L, R, ...]``. ``rows``: an array of state rows (gathered,
    updated, scattered; an inactive slot's is 0), or an int, the first of
    one contiguous run of rows, a slot each: then the run is read and
    written in place, an inactive slot's row as it was (measured on a v5e,
    128 rows, 6 KDA layers: 7.7 ms against 20.8 through the gather)."""
    if isinstance(rows, int):
        def read(pool):
            return jax.lax.dynamic_slice(
                pool, (li, rows) + (0,) * (pool.ndim - 2), (1, n) + pool.shape[2:]
            )[0]

        def write(pool, new, old):
            keep = active.reshape((n,) + (1,) * (new.ndim - 1))
            return jax.lax.dynamic_update_slice(
                pool, jnp.where(keep, new, old)[None],
                (li, rows) + (0,) * (pool.ndim - 2),
            )
    else:
        def read(pool):
            return pool[li, rows]

        def write(pool, new, old):
            return pool.at[li, rows].set(new)

    return read, write


def _refuse(unsupported: Dict[str, Any]) -> None:
    given = {k: v for k, v in unsupported.items() if v is not None and v is not False}
    if given:
        raise NotImplementedError(
            f"a model with a layer pattern takes no {sorted(given)}"
        )
