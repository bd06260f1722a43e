"""Generic decoder-only transformer, TPU-first.

Design (vs. the reference's delegation to vLLM's torch models):

- **Pure functions over a param pytree** — no Module state; everything jits
  and shards with `jax.sharding.NamedSharding` annotations applied by the
  engine.
- **Stacked layers + `lax.scan`** — per-layer weights are stacked on a
  leading [L, ...] axis and the layer loop is a scan: one compiled layer
  body regardless of depth (80-layer 72B compiles as fast as a 2-layer
  test model), and the paged KV cache rides through the scan as xs/ys.
- **Family differences as data** (ModelConfig): Qwen2 QKV bias, Gemma-2
  softcaps/post-norms/alternating sliding window, Gemma ``(1+w)`` RMSNorm,
  Qwen3 QK-norm — all static config the compiler folds away.
- **Paged KV cache everywhere**: prefill writes pages while attending over
  the in-flight prompt; decode attends through the block table
  (ops/attention.py reference impls; Pallas kernels swap in on TPU).
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from llmq_tpu.models import quant as qm
# The pools' allocator lives with the rest of the cache's shape; the
# benchmark and the tools import it from here.
from llmq_tpu.models.cache import make_kv_pages  # noqa: F401
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.ops import attention as attn_ops
from llmq_tpu.ops import collective_matmul as cm
from llmq_tpu.ops import dispatch as attn_dispatch

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Activation-stat taps (LLMQ_ACT_STATS) — numerics bisection instrumentation
# ---------------------------------------------------------------------------

#: Sink for (op, layer, mean|x|, max|x|) records emitted by the debug
#: callbacks below; drained by :func:`pop_act_stats`.
_ACT_STATS: List[Tuple[str, int, float, float]] = []


def act_stats_enabled() -> bool:
    """Whether the per-op activation taps are armed (LLMQ_ACT_STATS).

    Checked at TRACE time: with the flag off (the default) :func:`_tap`
    is `return x` and every compiled program is byte-identical to an
    uninstrumented build. Flip the env var before the first dispatch to
    get per-layer/per-op magnitude stats for divergence bisection."""
    return (os.environ.get("LLMQ_ACT_STATS") or "").lower() in (
        "1",
        "true",
        "yes",
        "on",
    )


def pop_act_stats() -> List[Tuple[str, int, float, float]]:
    """Drain and return the recorded (op, layer, mean|x|, max|x|) rows.

    Callbacks are unordered across devices, so consumers should key on
    the explicit (op, layer) labels, not arrival order."""
    out = list(_ACT_STATS)
    _ACT_STATS.clear()
    return out


def _record_stat(layer, mean_abs, max_abs, *, name: str) -> None:
    _ACT_STATS.append(
        (name, int(layer), float(mean_abs), float(max_abs))
    )


def _tap(x: jnp.ndarray, name: str, layer=-1) -> jnp.ndarray:
    """Record magnitude stats of ``x`` under ``name`` when the taps are
    armed; identity (and trace-invisible) otherwise. ``layer`` may be a
    traced scan index — it rides to the host inside the callback."""
    if not act_stats_enabled():
        return x
    x32 = jnp.abs(x.astype(jnp.float32))
    jax.debug.callback(
        lambda li, mn, mx: _record_stat(li, mn, mx, name=name),
        jnp.asarray(layer, jnp.int32),
        jnp.mean(x32),
        jnp.max(x32),
    )
    return x


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def rms_norm(
    x: jnp.ndarray, weight: jnp.ndarray, eps: float, *, one_plus: bool = False
) -> jnp.ndarray:
    """RMSNorm in f32 accumulation. Gemma uses ``x * (1 + w)``."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    x32 = x32 * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    out = x32 * (1.0 + w) if one_plus else x32 * w
    return out.astype(dtype)


def yarn_correction_range(
    beta_fast: float, beta_slow: float, dim: int, theta: float, original_ctx: int
) -> Tuple[int, int]:
    """YaRN (arXiv:2309.00071): the pair indices between which the
    frequencies go from kept (a pair that turns ``beta_fast`` times or more
    over the original context) to interpolated (``beta_slow`` or fewer)."""

    def pair_turning(turns: float) -> float:
        return dim * math.log(original_ctx / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = math.floor(pair_turning(beta_fast)), math.ceil(pair_turning(beta_slow))
    return max(low, 0), min(high, dim - 1)


def compute_rope_inv_freq(
    config: ModelConfig, rotary_dim: Optional[int] = None
) -> jnp.ndarray:
    """Inverse RoPE frequencies [rotary_dim/2] (default: the whole head),
    with llama3-style, linear or YaRN scaling."""
    d = config.head_dim_ if rotary_dim is None else rotary_dim
    inv_freq = 1.0 / (
        config.rope_theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    scaling = config.rope_scaling or {}
    rope_type = scaling.get("rope_type", scaling.get("type"))
    if rope_type == "yarn":
        # Interpolated by ``factor`` below the correction range, kept above
        # it, a linear ramp between. (``attention_factor`` scales cos and
        # sin where the rotation is applied: ``models/hybrid.py``.)
        low, high = yarn_correction_range(
            scaling.get("beta_fast", 32), scaling.get("beta_slow", 1), d,
            config.rope_theta, scaling.get("original_max_position_embeddings", 8192),
        )
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1
        )
        return inv_freq / scaling["factor"] * ramp + inv_freq * (1 - ramp)
    if rope_type == "llama3":
        factor = scaling.get("factor", 8.0)
        low_factor = scaling.get("low_freq_factor", 1.0)
        high_factor = scaling.get("high_freq_factor", 4.0)
        original_ctx = scaling.get("original_max_position_embeddings", 8192)
        low_freq_wavelen = original_ctx / low_factor
        high_freq_wavelen = original_ctx / high_factor
        wavelen = 2 * math.pi / inv_freq
        scaled = inv_freq / factor
        smooth = (original_ctx / wavelen - low_factor) / (high_factor - low_factor)
        smoothed = (1 - smooth) * scaled + smooth * inv_freq
        inv_freq = jnp.where(
            wavelen > low_freq_wavelen,
            scaled,
            jnp.where(wavelen < high_freq_wavelen, inv_freq, smoothed),
        )
    elif rope_type == "linear":
        inv_freq = inv_freq / scaling.get("factor", 1.0)
    return inv_freq


def apply_rope(
    x: jnp.ndarray,  # [..., T, n, d]
    positions: jnp.ndarray,  # [..., T]
    inv_freq: jnp.ndarray,  # [d/2]
) -> jnp.ndarray:
    """Rotate-half RoPE; positions may be -1 (padding) — harmless garbage."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, d/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@jax.named_scope("llmq.mlp")
def _mlp(
    h: jnp.ndarray,
    lp: Params,
    activation: str,
    plan: "cm.TpRingPlan | None" = None,
    layer=-1,
) -> jnp.ndarray:
    gate = _tap(qm.matmul(h, lp["gate_proj"]), "mlp.gate", layer)
    up = _tap(qm.matmul(h, lp["up_proj"]), "mlp.up", layer)
    if activation == "gelu_tanh":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        act = jax.nn.silu(gate)
    # down_proj is the row-parallel projection GSPMD follows with a
    # blocking all-reduce; with a tp-overlap plan it runs as the chunked
    # ppermute ring instead (plan=None is the literal qm.matmul).
    return _tap(
        cm.row_parallel_matmul(act * up, lp["down_proj"], plan, "down_proj"),
        "mlp.down",
        layer,
    )


def moe_token_pin_enabled() -> bool:
    """Whether the MoE grouped-matmul token-axis sharding pins are armed.

    Default ON. ``LLMQ_MOE_TOKEN_PIN=off`` re-introduces the mixed-mesh
    repartition bug deliberately — it exists so the SPMD diff gate's
    detune test (and a hardware bisection session) can reproduce the
    un-pinned programs; it is never a production setting.
    """
    return (os.environ.get("LLMQ_MOE_TOKEN_PIN") or "on").lower() not in (
        "0",
        "off",
        "false",
        "no",
    )


def _moe_token_pins(mesh):
    """(pin_rows, pin_repl) for the MoE grouped-matmul operands.

    GSPMD propagates the expert weights' tp sharding backwards through
    ``ragged_dot``/``segment_sum`` and is free to partition their
    flattened ``[N*k, ...]`` token/group axis over any mesh axis — but
    each shard would keep the GLOBAL ``group_sizes``, so every shard's
    expert-group boundaries are wrong and the grouped matmuls read the
    wrong experts' rows (bisected on the pinned mixed-mesh divergence:
    ``moe.gathered`` bit-stable, ``moe.gate`` rel 5e-1 on (2,2,2)).
    ``pin_rows`` pins ONLY that leading token/group axis unsharded and
    leaves every other dim to GSPMD (``P.UNCONSTRAINED``), so the
    per-expert column/row splits still shard over tp; ``pin_repl`` pins
    ``group_sizes`` fully replicated to match. Identity when no mesh is
    threaded (single-device paths, shard_map bodies).
    """
    if mesh is None or not moe_token_pin_enabled():
        return (lambda x: x), (lambda x: x)
    from jax.sharding import NamedSharding, PartitionSpec

    unconstrained = PartitionSpec.UNCONSTRAINED

    def pin_rows(x):
        spec = PartitionSpec(None, *([unconstrained] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec)
        )

    def pin_repl(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec())
        )

    return pin_rows, pin_repl


@jax.named_scope("llmq.moe")
def _moe_mlp(
    h: jnp.ndarray,
    lp: Params,
    config: ModelConfig,
    plan: "cm.TpRingPlan | None" = None,
    layer=-1,
    mesh=None,
) -> jnp.ndarray:
    """Sparse mixture-of-experts MLP (qwen2_moe/qwen3_moe semantics),
    TPU-first: tokens are sorted by routed expert and each expert's group
    runs as one ``jax.lax.ragged_dot`` (grouped matmul on the MXU) — the
    dense-per-expert loop a torch port would write is E/k× the FLOPs.

    Routing follows HF Qwen2MoeSparseMoeBlock: softmax over ALL experts
    in f32, then top-k (optionally renormalized), plus qwen2_moe's
    always-on shared expert blended through a sigmoid gate.

    The token/group axis of every grouped-matmul operand is pinned
    unsharded (``_moe_token_pins``): ``ragged_dot``'s group semantics
    are only correct when each shard sees ALL rows of ``xs`` alongside
    the global ``group_sizes``.
    """
    *lead, H = h.shape
    x = h.reshape(-1, H)
    N = x.shape[0]
    E = config.num_experts
    k = config.num_experts_per_tok
    pin_rows, pin_repl = _moe_token_pins(mesh)

    router_logits = _tap(
        (x @ lp["router"]).astype(jnp.float32), "moe.router", layer
    )  # [N, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # [N, k]
    if config.norm_topk_prob:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # Sort the N*k (token, expert) assignments by expert id so each
    # expert's tokens are one contiguous group for ragged_dot.
    flat_e = top_e.reshape(-1)  # [N*k]
    order = jnp.argsort(flat_e)  # stable: ties keep token order
    token_of = order // k  # source token per sorted row
    xs = _tap(pin_rows(x[token_of]), "moe.gathered", layer)  # [N*k, H]
    group_sizes = pin_repl(
        jnp.bincount(flat_e, length=E).astype(jnp.int32)
    )

    # ragged_dot takes a real array operand: int8 expert stacks are
    # dequantized per layer-scan step (a transient one-layer bf16 copy;
    # HBM-resident storage stays int8).
    gate = _tap(
        pin_rows(
            jax.lax.ragged_dot(
                xs, qm.dequantize(lp["expert_gate_proj"], x.dtype), group_sizes
            )
        ),
        "moe.gate",
        layer,
    )
    up = pin_rows(
        jax.lax.ragged_dot(
            xs, qm.dequantize(lp["expert_up_proj"], x.dtype), group_sizes
        )
    )
    if config.activation == "gelu_tanh":
        act = jax.nn.gelu(gate, approximate=True) * up
    else:
        act = jax.nn.silu(gate) * up
    down = _tap(
        pin_rows(
            cm.row_parallel_ragged_matmul(
                act, lp["expert_down_proj"], group_sizes, x.dtype, plan
            )
        ),
        "moe.down",
        layer,
    )

    w_sorted = top_w.reshape(-1)[order].astype(down.dtype)  # [N*k]
    out = _tap(
        jax.ops.segment_sum(
            down * w_sorted[:, None], token_of, num_segments=N
        ).astype(h.dtype),
        "moe.combine",
        layer,
    )

    if config.shared_expert_intermediate_size:
        shared = _mlp(
            x,
            {
                "gate_proj": lp["shared_gate_proj"],
                "up_proj": lp["shared_up_proj"],
                "down_proj": lp["shared_down_proj"],
            },
            config.activation,
            plan,
            layer,
        )
        out = out + jax.nn.sigmoid(x @ lp["shared_expert_gate"]) * shared
    return out.reshape(*lead, H)


# ---------------------------------------------------------------------------
# Transformer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Transformer:
    """Functional model: ``prefill`` and ``decode`` over a paged KV cache.

    ``mesh`` (optional) lets the attention dispatch wrap its Pallas
    kernels in ``shard_map`` over the tp axis (ops/dispatch.py); the
    pure-XLA fallback ignores it (GSPMD partitions it directly).
    ``attn_backend``: "auto" | "pallas" | "xla".

    ``tp_overlap``: the RESOLVED mode from
    ``ops/dispatch.resolve_tp_overlap`` — "on" routes the row-parallel
    projections (o_proj, down_proj, expert_down_proj, shared_down_proj)
    through the chunked ppermute rings in ``ops/collective_matmul.py``
    instead of GSPMD's per-layer all-reduces; "off" traces the literal
    pre-existing programs. Static (a frozen field), so every iteration
    of the layer scan — and every jit variant — sees the same choice.

    ``stage``: optional ``(lo, hi)`` GLOBAL layer range for pipeline
    parallelism. When set, the model executes only layers ``lo..hi-1``
    over a per-stage param tree (``parallel/pipeline.slice_stage_params``
    — ``params["layers"]`` leaves carry ``hi - lo`` layers) and a
    per-stage KV pool of the same depth; every forward method then
    accepts an upstream hidden state ``h`` (skipping the embedding
    unless this is the first stage) and can return the full hidden grid
    instead of logits (``return_hidden`` — any stage but the last).
    ``stage=None`` traces byte-identical programs to before the field
    existed.
    """

    config: ModelConfig
    mesh: Any = None
    attn_backend: str = "auto"
    tp_overlap: str = "off"
    stage: Optional[Tuple[int, int]] = None

    def _stage_range(self) -> Tuple[int, int]:
        return self.stage if self.stage is not None else (
            0, self.config.num_layers
        )

    @property
    def is_first_stage(self) -> bool:
        return self._stage_range()[0] == 0

    @property
    def is_last_stage(self) -> bool:
        return self._stage_range()[1] == self.config.num_layers

    # --- shared layer body -------------------------------------------------
    @jax.named_scope("llmq.qkv")
    def _qkv(
        self, lp: Params, h: jnp.ndarray, positions: jnp.ndarray, inv_freq,
        layer=-1,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        cfg = self.config
        d = cfg.head_dim_
        *lead, _ = h.shape
        h = _tap(h, "ln1.out", layer)
        q = qm.matmul(h, lp["q_proj"])
        k = qm.matmul(h, lp["k_proj"])
        v = qm.matmul(h, lp["v_proj"])
        if cfg.attention_bias:
            q = q + lp["q_bias"]
            k = k + lp["k_bias"]
            v = v + lp["v_bias"]
        q = q.reshape(*lead, cfg.num_heads, d)
        k = k.reshape(*lead, cfg.num_kv_heads, d)
        v = v.reshape(*lead, cfg.num_kv_heads, d)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        q = _tap(apply_rope(q, positions, inv_freq), "attn.q", layer)
        k = _tap(apply_rope(k, positions, inv_freq), "attn.k", layer)
        return q, k, _tap(v, "attn.v", layer)

    def _finish_layer(
        self, lp: Params, h: jnp.ndarray, attn_out: jnp.ndarray, layer=-1
    ) -> jnp.ndarray:
        cfg = self.config
        one_plus = cfg.model_type.startswith("gemma")
        plan = cm.ring_plan(self.mesh) if self.tp_overlap == "on" else None
        *lead, _, _ = attn_out.shape
        attn_flat = attn_out.reshape(*lead, cfg.num_heads * cfg.head_dim_)
        attn_flat = _tap(attn_flat, "attn.out", layer)
        with jax.named_scope("llmq.o_proj"):
            attn_proj = _tap(
                cm.row_parallel_matmul(attn_flat, lp["o_proj"], plan, "o_proj"),
                "attn.o_proj",
                layer,
            )
        if cfg.post_norms:
            attn_proj = rms_norm(
                attn_proj, lp["post_attn_norm"], cfg.rms_norm_eps, one_plus=one_plus
            )
        h = h + attn_proj
        mlp_in = rms_norm(h, lp["ln2"], cfg.rms_norm_eps, one_plus=one_plus)
        mlp_out = (
            _moe_mlp(mlp_in, lp, cfg, plan, layer, self.mesh)
            if cfg.num_experts
            else _mlp(mlp_in, lp, cfg.activation, plan, layer)
        )
        if cfg.post_norms:
            mlp_out = rms_norm(
                mlp_out, lp["post_mlp_norm"], cfg.rms_norm_eps, one_plus=one_plus
            )
        return _tap(h + mlp_out, "layer.out", layer)

    def _window_for_layers(self) -> jnp.ndarray:
        """Per-layer effective sliding window ([L] — this stage's layers,
        indexed by GLOBAL layer id); 'disabled' = max ctx."""
        cfg = self.config
        lo, hi = self._stage_range()
        disabled = cfg.max_position_embeddings + 1
        return jnp.array(
            [
                cfg.sliding_window
                if cfg.layer_uses_sliding_window(i)
                else disabled
                for i in range(lo, hi)
            ],
            dtype=jnp.int32,
        )

    def _layer_idx(self) -> jnp.ndarray:
        """Scan xs: LOCAL layer indices — they address the (per-stage)
        KV pool stack, whose leading axis is this stage's layers only.
        With ``stage=None`` local == global."""
        lo, hi = self._stage_range()
        return jnp.arange(hi - lo, dtype=jnp.int32)

    @jax.named_scope("llmq.embed")
    def _embed(self, params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        h = qm.embed_lookup(params["embed"], tokens)
        if cfg.scale_embeddings:
            h = h * jnp.asarray(
                math.sqrt(cfg.hidden_size), dtype=h.dtype
            )
        return h

    @jax.named_scope("llmq.lm_head")
    def _logits(self, params: Params, h: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        one_plus = cfg.model_type.startswith("gemma") or cfg.norm_unit_offset
        h = rms_norm(h, params["final_norm"], cfg.rms_norm_eps, one_plus=one_plus)
        head = params.get("lm_head")
        if head is None:
            logits = qm.tied_head_matmul(h, params["embed"]).astype(jnp.float32)
        else:
            logits = qm.matmul(h, head).astype(jnp.float32)
        if cfg.logit_softcap is not None:
            logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
        return _tap(logits, "lm_head.logits")

    # --- prefill -----------------------------------------------------------
    def prefill(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [B, T] right-padded prompt bucket
        lengths: jnp.ndarray,  # [B] true prompt lengths
        k_pages: jnp.ndarray,  # [L, P, page, n_kv, d]
        v_pages: jnp.ndarray,
        block_tables: jnp.ndarray,  # [B, pages_per_seq]
        *,
        h: Optional[jnp.ndarray] = None,  # [B, T, H] upstream stage hidden
        return_hidden: bool = False,  # stage output: full grid, no logits
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Full-prompt forward. Returns (last-token logits [B, V], k_pages,
        v_pages) with the prompt's K/V written into the cache pages.

        Pipeline stages thread ``h`` in (non-first stages skip the
        embedding) and set ``return_hidden`` (non-last stages return the
        [B, T, H] grid instead of logits)."""
        cfg = self.config
        B, T = tokens.shape
        inv_freq = compute_rope_inv_freq(cfg)
        pos_grid = jnp.arange(T)[None, :].astype(jnp.int32)
        positions = jnp.where(
            pos_grid < lengths[:, None], jnp.broadcast_to(pos_grid, (B, T)), -1
        )
        if h is None:
            h = self._embed(params, tokens)
        windows = self._window_for_layers()
        one_plus = cfg.model_type.startswith("gemma")

        page_size = k_pages.shape[2]
        page_aligned = T % page_size == 0

        def layer_fn(carry, xs):
            # KV pages ride in the carry as the full [L, ...] stack and are
            # written via a layer-indexed scatter: slicing the per-layer
            # pool out (and re-inserting it) forces XLA to materialize
            # full-pool copies around the attention custom call.
            h, kps, vps = carry
            lp, window, li = xs
            x = rms_norm(h, lp["ln1"], cfg.rms_norm_eps, one_plus=one_plus)
            q, k, v = self._qkv(lp, x, positions, inv_freq, li)
            with jax.named_scope("llmq.kv_write"):
                if page_aligned:
                    # Prompt positions are 0..T-1, so whole pages can be
                    # written in one block-scatter row each (~10 ms/chunk
                    # cheaper than the token scatter at 3B/8x256, measured).
                    kps, vps = attn_ops.write_prompt_kv_pages(
                        kps, vps, k, v, block_tables, li, mesh=self.mesh
                    )
                else:
                    kps, vps = attn_ops.write_kv_pages(
                        kps, vps, k, v, block_tables, positions, layer=li
                    )
            attn_out = attn_dispatch.prefill_attention(
                q,
                k,
                v,
                scale=cfg.attn_scale,
                lengths=lengths,
                sliding_window=window,
                softcap=cfg.attn_softcap,
                mesh=self.mesh,
                backend=self.attn_backend,
            )
            h = self._finish_layer(lp, h, attn_out, li)
            return (h, kps, vps), None

        (h, k_pages, v_pages), _ = jax.lax.scan(
            layer_fn,
            (h, k_pages, v_pages),
            (params["layers"], windows, self._layer_idx()),
        )
        if return_hidden:
            return h, k_pages, v_pages
        last_idx = jnp.maximum(lengths - 1, 0)
        last_h = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)[:, 0]
        return self._logits(params, last_h), k_pages, v_pages

    # --- shared paged-chunk trunk ------------------------------------------
    def _paged_chunk_trunk(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [B, C] query tokens (padding rows arbitrary)
        positions: jnp.ndarray,  # [B, C] absolute positions (−1 = padding)
        k_pages: jnp.ndarray,  # [L, P, page, n_kv, d]
        v_pages: jnp.ndarray,
        block_tables: jnp.ndarray,  # [B, pages_per_seq]
        *,
        backend: Optional[str] = None,
        h: Optional[jnp.ndarray] = None,  # [B, C, H] upstream stage hidden
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """The write-then-attend layer scan shared by chunked prefill,
        speculative verify, and the fused mixed step: write each row's
        valid positions' K/V into the paged cache, attend every query
        against everything cached so far (causal), run the MLP. Returns
        the full hidden grid ``[B, C, H]`` — callers choose which
        positions become logits. Per-row positions must satisfy the
        leading-contiguous-run contract of
        ``ops/dispatch.chunked_prefill_attention``."""
        cfg = self.config
        inv_freq = compute_rope_inv_freq(cfg)
        if h is None:
            h = self._embed(params, tokens)  # [B, C, H]
        windows = self._window_for_layers()
        one_plus = cfg.model_type.startswith("gemma")
        attn_backend = self.attn_backend if backend is None else backend

        def layer_fn(carry, xs):
            h, kps, vps = carry
            lp, window, li = xs
            x = rms_norm(h, lp["ln1"], cfg.rms_norm_eps, one_plus=one_plus)
            q, k, v = self._qkv(lp, x, positions, inv_freq, li)
            with jax.named_scope("llmq.kv_write"):
                kps, vps = attn_ops.write_kv_pages(
                    kps, vps, k, v, block_tables, positions, layer=li
                )
            attn_out = attn_dispatch.chunked_prefill_attention(
                q,
                kps,
                vps,
                block_tables,
                positions,
                scale=cfg.attn_scale,
                sliding_window=window,
                softcap=cfg.attn_softcap,
                mesh=self.mesh,
                backend=attn_backend,
                layer=li,
            )
            h = self._finish_layer(lp, h, attn_out, li)
            return (h, kps, vps), None

        return jax.lax.scan(
            layer_fn,
            (h, k_pages, v_pages),
            (params["layers"], windows, self._layer_idx()),
        )[0]

    # --- chunked prefill ---------------------------------------------------
    def prefill_chunk(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [B, C] one chunk of prompt tokens
        positions: jnp.ndarray,  # [B, C] absolute positions (−1 = padding)
        k_pages: jnp.ndarray,  # [L, P, page, n_kv, d]
        v_pages: jnp.ndarray,
        block_tables: jnp.ndarray,  # [B, pages_per_seq]
        last_in_chunk: jnp.ndarray,  # [B] index of each row's final valid
        #                              position within this chunk (0 if none)
        *,
        h: Optional[jnp.ndarray] = None,  # [B, C, H] upstream stage hidden
        return_hidden: bool = False,  # stage output: full grid, no logits
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One fixed-size chunk of prompt positions through all layers:
        writes the chunk's K/V into the cache and attends each query
        against everything cached so far (earlier chunks + itself,
        causal). Any prompt length runs through ONE compiled executable —
        no per-bucket variants, ≤ C−1 positions of padding — and a long
        prompt no longer stalls decode for its whole length (the engine
        interleaves decode steps between chunks). Returns logits for each
        row's ``last_in_chunk`` position (meaningful only on a row's
        final chunk) plus the updated pages.
        """
        h, k_pages, v_pages = self._paged_chunk_trunk(
            params, tokens, positions, k_pages, v_pages, block_tables, h=h
        )
        if return_hidden:
            return h, k_pages, v_pages
        last_h = jnp.take_along_axis(
            h, last_in_chunk[:, None, None], axis=1
        )[:, 0]
        return self._logits(params, last_h), k_pages, v_pages

    # --- fused mixed prefill+decode ----------------------------------------
    def mixed(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [S, C] combined query grid (see engine)
        positions: jnp.ndarray,  # [S, C] absolute positions (−1 = padding)
        k_pages: jnp.ndarray,  # [L, P, page, n_kv, d]
        v_pages: jnp.ndarray,
        block_tables: jnp.ndarray,  # [S, pages_per_seq]
        gather_idx: jnp.ndarray,  # [S] which chunk position becomes the
        #                           row's logits (decode rows: 0; the
        #                           piggy row: its segment's last valid)
        *,
        h: Optional[jnp.ndarray] = None,  # [S, C, H] upstream stage hidden
        return_hidden: bool = False,  # stage output: full grid, no logits
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One fused mixed step: every active decode slot scores its
        single next position while ONE pending request's prefill chunk
        segment rides along in the same grid — decode rows occupy column
        0 of the ``[S, C]`` grid (a one-position leading run at their
        context length), the piggy row carries its budgeted segment (a
        leading run at the chunk offset). The paged-KV writes keep rows
        isolated, so decode math is position-for-position identical to
        the plain decode step; only the LM-head input is gathered
        per-row (``gather_idx``) to avoid an S·C logit grid. Returns
        (logits [S, V], k_pages, v_pages)."""
        cfg = self.config
        kernel = attn_dispatch.mixed_kernel_plan(
            cfg.num_heads, cfg.num_kv_heads, self.mesh, self.attn_backend
        )
        h, k_pages, v_pages = self._paged_chunk_trunk(
            params,
            tokens,
            positions,
            k_pages,
            v_pages,
            block_tables,
            backend="xla" if kernel == "xla" else self.attn_backend,
            h=h,
        )
        if return_hidden:
            return h, k_pages, v_pages
        row_h = jnp.take_along_axis(
            h, gather_idx[:, None, None], axis=1
        )[:, 0]
        return self._logits(params, row_h), k_pages, v_pages

    # --- speculative verify ------------------------------------------------
    def verify(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [S, Q] current token + draft candidates
        positions: jnp.ndarray,  # [S, Q] absolute positions (−1 = inactive)
        k_pages: jnp.ndarray,  # [L, P, page, n_kv, d]
        v_pages: jnp.ndarray,
        block_tables: jnp.ndarray,  # [S, pages_per_seq]
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Multi-query decode step for speculative verification: scores
        Q = spec_tokens+1 candidate positions per slot in one dispatch.
        The body is ``prefill_chunk`` with the slot axis as the batch —
        write the candidates' K/V, then attend each candidate against the
        whole cache causally — but logits come back for *every* position
        ([S, Q, V]), since acceptance needs the model's choice at each
        one. Per-row positions must be a leading contiguous run
        ``[ctx .. ctx+n, -1 …]`` (the chunked-prefill kernel contract);
        rejected candidates' K/V stay in place and are overwritten by the
        next verify step at the same positions, so no cache rollback is
        needed.
        """
        h, k_pages, v_pages = self._paged_chunk_trunk(
            params, tokens, positions, k_pages, v_pages, block_tables
        )
        return self._logits(params, h), k_pages, v_pages

    # --- decode ------------------------------------------------------------
    def decode(
        self,
        params: Params,
        tokens: jnp.ndarray,  # [S] current token per slot
        context_lens: jnp.ndarray,  # [S] tokens already cached (excl. new)
        k_pages: jnp.ndarray,  # [L, P, page, n_kv, d]
        v_pages: jnp.ndarray,
        block_tables: jnp.ndarray,  # [S, pages_per_seq]
        active: jnp.ndarray,  # [S] bool — slot holds a live sequence
        *,
        h: Optional[jnp.ndarray] = None,  # [S, H] upstream stage hidden
        return_hidden: bool = False,  # stage output: hidden, no logits
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One decode step for every active slot. Returns (logits [S, V],
        k_pages, v_pages).

        Scan-compatible by construction: a pure function of its array
        arguments (the engine's fused decode blocks run it K times
        inside one ``lax.scan`` with (k_pages, v_pages, state) as the
        carry), and the only Python-level branching — the kernel plan
        ``decode_attention`` resolves at trace time — is a function of
        shapes and the backend alone, so every scan iteration inlines
        the identical kernel choice. Inactive slots write no KV: the
        scatter routes their positions to -1 (dropped)."""
        cfg = self.config
        S = tokens.shape[0]
        inv_freq = compute_rope_inv_freq(cfg)
        positions = jnp.where(active, context_lens, -1).astype(jnp.int32)  # [S]
        if h is None:
            h = self._embed(params, tokens)  # [S, H]
        windows = self._window_for_layers()
        one_plus = cfg.model_type.startswith("gemma")
        ctx_incl = jnp.where(active, context_lens + 1, 0)

        def layer_fn(carry, xs):
            h, kps, vps = carry
            lp, window, li = xs
            x = rms_norm(h, lp["ln1"], cfg.rms_norm_eps, one_plus=one_plus)
            q, k, v = self._qkv(lp, x[:, None, :], positions[:, None], inv_freq, li)
            # q/k/v: [S, 1, heads, d]. The KV stack is written and read
            # in place via the layer index — see prefill's layer_fn.
            with jax.named_scope("llmq.kv_write"):
                kps, vps = attn_ops.write_kv_pages(
                    kps, vps, k, v, block_tables, positions[:, None],
                    layer=li,
                )
            attn_out = attn_dispatch.decode_attention(
                q[:, 0],
                kps,
                vps,
                block_tables,
                ctx_incl,
                scale=cfg.attn_scale,
                sliding_window=window,
                softcap=cfg.attn_softcap,
                mesh=self.mesh,
                backend=self.attn_backend,
                layer=li,
            )
            h = self._finish_layer(lp, h, attn_out, li)
            return (h, kps, vps), None

        (h, k_pages, v_pages), _ = jax.lax.scan(
            layer_fn,
            (h, k_pages, v_pages),
            (params["layers"], windows, self._layer_idx()),
        )
        if return_hidden:
            return h, k_pages, v_pages
        return self._logits(params, h), k_pages, v_pages


def build_model(config: ModelConfig, **kwargs) -> Transformer:
    """The model of a configuration: the uniform stack above, or, for a
    declared layer pattern, ``models/hybrid.HybridTransformer``."""
    if config.layer_pattern is None:
        return Transformer(config, **kwargs)
    from llmq_tpu.models import hybrid

    return hybrid.HybridTransformer(config, **kwargs)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

# Quantized random init generates + quantizes stacked weights one
# leading-axis slice at a time past this full-precision size — a 9B
# gate_proj is ~11 GB in f32, which alone exhausts a 16 GB chip
# (measured: the r05 int8-9B bench died inside init_params before the
# chunked path existed). Patchable so tests exercise the chunked path
# on tiny models.
CHUNKED_INIT_F32_BYTES = 1 << 30


def init_params(
    config: ModelConfig, key: jax.Array, dtype=jnp.float32,
    *, quantize: bool | str = False,
) -> Params:
    """Random init (testing / benchmarks without a checkpoint).

    ``quantize`` produces the int8 weight-only tree (``models/quant.py``)
    directly: each big weight is quantized with a donated jit the moment
    it is created, so peak HBM is the int8 tree plus ONE full-precision
    tensor — a 9B preset quantizes on a 16 GB chip where init-then-
    quantize would OOM on the bf16 tree alone. ``quantize="int4"`` puts
    the layer matmul weights on the packed int4 group rung instead
    (embed/lm_head stay int8, mirroring the checkpoint loader)."""
    if config.layer_pattern is not None:
        if quantize:
            raise ValueError(
                "quantised weights are not built for a model with a layer pattern"
            )
        from llmq_tpu.models import hybrid

        return hybrid.init_params(config, key, dtype)
    cfg = config
    quant_mode = (
        "int4" if str(quantize).lower() == "int4"
        else ("int8" if quantize else None)
    )
    d = cfg.head_dim_
    L, H, I = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    keys = iter(jax.random.split(key, 16))

    def w(key, shape, fan_in, *, q: bool = False, axis: int = -2,
          top: bool = False):
        int4 = bool(q) and quant_mode == "int4" and not top
        f32_bytes = 4 * math.prod(shape)
        if quant_mode and q and f32_bytes > CHUNKED_INIT_F32_BYTES and len(shape) > 2:
            # Big stacked weights (a 9B gate_proj is ~11 GB in f32):
            # generate + quantize one leading-axis slice at a time so the
            # full-precision transient is one LAYER, not the whole stack —
            # then stack the int8 results. Small weights keep the
            # single-shot path (and its exact random stream).
            parts = []
            for k in jax.random.split(key, shape[0]):
                arr = (
                    jax.random.normal(k, shape[1:], jnp.float32)
                    / math.sqrt(fan_in)
                ).astype(dtype)
                parts.append(
                    qm.quantize_array_int4_donated(arr, scale_dtype=dtype)
                    if int4
                    else qm.quantize_array_donated(
                        arr, axis=axis, scale_dtype=dtype
                    )
                )
            return {
                key_: jnp.stack([p[key_] for p in parts])
                for key_ in parts[0]
            }
        arr = (
            jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        ).astype(dtype)
        if quant_mode and q:
            if int4:
                return qm.quantize_array_int4_donated(arr, scale_dtype=dtype)
            return qm.quantize_array_donated(arr, axis=axis, scale_dtype=dtype)
        return arr

    layers: Params = {
        "ln1": jnp.ones((L, H), dtype),
        "ln2": jnp.ones((L, H), dtype),
        "q_proj": w(next(keys), (L, H, cfg.num_heads * d), H, q=True),
        "k_proj": w(next(keys), (L, H, cfg.num_kv_heads * d), H, q=True),
        "v_proj": w(next(keys), (L, H, cfg.num_kv_heads * d), H, q=True),
        "o_proj": w(
            next(keys), (L, cfg.num_heads * d, H), cfg.num_heads * d, q=True
        ),
    }
    if cfg.num_experts:
        E, Im = cfg.num_experts, cfg.moe_intermediate_size
        layers["router"] = w(next(keys), (L, H, E), H)
        layers["expert_gate_proj"] = w(next(keys), (L, E, H, Im), H, q=True)
        layers["expert_up_proj"] = w(next(keys), (L, E, H, Im), H, q=True)
        layers["expert_down_proj"] = w(next(keys), (L, E, Im, H), Im, q=True)
        if cfg.shared_expert_intermediate_size:
            Is = cfg.shared_expert_intermediate_size
            layers["shared_gate_proj"] = w(next(keys), (L, H, Is), H, q=True)
            layers["shared_up_proj"] = w(next(keys), (L, H, Is), H, q=True)
            layers["shared_down_proj"] = w(next(keys), (L, Is, H), Is, q=True)
            layers["shared_expert_gate"] = w(next(keys), (L, H, 1), H)
    else:
        layers["gate_proj"] = w(next(keys), (L, H, I), H, q=True)
        layers["up_proj"] = w(next(keys), (L, H, I), H, q=True)
        layers["down_proj"] = w(next(keys), (L, I, H), I, q=True)
    if cfg.attention_bias:
        layers["q_bias"] = jnp.zeros((L, cfg.num_heads * d), dtype)
        layers["k_bias"] = jnp.zeros((L, cfg.num_kv_heads * d), dtype)
        layers["v_bias"] = jnp.zeros((L, cfg.num_kv_heads * d), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, d), dtype)
        layers["k_norm"] = jnp.ones((L, d), dtype)
    if cfg.post_norms:
        layers["post_attn_norm"] = jnp.ones((L, H), dtype)
        layers["post_mlp_norm"] = jnp.ones((L, H), dtype)
    params: Params = {
        "embed": w(next(keys), (cfg.vocab_size, H), H, q=True, axis=-1,
                   top=True),
        "layers": layers,
        "final_norm": jnp.ones((H,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w(next(keys), (H, cfg.vocab_size), H, q=True,
                              top=True)
    return params
