"""Model architecture config.

One config dataclass covers the decoder families the reference deployments
used (SURVEY.md §6, BASELINE.json): Llama-3.x, Qwen2/2.5 (Tower-Plus models
are Qwen2.5 finetunes), Gemma-2, Mistral. ``from_hf_config`` maps a
HuggingFace ``config.json`` onto it.

Family differences expressed as data, not subclasses:

- Qwen2: attention QKV bias (``attention_bias=True``).
- Gemma-2: GeLU MLP, embedding scaling by sqrt(hidden), logit softcapping,
  attn softcapping, post-norms around attn/mlp, alternating sliding-window
  layers, head_dim != hidden/n_heads.
- Llama/Mistral: the baseline (SiLU MLP, RoPE, GQA, RMSNorm).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, Optional, Tuple


def _as_id_list(ids: Any) -> list:
    """Normalize an HF token-id field: int, list, or absent → list[int]."""
    if ids is None:
        return []
    if isinstance(ids, int):
        return [ids]
    return list(ids)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    intermediate_size: int
    head_dim: Optional[int] = None  # default hidden_size // num_heads
    max_position_embeddings: int = 131072
    rope_theta: float = 10000.0
    rope_scaling: Optional[Dict[str, Any]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style QKV bias
    activation: str = "silu"  # "silu" | "gelu_tanh"
    scale_embeddings: bool = False  # Gemma: embed * sqrt(hidden)
    logit_softcap: Optional[float] = None  # Gemma-2 final softcap
    attn_softcap: Optional[float] = None  # Gemma-2 attention softcap
    post_norms: bool = False  # Gemma-2 post-attn/post-mlp norms; pangu's sandwich_norm
    qk_norm: bool = False  # Qwen3/Gemma-3 per-head q/k RMSNorm
    sliding_window: Optional[int] = None
    sliding_window_pattern: int = 1  # every Nth layer is global (Gemma-2: 2)
    query_pre_attn_scalar: Optional[float] = None  # Gemma-2 attn scale
    # Mixture-of-experts (qwen2_moe/qwen3_moe): None → dense MLP.
    num_experts: Optional[int] = None
    num_experts_per_tok: int = 0
    moe_intermediate_size: Optional[int] = None
    shared_expert_intermediate_size: Optional[int] = None  # qwen2_moe only
    norm_topk_prob: bool = False  # renormalize the top-k routing weights
    # Declared layer pattern (bailing_hybrid, pangu_ultra_moe, lfm2_moe,
    # evabyte): one (attention, mlp) pair a layer, attention "kda" | "mla"
    # | "conv" | "gqa" | "eva", mlp "dense" | "moe". None: every layer is the family's one
    # kind, run by ``models/transformer.py`` as before; a pattern is run by
    # ``models/hybrid.py``.
    layer_pattern: Optional[Tuple[Tuple[str, str], ...]] = None
    # KDA (delta-rule) layers: heads are num_heads x head_dim. The taps of
    # their short convolution, and of a "conv" layer's (``conv_L_cache``).
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0  # bounded ("safe") gate: g in [bound, 0]
    # MLA (latent attention) layers. ``q_lora_rank``: the query goes through
    # a normed latent of that width; None: one full-rank projection.
    # ``mla_head_gate``: a head-wise sigmoid gate before ``o_proj``.
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    mla_head_gate: bool = False
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # Sigmoid router of a layer pattern: groups (1: none), a selection
    # bias (``router_bias``), a scaling factor.
    n_group: int = 1
    topk_group: int = 1
    router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # Added to the sum of the chosen scores before it divides them.
    router_norm_eps: float = 1e-20
    # (first, count) of the routed experts this chip holds; None: all of
    # them. The router keeps its width (num_experts) either way.
    experts_held: Optional[Tuple[int, int]] = None
    # EVA layers ("eva"): exact softmax inside the query's own window of
    # ``eva_window`` positions, one learned summary a chunk of ``eva_chunk``
    # for every earlier window (``ops/attention.eva_row``: the cache's rows).
    eva_window: Optional[int] = None
    eva_chunk: Optional[int] = None
    # RMSNorm weights are ``1 + w`` (evabyte's ``norm_add_unit_offset``), in
    # a layer pattern; the Gemma families are told by their ``model_type``.
    norm_unit_offset: bool = False
    eos_token_ids: Tuple[int, ...] = ()
    bos_token_id: Optional[int] = None
    model_type: str = "llama"

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def attn_scale(self) -> float:
        if self.query_pre_attn_scalar is not None:
            return self.query_pre_attn_scalar**-0.5
        return self.head_dim_**-0.5

    def layer_uses_sliding_window(self, layer: int) -> bool:
        """Gemma-2 interleaves sliding/global attention layers."""
        if self.sliding_window is None:
            return False
        if self.sliding_window_pattern <= 1:
            return True
        return (layer % self.sliding_window_pattern) != (
            self.sliding_window_pattern - 1
        )

    # ------------------------------------------------------------------
    @classmethod
    def from_hf_config(cls, hf: Dict[str, Any]) -> "ModelConfig":
        """Map a HuggingFace config.json dict (llama/qwen2/gemma2/mistral)."""
        mt = hf.get("model_type", "llama")
        if mt == "bailing_hybrid":
            return cls._from_bailing_hybrid(hf)
        if mt == "pangu_ultra_moe":
            return cls._from_pangu_ultra_moe(hf)
        if mt == "lfm2_moe":
            return cls._from_lfm2_moe(hf)
        if mt == "evabyte":
            return cls._from_evabyte(hf)
        eos = _as_id_list(hf.get("eos_token_id"))
        common = dict(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            intermediate_size=hf["intermediate_size"],
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get("max_position_embeddings", 131072),
            rope_theta=hf.get("rope_theta", 10000.0),
            rope_scaling=hf.get("rope_scaling"),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            eos_token_ids=tuple(eos),
            bos_token_id=hf.get("bos_token_id"),
            model_type=mt,
        )
        if mt in ("llama", "mistral"):
            return cls(
                **common,
                attention_bias=hf.get("attention_bias", False),
                sliding_window=hf.get("sliding_window"),
            )
        if mt == "qwen2":
            # Qwen2 ships QKV bias; sliding window usually disabled in config.
            return cls(
                **common,
                attention_bias=True,
                sliding_window=(
                    hf.get("sliding_window") if hf.get("use_sliding_window") else None
                ),
            )
        if mt in ("qwen2_moe", "qwen3_moe"):
            # Sparse-MoE decoders. Only the uniform all-sparse layout is
            # supported (every public qwen-MoE checkpoint uses it); a
            # config interleaving dense layers must fail loudly rather
            # than produce silently-wrong numerics.
            if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
                raise ValueError(
                    f"{mt} with interleaved dense layers "
                    "(mlp_only_layers/decoder_sparse_step) is not supported"
                )
            return cls(
                **common,
                attention_bias=(mt == "qwen2_moe"),
                qk_norm=(mt == "qwen3_moe"),
                sliding_window=(
                    hf.get("sliding_window") if hf.get("use_sliding_window") else None
                ),
                num_experts=hf["num_experts"],
                num_experts_per_tok=hf["num_experts_per_tok"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                shared_expert_intermediate_size=(
                    hf.get("shared_expert_intermediate_size")
                    if mt == "qwen2_moe"
                    else None
                ),
                norm_topk_prob=hf.get("norm_topk_prob", False),
            )
        if mt == "qwen3":
            return cls(**common, attention_bias=False, qk_norm=True)
        if mt == "gemma2":
            return cls(
                **common,
                activation="gelu_tanh",
                scale_embeddings=True,
                logit_softcap=hf.get("final_logit_softcapping", 30.0),
                attn_softcap=hf.get("attn_logit_softcapping", 50.0),
                post_norms=True,
                sliding_window=hf.get("sliding_window", 4096),
                sliding_window_pattern=2,
                query_pre_attn_scalar=hf.get("query_pre_attn_scalar"),
            )
        raise ValueError(f"Unsupported model_type: {mt!r}")

    @classmethod
    def _from_bailing_hybrid(cls, hf: Dict[str, Any]) -> "ModelConfig":
        """Ling-3.0 (``bailing_hybrid``): KDA layers with every
        ``layer_group_size``-th an MLA layer, ``first_k_dense_replace``
        dense MLPs and routed experts after. Two keys of this repo's own
        say what of the published model is here: ``kept_layers`` (published
        layer indices, default all) and ``experts_held`` ([first, count],
        default all). The multi-token-prediction module
        (``num_nextn_predict_layers``) is a draft head only
        self-speculation runs and is not built, as public loaders do."""
        group = int(hf["layer_group_size"])
        dense = int(hf["first_k_dense_replace"])
        kept = hf.get("kept_layers")
        if kept is None:
            kept = range(int(hf["num_hidden_layers"]))
        kept = tuple(int(i) for i in kept)
        for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            limits = hf.get(key) or []
            clamped = [i for i in kept if i < len(limits) and limits[i]]
            if clamped:
                raise ValueError(
                    f"bailing_hybrid: {key} is non-zero on kept layers "
                    f"{clamped}: the clamped SwiGLU is not built"
                )
        # What the block is built for; any other value is another block.
        wanted = {
            "q_lora_rank": None, "rope_scaling": None, "use_kda_lora": False,
            "use_bias": False, "use_qkv_bias": False, "use_nGPT": False,
            "value_norm": False, "up_proj_norm": False,
            "scale_router_input": False, "use_mla_nope": False,
            "no_kda_lora": True, "kda_safe_gate": True, "linear_silu": True,
            "rope_interleave": True, "use_qk_norm": True, "group_norm_size": 1,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "gated_attention_proj_granularity_type": "head_wise",
            "hidden_act": "silu",
        }
        for key, want in wanted.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"bailing_hybrid: {key}={hf[key]!r} is not built "
                    f"(only {want!r} is)"
                )
        held = hf.get("experts_held")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=len(kept),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            intermediate_size=hf["intermediate_size"],
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get("max_position_embeddings", 131072),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            eos_token_ids=tuple(_as_id_list(hf.get("eos_token_id"))),
            bos_token_id=hf.get("bos_token_id"),
            model_type="bailing_hybrid",
            layer_pattern=tuple(
                (
                    "mla" if (i + 1) % group == 0 else "kda",
                    "dense" if i < dense else "moe",
                )
                for i in kept
            ),
            short_conv_kernel_size=hf.get("short_conv_kernel_size", 4),
            kda_lower_bound=float(hf.get("kda_lower_bound", -5.0)),
            kv_lora_rank=hf["kv_lora_rank"],
            mla_head_gate=True,
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            shared_expert_intermediate_size=(
                hf["moe_shared_expert_intermediate_size"]
                * int(hf.get("num_shared_experts", 1))
            ),
            norm_topk_prob=hf.get("norm_topk_prob", True),
            n_group=hf.get("n_group", 1),
            topk_group=hf.get("topk_group", 1),
            router_bias=True,
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            experts_held=None if held is None else (int(held[0]), int(held[1])),
        )

    @classmethod
    def _from_pangu_ultra_moe(cls, hf: Dict[str, Any]) -> "ModelConfig":
        """openPangu-Ultra-MoE (``pangu_ultra_moe``): latent attention on
        every layer, the query through a normed latent (``q_lora_rank``),
        ``first_k_dense_replace`` dense MLPs and routed experts after
        (one group, no selection bias, sigmoid scores) with
        ``n_shared_experts`` shared ones run as one of their summed width;
        ``sandwich_norm``: an RMSNorm on each sub-layer's output before
        the residual add. ``experts_held`` ([first, count], default all)
        is this repo's own key, as in ``bailing_hybrid``. The
        multi-token-prediction module (``num_nextn_predict_layers``) is
        not built, as public loaders leave it out."""
        wanted = {
            "attention_bias": False, "hidden_act": "silu", "rope_scaling": None,
            "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid",
            "moe_router_enable_expert_bias": False, "rope_interleave": True,
            "num_key_value_heads": hf["num_attention_heads"],
        }
        for key, want in wanted.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"pangu_ultra_moe: {key}={hf[key]!r} is not built "
                    f"(only {want!r} is)"
                )
        layers, dense = int(hf["num_hidden_layers"]), int(hf["first_k_dense_replace"])
        held = hf.get("experts_held")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=layers,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_attention_heads"],
            intermediate_size=hf["intermediate_size"],
            max_position_embeddings=hf.get("max_position_embeddings", 131072),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            post_norms=bool(hf.get("sandwich_norm", False)),
            eos_token_ids=tuple(_as_id_list(hf.get("eos_token_id"))),
            bos_token_id=hf.get("bos_token_id"),
            model_type="pangu_ultra_moe",
            layer_pattern=tuple(
                ("mla", "dense" if i < dense else "moe") for i in range(layers)
            ),
            kv_lora_rank=hf["kv_lora_rank"],
            q_lora_rank=hf.get("q_lora_rank"),
            qk_nope_head_dim=hf["qk_nope_head_dim"],
            qk_rope_head_dim=hf["qk_rope_head_dim"],
            v_head_dim=hf["v_head_dim"],
            num_experts=hf["n_routed_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            shared_expert_intermediate_size=(
                hf["moe_intermediate_size"] * int(hf.get("n_shared_experts", 1))
            ),
            norm_topk_prob=hf.get("norm_topk_prob", True),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            experts_held=None if held is None else (int(held[0]), int(held[1])),
        )

    @classmethod
    def _from_lfm2_moe(cls, hf: Dict[str, Any]) -> "ModelConfig":
        """LFM2 with routed experts (``lfm2_moe``): ``layer_types`` names
        each layer's operator, a gated short convolution (``conv``, a tail
        of ``conv_L_cache - 1`` inputs a sequence, no attention) or
        grouped-query softmax attention (``full_attention``) with per-head
        RMSNorms of q and k before half-split rotary; the first
        ``num_dense_layers`` MLPs are dense, the rest sigmoid-routed
        experts chosen by score plus ``expert_bias`` and weighted by the
        score alone, no shared expert. ``kept_layers`` (published layer
        indices, default all) is this repo's own key: layer ``j`` of those
        kept has the operator of its published index and a dense MLP
        where ``j < num_dense_layers``."""
        rope = hf.get("rope_parameters") or {}
        wanted = {"conv_bias": False, "hidden_act": "silu", "rope_scaling": None}
        for key, want in wanted.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"lfm2_moe: {key}={hf[key]!r} is not built (only {want!r} is)"
                )
        if rope.get("rope_type", "default") != "default":
            raise ValueError(
                f"lfm2_moe: rope_type={rope['rope_type']!r} is not built "
                "(only 'default' is)"
            )
        kinds = {"conv": "conv", "full_attention": "gqa"}
        kept = hf.get("kept_layers")
        if kept is None:
            kept = range(int(hf["num_hidden_layers"]))
        kept = tuple(int(i) for i in kept)
        if len(kept) != int(hf["num_hidden_layers"]):
            raise ValueError(
                f"lfm2_moe: kept_layers names {len(kept)} layers, "
                f"num_hidden_layers {hf['num_hidden_layers']}"
            )
        dense = int(hf.get("num_dense_layers", 0))
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=len(kept),
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
            intermediate_size=hf["intermediate_size"],
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get("max_position_embeddings", 128000),
            rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
            rms_norm_eps=hf.get("norm_eps", 1e-5),
            tie_word_embeddings=hf.get(
                "tie_embedding", hf.get("tie_word_embeddings", True)
            ),
            qk_norm=True,
            eos_token_ids=tuple(_as_id_list(hf.get("eos_token_id"))),
            bos_token_id=hf.get("bos_token_id"),
            model_type="lfm2_moe",
            layer_pattern=tuple(
                (kinds[hf["layer_types"][i]], "dense" if j < dense else "moe")
                for j, i in enumerate(kept)
            ),
            short_conv_kernel_size=int(hf.get("conv_L_cache", 3)),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            shared_expert_intermediate_size=None,
            norm_topk_prob=hf.get("norm_topk_prob", True),
            router_bias=bool(hf.get("use_expert_bias", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            router_norm_eps=1e-6,
        )

    @classmethod
    def _from_evabyte(cls, hf: Dict[str, Any]) -> "ModelConfig":
        """EvaByte (``evabyte``): every layer multi-head EVA attention
        (``attention_class`` "eva": exact softmax inside the query's own
        ``window_size`` positions, one learned summary a ``chunk_size``
        positions for every earlier window) and a SwiGLU MLP, RMSNorm
        weights ``1 + w`` (``norm_add_unit_offset``), rotary over the whole
        head. Its float32 residual adds and logits (``fp32_skip_add``,
        ``fp32_logits``) are what the step computes anyway: a bf16 add is
        made in float32 and rounded, the logits are float32. Only
        prediction head 0 is served (``num_pred_heads`` is not read)."""
        wanted = {
            "attention_class": "eva", "hidden_act": "silu", "rope_scaling": None,
            "attention_bias": False, "norm_add_unit_offset": True,
        }
        for key, want in wanted.items():
            if hf.get(key, want) != want:
                raise ValueError(
                    f"evabyte: {key}={hf[key]!r} is not built (only {want!r} is)"
                )
        heads = hf["num_attention_heads"]
        if hf.get("num_key_value_heads", heads) != heads:
            raise ValueError("evabyte: EVA layers are multi-head (kv heads = heads)")
        window, chunk = int(hf["window_size"]), int(hf["chunk_size"])
        if window % chunk:
            raise ValueError(f"evabyte: window_size {window} is not whole chunks of {chunk}")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=heads,
            intermediate_size=hf["intermediate_size"],
            head_dim=hf.get("head_dim"),
            max_position_embeddings=hf.get("max_position_embeddings", 32768),
            rope_theta=float(hf.get("rope_theta", 100000)),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            eos_token_ids=tuple(_as_id_list(hf.get("eos_token_id"))),
            bos_token_id=hf.get("bos_token_id"),
            model_type="evabyte",
            layer_pattern=(("eva", "dense"),) * int(hf["num_hidden_layers"]),
            eva_window=window,
            eva_chunk=chunk,
            norm_unit_offset=True,
        )

    @property
    def experts_held_(self) -> Tuple[int, int]:
        """(first, count) of the routed experts held here."""
        return self.experts_held or (0, self.num_experts or 0)

    @classmethod
    def from_pretrained(cls, model_path: str | Path) -> "ModelConfig":
        """Load from a local HF checkpoint directory's config.json.

        ``generation_config.json``'s EOS set is unioned in: Llama-3-style
        checkpoints list the extra stop ids (e.g. ``<|eot_id|>``) *only*
        there, and a model that never stops on its chat-turn terminator
        generates garbage tails (reference parity: vLLM reads the
        generation config, ``llmq/workers/vllm_worker.py:148-165``).
        """
        base = Path(model_path)
        hf = json.loads((base / "config.json").read_text())
        gen_path = base / "generation_config.json"
        if gen_path.exists():
            try:
                gen = json.loads(gen_path.read_text())
            # ValueError covers JSONDecodeError and UnicodeDecodeError
            # (corrupt bytes must not abort model loading either).
            except (OSError, ValueError):
                gen = None
            # Tolerate any malformed shape, not just broken syntax.
            gen_eos = gen.get("eos_token_id") if isinstance(gen, dict) else None
            if gen_eos is not None:
                hf["eos_token_id"] = list(
                    dict.fromkeys(  # ordered union
                        _as_id_list(hf.get("eos_token_id"))
                        + _as_id_list(gen_eos)
                    )
                )
        return cls.from_hf_config(hf)

    # --- handy test configs ------------------------------------------------
    @classmethod
    def tiny(cls, **overrides) -> "ModelConfig":
        base = dict(
            vocab_size=256,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            intermediate_size=128,
            rope_theta=10000.0,
            eos_token_ids=(0,),
        )
        base.update(overrides)
        return cls(**base)

    def num_params(self) -> int:
        """Approximate parameter count (for memory budgeting)."""
        h, v, l = self.hidden_size, self.vocab_size, self.num_layers
        d = self.head_dim_
        attn = h * d * self.num_heads + 2 * h * d * self.num_kv_heads + d * self.num_heads * h
        if self.num_experts:
            mlp = 3 * h * (self.moe_intermediate_size or 0) * self.num_experts
            mlp += h * self.num_experts  # router
            if self.shared_expert_intermediate_size:
                mlp += 3 * h * self.shared_expert_intermediate_size + h
        else:
            mlp = 3 * h * self.intermediate_size
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        return l * (attn + mlp + 2 * h) + embed + h

    def active_params_per_token(self) -> int:
        """Params touched per token (MoE: only routed + shared experts) —
        the MFU-relevant count for throughput estimates."""
        if not self.num_experts:
            return self.num_params()
        h, l = self.hidden_size, self.num_layers
        dense_like = dataclasses.replace(self, num_experts=None)
        per_layer_moe = 3 * h * (self.moe_intermediate_size or 0)
        active = self.num_experts_per_tok * per_layer_moe
        if self.shared_expert_intermediate_size:
            active += 3 * h * self.shared_expert_intermediate_size + h
        active += h * self.num_experts  # router
        return dense_like.num_params() - l * 3 * h * self.intermediate_size + l * active
