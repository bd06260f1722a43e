"""Named architecture presets (public model-card dimensions).

Used by ``preset://<name>`` model specs: the worker/benchmark instantiates
the architecture with random weights — no checkpoint download, no egress —
which is how bench.py measures real-size throughput on hardware, and how
tests exercise realistic shapes. The reference's production models map to:
Tower-Plus-2B/9B → gemma2-2b/9b finetunes, Tower-Plus-72B → qwen2.5-72b
(SURVEY.md §6 production scale proof).
"""

from __future__ import annotations

from llmq_tpu.models.config import ModelConfig

_Q = dict(model_type="qwen2", attention_bias=True, rope_theta=1_000_000.0)
_G = dict(
    model_type="gemma2",
    activation="gelu_tanh",
    scale_embeddings=True,
    logit_softcap=30.0,
    attn_softcap=50.0,
    post_norms=True,
    sliding_window=4096,
    sliding_window_pattern=2,
    tie_word_embeddings=True,
)

PRESETS = {
    "tiny": ModelConfig.tiny(),
    "qwen2.5-0.5b": ModelConfig(
        vocab_size=151936, hidden_size=896, num_layers=24, num_heads=14,
        num_kv_heads=2, intermediate_size=4864, tie_word_embeddings=True,
        max_position_embeddings=32768, **_Q,
    ),
    "qwen2.5-1.5b": ModelConfig(
        vocab_size=151936, hidden_size=1536, num_layers=28, num_heads=12,
        num_kv_heads=2, intermediate_size=8960, tie_word_embeddings=True,
        max_position_embeddings=32768, **_Q,
    ),
    "qwen2.5-3b": ModelConfig(
        vocab_size=151936, hidden_size=2048, num_layers=36, num_heads=16,
        num_kv_heads=2, intermediate_size=11008, tie_word_embeddings=True,
        max_position_embeddings=32768, **_Q,
    ),
    "qwen2.5-7b": ModelConfig(
        vocab_size=152064, hidden_size=3584, num_layers=28, num_heads=28,
        num_kv_heads=4, intermediate_size=18944,
        max_position_embeddings=32768, **_Q,
    ),
    "qwen2.5-72b": ModelConfig(
        vocab_size=152064, hidden_size=8192, num_layers=80, num_heads=64,
        num_kv_heads=8, intermediate_size=29568,
        max_position_embeddings=32768, **_Q,
    ),
    "llama3.1-8b": ModelConfig(
        vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, rope_theta=500000.0,
        rope_scaling={
            "rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
            "high_freq_factor": 4.0, "original_max_position_embeddings": 8192,
        },
        model_type="llama",
    ),
    "gemma2-2b": ModelConfig(
        vocab_size=256000, hidden_size=2304, num_layers=26, num_heads=8,
        num_kv_heads=4, head_dim=256, intermediate_size=9216,
        query_pre_attn_scalar=256, max_position_embeddings=8192, **_G,
    ),
    "gemma2-9b": ModelConfig(
        vocab_size=256000, hidden_size=3584, num_layers=42, num_heads=16,
        num_kv_heads=8, head_dim=256, intermediate_size=14336,
        query_pre_attn_scalar=256, max_position_embeddings=8192, **_G,
    ),
    # The reference's headline 9B operating point (Tower-Plus-9B ×8 workers,
    # utils/run_llmq_benchmark.slurm:5-8) — architecture of its base model.
    "tower-plus-9b": ModelConfig(
        vocab_size=256000, hidden_size=3584, num_layers=42, num_heads=16,
        num_kv_heads=8, head_dim=256, intermediate_size=14336,
        query_pre_attn_scalar=256, max_position_embeddings=8192, **_G,
    ),
    # Sparse MoE (Qwen1.5-MoE-A2.7B card): 60 experts, 4 routed + 1
    # shared per token — exercises the grouped-matmul expert path at a
    # realistic expert count.
    "qwen1.5-moe-a2.7b": ModelConfig(
        vocab_size=151936, hidden_size=2048, num_layers=24, num_heads=16,
        num_kv_heads=16, intermediate_size=5632, model_type="qwen2_moe",
        attention_bias=True, rope_theta=1_000_000.0,
        max_position_embeddings=8192, num_experts=60, num_experts_per_tok=4,
        moe_intermediate_size=1408, shared_expert_intermediate_size=5632,
        norm_topk_prob=False, tie_word_embeddings=False,
    ),
}

# Ling-3.0-flash (inclusionAI, ``bailing_hybrid``), the published config's
# keys that shape the model: KDA layers with every sixth an MLA layer, two
# dense MLPs, then 512 sigmoid-routed experts of which 8 a token, one
# shared expert.
_LING_3_FLASH = dict(
    model_type="bailing_hybrid", vocab_size=157184, hidden_size=2560,
    num_hidden_layers=42, num_attention_heads=32, num_key_value_heads=32,
    head_dim=128, intermediate_size=6144, max_position_embeddings=262144,
    rope_theta=6000000, rope_interleave=True, rms_norm_eps=1e-06,
    tie_word_embeddings=False, layer_group_size=6, first_k_dense_replace=2,
    short_conv_kernel_size=4, kda_lower_bound=-5, kda_safe_gate=True,
    no_kda_lora=True, linear_silu=True, use_qk_norm=True, group_norm_size=1,
    q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
    gated_attention_proj_granularity_type="head_wise",
    num_experts=512, num_experts_per_tok=8, moe_intermediate_size=768,
    num_shared_experts=1, moe_shared_expert_intermediate_size=768,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=8, topk_group=4,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    moe_router_enable_expert_bias=True,
    expert_swiglu_limit_list=[0] * 35 + [4] * 7,
    share_expert_swiglu_limit_list=[0] * 34 + [5] * 6 + [7] * 2,
)
# One chip's share of four that share each layer: published layer 0 (the
# two leading dense layers count once) and layers 6-11 (one whole period:
# five KDA, one MLA, all with experts); experts 0-127 of 512 (two whole
# groups; the router keeps its 512 outputs and 8 a token); rows 0-39,295 of
# the vocabulary. Every width is the published one.
PRESETS["ling-3.0-flash-ep4"] = ModelConfig.from_hf_config(
    dict(
        _LING_3_FLASH, kept_layers=[0, 6, 7, 8, 9, 10, 11],
        experts_held=[0, 128], vocab_size=157184 // 4,
    )
)

# openPangu-Ultra-MoE-718B (``pangu_ultra_moe``), the published config:
# latent attention with a query LoRA on all 61 layers, sandwich norms,
# three dense MLPs, then 256 sigmoid-routed experts (one group, no
# selection bias) of which 8 a token, one shared expert.
_OPENPANGU_ULTRA_MOE = dict(
    model_type="pangu_ultra_moe", vocab_size=153600, hidden_size=7680,
    num_hidden_layers=61, num_attention_heads=128, num_key_value_heads=128,
    intermediate_size=18432, max_position_embeddings=131072,
    rope_theta=25600000, rms_norm_eps=1e-05, tie_word_embeddings=False,
    attention_bias=False, hidden_act="silu", sandwich_norm=True,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, first_k_dense_replace=3,
    n_routed_experts=256, n_shared_experts=1, num_experts_per_tok=8,
    moe_intermediate_size=2048, norm_topk_prob=True,
    routed_scaling_factor=2.5, num_nextn_predict_layers=1,
)
# One chip's share of 16 that share each layer: one of the three leading
# dense layers and four expert layers (a pipeline stage's worth); experts
# 0-15 of 256 (the router keeps its 256 outputs and 8 a token); rows
# 0-19,199 of the vocabulary (split over 8). Every width is the published
# one.
PRESETS["openpangu-ultra-moe-718b-ep16"] = ModelConfig.from_hf_config(
    dict(
        _OPENPANGU_ULTRA_MOE, num_hidden_layers=5, first_k_dense_replace=1,
        experts_held=[0, 16], vocab_size=153600 // 8,
    )
)
# The same block at widths a CPU test serves (byte tokenizer: ids < 304).
PRESETS["openpangu-ultra-moe-tiny"] = ModelConfig.from_hf_config(
    dict(
        _OPENPANGU_ULTRA_MOE, vocab_size=304, hidden_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        intermediate_size=128, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        first_k_dense_replace=1, n_routed_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, experts_held=[4, 8],
    )
)

# LFM2-24B-A2B (LiquidAI, ``lfm2_moe``), the published config: gated
# short-convolution layers with every fourth (from layer 2) a GQA layer of
# 32 / 8 heads of 64, two dense MLPs, then 64 sigmoid-routed experts
# (a selection bias, no group, no shared expert) of which 4 a token.
_LFM2_24B_A2B = dict(
    model_type="lfm2_moe", vocab_size=65536, hidden_size=2048,
    num_hidden_layers=40, num_attention_heads=32, num_key_value_heads=8,
    intermediate_size=11776, max_position_embeddings=128000,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    norm_eps=1e-05, conv_L_cache=3, conv_bias=False,
    layer_types=["conv" if i < 2 or i % 4 != 2 else "full_attention" for i in range(40)],
    num_dense_layers=2, num_experts=64, num_experts_per_tok=4,
    moe_intermediate_size=1536, norm_topk_prob=True, routed_scaling_factor=1,
    use_expert_bias=True,
)
# One pipeline stage of five, every layer whole on its chip and every
# expert held: published layer 0 (a conv layer with the dense MLP; the two
# leading dense layers count once) and layers 2-9 (two whole periods:
# attention, three conv, all with experts). Every width is the published
# one; the embedding is tied and whole.
PRESETS["lfm2-24b-a2b-pp5"] = ModelConfig.from_hf_config(
    dict(
        _LFM2_24B_A2B, num_hidden_layers=9, num_dense_layers=1,
        kept_layers=[0, 2, 3, 4, 5, 6, 7, 8, 9],
    )
)
# The same structure at widths a CPU test serves (byte tokenizer: ids <
# 304): a dense conv layer, both periods, 4 of 8 experts a token.
PRESETS["lfm2-moe-tiny"] = ModelConfig.from_hf_config(
    dict(
        _LFM2_24B_A2B, vocab_size=304, hidden_size=64, num_hidden_layers=9,
        num_dense_layers=1, kept_layers=[0, 2, 3, 4, 5, 6, 7, 8, 9],
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        num_experts=8, moe_intermediate_size=32,
    )
)

# EvaByte 6.5B (``evabyte``), the published config: 32 layers of multi-head
# EVA attention (32 heads of 128; exact softmax inside the query's own
# 2,048-byte window, one learned summary a 16-byte chunk for every earlier
# window) and a SwiGLU MLP, a vocabulary of 320 byte ids, 8 prediction
# heads.
_EVABYTE = dict(
    model_type="evabyte", vocab_size=320, hidden_size=4096,
    num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
    intermediate_size=11008, max_position_embeddings=32768, rope_theta=100000,
    rms_norm_eps=1e-05, tie_word_embeddings=False, attention_bias=False,
    hidden_act="silu", attention_class="eva", window_size=2048, chunk_size=16,
    norm_add_unit_offset=True, fp32_logits=True, fp32_skip_add=True,
    num_pred_heads=8,
)
# One pipeline stage of four, every layer whole on its chip with all 32
# heads: the embedding, layers 0-7 and prediction head 0, so that the
# stage yields bytes. Every width, the window and the chunk are the
# published ones.
PRESETS["evabyte-6.5b-pp4"] = ModelConfig.from_hf_config(
    dict(_EVABYTE, num_hidden_layers=8, num_pred_heads=1)
)
# The same block at widths a CPU test serves (byte tokenizer: ids < 304):
# windows of 32 and chunks of 4, so that with pages of 8 or 16 windows,
# chunks and pages all cross inside a short test.
PRESETS["evabyte-tiny"] = ModelConfig.from_hf_config(
    dict(
        _EVABYTE, vocab_size=304, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
        window_size=32, chunk_size=4, num_pred_heads=1,
    )
)


def get_preset(name: str) -> ModelConfig:
    try:
        return PRESETS[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown preset {name!r}; available: {sorted(PRESETS)}"
        ) from None
