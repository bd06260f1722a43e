"""Models with a declared layer pattern (``models/hybrid.py``): KDA layers
with a per-sequence state beside MLA layers over a paged latent cache
(``bailing_hybrid``), or MLA layers alone with a query LoRA and sandwich
norms (``pangu_ultra_moe``), routed experts held by share. CPU, tiny
widths, seeded weights.

The comparison is with the benchmark's plain references
(``benchmark/architectures/bailing_hybrid.py``, ``pangu_ultra_moe.py``:
float32, token-by-token recurrence, expanded MLA, nothing imported from
the program): prefill and then decoding through the caches must give the
logits of the reference's full forward pass.
"""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architectures, weights
from llmq_tpu.models import cache, hybrid
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.presets import _LING_3_FLASH, _OPENPANGU_ULTRA_MOE, get_preset
from llmq_tpu.models.transformer import build_model, make_kv_pages
from llmq_tpu.ops import delta_rule

# The published config's keys at a tiny size: 12 layers in periods of 3
# (KDA, KDA, MLA), two leading dense MLPs, 16 experts in 4 groups.
HF = dict(
    model_type="bailing_hybrid", vocab_size=304, hidden_size=64,
    num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=128, rope_theta=10000.0, rms_norm_eps=1e-6,
    layer_group_size=3, first_k_dense_replace=2, short_conv_kernel_size=4,
    kda_lower_bound=-5, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_shared_experts=1, n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, tie_word_embeddings=False,
)
KEPT = [0, 3, 4, 5]  # dense KDA, then one whole period


def configs(first=4, held=8, kept=KEPT):
    """(the program's ModelConfig, the benchmark file's keys) of one share."""
    hf = dict(HF, kept_layers=kept, experts_held=[first, held])
    file_cfg = dict(
        hf, architecture="bailing_hybrid", num_experts=held,
        num_experts_published=HF["num_experts"], num_hidden_layers=len(kept),
    )
    return ModelConfig.from_hf_config(hf), file_cfg


def seeded(file_cfg, seed=7):
    """Weights by the benchmark's rules: norms and biases off 1 and 0."""
    arch = architectures.of(file_cfg)
    return arch, weights.make_weights(arch, file_cfg, seed, None, dtype=jnp.float32)


MC, FILE_CFG = configs()
ARCH, PARAMS = seeded(FILE_CFG)
MODEL = build_model(MC)
PAGE, PAGES, PPS = 8, 24, 6

# openPangu-Ultra-MoE's published keys at a tiny size: latent attention on
# every layer, one leading dense MLP, 16 experts in one group, no bias.
PANGU_HF = dict(
    model_type="pangu_ultra_moe", vocab_size=304, hidden_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, rope_theta=10000.0, rms_norm_eps=1e-5,
    q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, first_k_dense_replace=1, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
    norm_topk_prob=True, routed_scaling_factor=2.5, sandwich_norm=True,
    tie_word_embeddings=False,
)


def pangu_configs(first=4, held=8, **changed):
    """(the program's ModelConfig, the benchmark file's keys) of one share."""
    hf = dict(PANGU_HF, experts_held=[first, held], **changed)
    file_cfg = dict(
        hf, architecture="pangu_ultra_moe", n_routed_experts=held,
        n_routed_experts_published=PANGU_HF["n_routed_experts"],
    )
    return ModelConfig.from_hf_config(hf), file_cfg


# LFM2-24B-A2B's published keys at a tiny size: a dense conv layer, then
# both periods (attention, three conv) with 8 experts of which 4 a token,
# chosen by score + bias (seeded biases are 0.1 n: not zero).
LFM2_HF = dict(
    model_type="lfm2_moe", vocab_size=304, hidden_size=64, num_hidden_layers=9,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    norm_eps=1e-5, conv_L_cache=3, conv_bias=False,
    layer_types=["conv" if i < 2 or i % 4 != 2 else "full_attention" for i in range(40)],
    kept_layers=[0, 2, 3, 4, 5, 6, 7, 8, 9], num_dense_layers=1, num_experts=8,
    num_experts_per_tok=4, moe_intermediate_size=32, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True,
)
LFM2_GROUPS = [
    ("conv", "dense", 1), ("gqa", "moe", 1), ("conv", "moe", 3), ("gqa", "moe", 1),
    ("conv", "moe", 3),
]


def lfm2_configs(**changed):
    hf = dict(LFM2_HF, **changed)
    return ModelConfig.from_hf_config(hf), dict(hf, architecture="lfm2_moe")


class Family:
    """A configuration with its reference, seeded weights and model."""

    def __init__(self, mc, file_cfg, groups):
        self.mc, self.file_cfg, self.groups = mc, file_cfg, groups
        self.arch, self.params = seeded(file_cfg)
        self.model = build_model(mc)


_MADE = {}
_FAMILIES = {
    "ling": lambda: (MC, FILE_CFG, [("kda", "dense", 1), ("kda", "moe", 2), ("mla", "moe", 1)]),
    "pangu": lambda: (*pangu_configs(), [("mla", "dense", 1), ("mla", "moe", 3)]),
    "pangu_no_query_lora": lambda: (
        *pangu_configs(q_lora_rank=None), [("mla", "dense", 1), ("mla", "moe", 3)]),
    "pangu_no_sandwich_norms": lambda: (
        *pangu_configs(sandwich_norm=False), [("mla", "dense", 1), ("mla", "moe", 3)]),
    "pangu_lead_layer_alone": lambda: (
        *pangu_configs(num_hidden_layers=1), [("mla", "dense", 1)]),
    "pangu_expert_layers_alone": lambda: (
        *pangu_configs(num_hidden_layers=2, first_k_dense_replace=0), [("mla", "moe", 2)]),
    "lfm2": lambda: (*lfm2_configs(), LFM2_GROUPS),
    "lfm2_no_selection_bias": lambda: (*lfm2_configs(use_expert_bias=False), LFM2_GROUPS),
    "lfm2_lead_layer_alone": lambda: (
        *lfm2_configs(num_hidden_layers=1, kept_layers=[0]), [("conv", "dense", 1)]),
    "lfm2_one_period_alone": lambda: (
        *lfm2_configs(num_hidden_layers=4, kept_layers=[2, 3, 4, 5], num_dense_layers=0),
        [("gqa", "moe", 1), ("conv", "moe", 3)]),
}


def family(name) -> Family:
    if name not in _MADE:
        _MADE[name] = Family(*_FAMILIES[name]())
    return _MADE[name]


@pytest.mark.parametrize("name", _FAMILIES)
def test_tree_is_the_one_the_benchmark_describes(name):
    f = family(name)
    ours = jax.tree.map(
        tuple, hybrid.param_shapes(f.mc), is_leaf=lambda x: isinstance(x, tuple)
    )
    assert ours == f.arch.tree_shapes(f.file_cfg)
    assert [(g.attn, g.mlp, g.count) for g in hybrid.layer_groups(f.mc)] == f.groups


@pytest.mark.parametrize("name, rows", [
    ("ling", 1), ("ling", 4), ("pangu", 1), ("pangu", 4), ("pangu_no_query_lora", 4),
    ("pangu_no_sandwich_norms", 4), ("pangu_lead_layer_alone", 4),
    ("pangu_expert_layers_alone", 4),
    ("lfm2", 1), ("lfm2", 4), ("lfm2_no_selection_bias", 4), ("lfm2_lead_layer_alone", 4),
    ("lfm2_one_period_alone", 4),
])
def test_prefill_then_decode_matches_the_reference(name, rows):
    """Batches of 1 and 4 rows with padding: rows of different lengths in
    one bucket, a padded row, then decode steps with an inactive slot,
    against the reference's full forward pass. For the lead layer and the
    expert layers of ``pangu_ultra_moe``, with and without the query LoRA
    and the sandwich norms."""
    f = family(name)
    MC, MODEL, PARAMS, ARCH, FILE_CFG = f.mc, f.model, f.params, f.arch, f.file_cfg
    rng = np.random.default_rng(rows)
    lengths = [19, 7, 0, 12][:rows]
    steps = 6
    seqs = [list(rng.integers(1, 300, size=n + steps)) for n in lengths]
    k, v = make_kv_pages(MC, PAGES, PAGE, jnp.float32)
    bt = np.zeros((rows, PPS), np.int32)
    free = iter(range(1, PAGES))
    for r, n in enumerate(lengths):
        if n:
            bt[r, : -(-(n + steps) // PAGE)] = [
                next(free) for _ in range(-(-(n + steps) // PAGE))
            ]
    tokens = np.zeros((rows, 32), np.int32)
    for r, n in enumerate(lengths):
        tokens[r, :n] = seqs[r][:n]
    logits, k, v = jax.jit(MODEL.prefill)(
        PARAMS, tokens, np.asarray(lengths, np.int32), k, v, bt
    )
    got = [[np.asarray(logits[r])] for r in range(rows)]
    decode = jax.jit(MODEL.decode)
    active = np.asarray([n > 0 for n in lengths])
    for j in range(steps - 1):
        toks = np.asarray([s[n + j] if n else 0 for s, n in zip(seqs, lengths)], np.int32)
        ctx = np.asarray([n + j for n in lengths], np.int32)
        logits, k, v = decode(PARAMS, toks, ctx, k, v, bt, active)
        for r in range(rows):
            got[r].append(np.asarray(logits[r]))
    for r, n in enumerate(lengths):
        if not n:
            continue
        ref = np.asarray(ARCH.forward_logits(
            PARAMS, FILE_CFG, seqs[r][: n + steps - 1], list(range(n - 1, n + steps - 1))
        ))
        np.testing.assert_allclose(np.stack(got[r]), ref, atol=2e-4, rtol=0)


def test_long_prompt_takes_the_grouped_experts_and_blocked_attention():
    """A bucket of 640 tokens: more rows than ``DENSE_EXPERT_ROWS`` (the
    experts go through ``ragged_dot`` on the whole stack, inside the layer
    scan) and more than one block of query rows; then decode over five
    pages of 128."""
    rng = np.random.default_rng(5)
    n, steps = 600, 3
    ids = list(rng.integers(1, 300, size=n + steps))
    k, v = make_kv_pages(MC, 8, 128, jnp.float32)
    bt = np.asarray([[1, 2, 3, 4, 5, 0]], np.int32)
    tokens = np.zeros((1, 640), np.int32)
    tokens[0, :n] = ids[:n]
    logits, k, v = jax.jit(MODEL.prefill)(PARAMS, tokens, np.asarray([n], np.int32), k, v, bt)
    got = [np.asarray(logits[0])]
    for j in range(steps - 1):
        logits, k, v = jax.jit(MODEL.decode)(
            PARAMS, np.asarray([ids[n + j]], np.int32), np.asarray([n + j], np.int32),
            k, v, bt, np.asarray([True]),
        )
        got.append(np.asarray(logits[0]))
    ref = ARCH.forward_logits(PARAMS, FILE_CFG, ids[: n + steps - 1], list(range(n - 1, n + steps - 1)))
    np.testing.assert_allclose(np.stack(got), np.asarray(ref), atol=2e-4, rtol=0)


def test_state_rows_given_by_the_caller_are_the_rows_used():
    """The engine passes slot + 1; the default is the first page."""
    k, v = make_kv_pages(MC, PAGES, PAGE, jnp.float32, state_rows=3)
    assert v["S"].shape[1] == 3 and k.shape[1] == PAGES
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :9] = np.arange(1, 10)
    bt = np.zeros((1, PPS), np.int32)
    bt[0, :2] = [5, 6]
    _, _, v = jax.jit(MODEL.prefill)(
        PARAMS, tokens, np.asarray([9], np.int32), k, v, bt, np.asarray([2], np.int32)
    )
    S = np.asarray(v["S"])
    assert np.abs(S[:, 2]).max() > 0 and np.abs(S[:, :2]).max() == 0


def test_a_kda_layers_output_is_a_value_of_its_own_in_both_decode_forms():
    """``_kda_decode`` keeps ``o`` behind an ``optimization_barrier``
    whether the rows are a run (the engine's step; on the chip the
    one-pass kernel) or an array (the benchmark's direct path, the XLA
    form): with it the two give the same bits on the chip, without it an
    expert flips within a layer or two and the benchmark's
    ``served_regret`` reads 0.8 (PERF.md section 6, PR 47). One a group of
    KDA layers: the lead layer and the scan's body. Prefill has none."""
    k, v = make_kv_pages(MC, PAGES, PAGE, jnp.float32, state_rows=3)
    zeros = np.zeros((2,), np.int32)
    args = (PARAMS, zeros, zeros, k, v, np.zeros((2, PPS), np.int32), np.ones((2,), bool))
    for rows in (1, np.asarray([1, 2], np.int32)):
        text = str(jax.make_jaxpr(lambda *a, rows=rows: MODEL.decode(*a, rows))(*args))
        assert text.count("optimization_barrier") == 2
    prefill = jax.make_jaxpr(MODEL.prefill)(
        PARAMS, np.zeros((1, 16), np.int32), np.asarray([9], np.int32), k, v,
        np.zeros((1, PPS), np.int32), np.asarray([2], np.int32),
    )
    assert "optimization_barrier" not in str(prefill)


def test_scanned_kda_prefill_is_the_token_recurrence():
    rng = np.random.default_rng(0)
    B, T, n, d = 2, 12, 3, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, n, d)), jnp.float32) for _ in range(3))
    alpha = jnp.asarray(rng.uniform(0.1, 1.0, size=(B, T, n, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(B, T, n)), jnp.float32)
    lengths = np.asarray([12, 5])
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    S, o = delta_rule.kda_scan(q, k, v, alpha, beta, valid)
    for b in range(B):
        state = np.zeros((n, d, d))
        for t in range(lengths[b]):
            decayed = np.asarray(alpha[b, t])[:, :, None] * state
            delta = np.asarray(v[b, t]) - np.einsum("nkv,nk->nv", decayed, k[b, t])
            state = decayed + np.asarray(beta[b, t])[:, None, None] * np.einsum(
                "nk,nv->nkv", k[b, t], delta
            )
            np.testing.assert_allclose(
                o[b, t], np.einsum("nkv,nk->nv", state, q[b, t]), atol=1e-4
            )
        np.testing.assert_allclose(S[b], state, atol=1e-4)  # padding: no-ops
    # the convolution: a token at a time from the tails equals the bucket
    u = jnp.asarray(rng.normal(size=(B, T, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    out, tail = delta_rule.causal_conv(u, w, jnp.asarray(lengths))
    np.testing.assert_allclose(tail[1], u[1, 2:5])
    step_tail = jnp.zeros((B, 3, 6))
    for t in range(T):
        got, step_tail = delta_rule.conv_step(step_tail, u[:, t], w)
        np.testing.assert_allclose(got, out[:, t], atol=1e-5)


@pytest.mark.parametrize("name, stack", [("ling", "stack2"), ("pangu", "stack1")])
def test_absorbed_mla_is_expanded_mla(name, stack):
    """Decode (query absorbed into the latent, attention over the cached
    rows) gives what prefill (keys and values raised a head) gives, with
    a head-wise gate and with a query LoRA."""
    f = family(name)
    MC, MODEL = f.mc, f.model
    lp = {name: w[0] for name, w in f.params[stack].items()}
    rng = np.random.default_rng(1)
    T = 16
    x = jnp.asarray(rng.normal(size=(1, T, MC.hidden_size)), jnp.float32)
    latent, _ = make_kv_pages(MC, PAGES, PAGE, jnp.float32)
    bt = np.asarray([[4, 9, 0, 0, 0, 0]], np.int32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    lengths = jnp.asarray([T], jnp.int32)
    expanded, latent = MODEL._mla_prefill(lp, x, positions, lengths, latent, bt, 0)
    for t in (0, 7, T - 1):
        absorbed, _ = MODEL._mla_decode(
            lp, x[:, t], jnp.asarray([t], jnp.int32), latent, bt,
            jnp.asarray([t + 1], jnp.int32), 0,
        )
        np.testing.assert_allclose(absorbed[0], expanded[0, t], atol=2e-5)


@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "grouped"])
def test_four_shares_add_up_to_the_uncut_layer(dense_rows, monkeypatch):
    """Both forms of the held experts' sum (every expert on every row;
    sorted assignments and ``ragged_dot``). The parts that the four shares of an expert layer give, with the
    shared expert (which every chip computes alike) counted once, add up
    to what the uncut reference gives for the whole layer."""
    monkeypatch.setattr(hybrid, "DENSE_EXPERT_ROWS", dense_rows)
    whole_mc, whole_cfg = configs(first=0, held=16)
    _, whole = seeded(whole_cfg, seed=11)
    lp = {name: w[1] for name, w in whole["stack1"].items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(9, 64)), jnp.float32)
    ref = architectures.of(whole_cfg)
    z = tuple(sorted(ref._sizes(whole_cfg).items()))
    route = (4, 2, 4, 2.5, True, 0)
    shared, w, _ = ref._shared_and_route(x, lp, z=z, route_cfg=route, control=None)
    uncut = shared + ref._expert_block(
        x, w, lp["expert_gate_proj"], lp["expert_up_proj"], lp["expert_down_proj"],
        control=None,
    )
    total, assignments = jnp.zeros_like(shared), 0
    for first in (0, 4, 8, 12):
        share = {
            name: leaf[first : first + 4] if name.startswith("expert_") else leaf
            for name, leaf in lp.items()
        }
        mc = dataclasses.replace(whole_mc, experts_held=(first, 4))
        part, counts = hybrid.moe_held(x, share, mc)
        total = total + (part - shared)
        assignments += int(counts[0])
    assert assignments == 9 * 4  # every assignment lands on exactly one share
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5)


@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "grouped"])
def test_sixteen_shares_of_one_group_add_up_to_the_uncut_layer(dense_rows, monkeypatch):
    """The same for ``pangu_ultra_moe``'s router (one group, no selection
    bias): sixteen shares of one expert each, the shared expert counted
    once, against the uncut reference's layer."""
    monkeypatch.setattr(hybrid, "DENSE_EXPERT_ROWS", dense_rows)
    whole_mc, whole_cfg = pangu_configs(first=0, held=16)
    ref, whole = seeded(whole_cfg, seed=11)
    lp = {name: w[1] for name, w in whole["stack1"].items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(9, 64)), jnp.float32)
    z = tuple(sorted(ref._sizes(whole_cfg).items()))
    shared, w, _ = ref._shared_and_route(
        x, lp, z=z, route_cfg=(4, 2.5, True, 0), control=None
    )
    uncut = shared + ref._expert_block(
        x, w, lp["expert_gate_proj"], lp["expert_up_proj"], lp["expert_down_proj"],
        control=None,
    )
    total, assignments, hit = jnp.zeros_like(shared), 0, 0
    for first in range(16):
        share = {
            name: leaf[first : first + 1] if name.startswith("expert_") else leaf
            for name, leaf in lp.items()
        }
        mc = dataclasses.replace(whole_mc, experts_held=(first, 1))
        part, counts = hybrid.moe_held(x, share, mc)
        total = total + (part - shared)
        assignments += int(counts[0])
        hit += int(counts[1])
    assert assignments == 9 * 4 and 4 <= hit <= 16
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5)


def test_pangu_from_hf_config_on_the_published_keys():
    """The cut the benchmark serves, and the published counts from the
    shapes: attention 196.6 M, an expert 47.2 M, the dense lead layer
    621.3 M, the cut 4,919 M (9.84 GB in bf16)."""
    cut = get_preset("openpangu-ultra-moe-718b-ep16")
    assert cut.layer_pattern == (("mla", "dense"),) + (("mla", "moe"),) * 4
    assert (cut.hidden_size, cut.num_heads, cut.intermediate_size) == (7680, 128, 18432)
    assert (cut.q_lora_rank, cut.kv_lora_rank, cut.qk_nope_head_dim,
            cut.qk_rope_head_dim, cut.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (cut.num_experts, cut.experts_held, cut.num_experts_per_tok) == (256, (0, 16), 8)
    assert (cut.moe_intermediate_size, cut.shared_expert_intermediate_size) == (2048, 2048)
    assert (cut.n_group, cut.router_bias, cut.routed_scaling_factor) == (1, False, 2.5)
    assert cut.post_norms and not cut.mla_head_gate and cut.rms_norm_eps == 1e-5
    assert cut.vocab_size == 153600 // 8 and cut.rope_theta == 25.6e6
    assert hybrid.count_layers(cut, "kda") == 0

    def millions(shapes, keep=lambda name: True):
        flat = jax.tree.flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
        return sum(int(np.prod(s)) for path, s in flat if keep(path[-1].key)) / 1e6

    shapes = hybrid.param_shapes(cut)
    lead = {k: v[1:] for k, v in shapes["stack0"].items()}
    layer = {k: v[1:] for k, v in shapes["stack1"].items()}
    attention = ("mla_qa_proj", "mla_qb_proj", "mla_kva_proj", "mla_kvb_proj", "o_proj")
    assert round(millions(layer, lambda n: n in attention), 1) == 196.6
    assert round(millions(layer, lambda n: n.startswith("expert_")) / 16, 1) == 47.2
    assert round(millions(layer, lambda n: n.startswith("shared_")), 1) == 47.2
    assert round(millions(lead), 1) == 621.3
    assert round(millions(shapes)) == 4919
    whole = ModelConfig.from_hf_config(_OPENPANGU_ULTRA_MOE)
    assert len(whole.layer_pattern) == 61 and whole.experts_held_ == (0, 256)
    assert [m for _, m in whole.layer_pattern[:4]] == ["dense"] * 3 + ["moe"]
    for key, value in (("n_group", 8), ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn"}), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):  # refused by name
            ModelConfig.from_hf_config(dict(_OPENPANGU_ULTRA_MOE, **{key: value}))


def test_from_hf_config_on_the_published_keys():
    cut = get_preset("ling-3.0-flash-ep4")
    assert cut.layer_pattern == (("kda", "dense"),) + (("kda", "moe"),) * 5 + (("mla", "moe"),)
    assert (cut.hidden_size, cut.num_heads, cut.head_dim_, cut.intermediate_size) == (
        2560, 32, 128, 6144)
    assert (cut.kv_lora_rank, cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim) == (
        512, 128, 64, 128)
    assert (cut.num_experts, cut.experts_held, cut.num_experts_per_tok) == (512, (0, 128), 8)
    assert (cut.moe_intermediate_size, cut.shared_expert_intermediate_size) == (768, 768)
    assert (cut.n_group, cut.topk_group, cut.routed_scaling_factor) == (8, 4, 2.5)
    assert cut.vocab_size == 157184 // 4 and not cut.tie_word_embeddings
    n = sum(np.prod(s) for s in jax.tree.leaves(
        hybrid.param_shapes(cut), is_leaf=lambda x: isinstance(x, tuple)))
    assert round(n / 1e6) == 5232  # 10.46 GB in bf16
    first_34 = ModelConfig.from_hf_config(dict(_LING_3_FLASH, kept_layers=range(34)))
    assert [a for a, _ in first_34.layer_pattern].count("mla") == 5
    assert [m for _, m in first_34.layer_pattern[:3]] == ["dense", "dense", "moe"]
    with pytest.raises(ValueError, match="swiglu_limit_list"):  # layers 34-41 clamp
        ModelConfig.from_hf_config(_LING_3_FLASH)
    with pytest.raises(ValueError, match="q_lora_rank"):
        ModelConfig.from_hf_config(dict(_LING_3_FLASH, kept_layers=[0], q_lora_rank=1536))


def test_big_buckets_take_rows_and_blocks_one_at_a_time(monkeypatch):
    """Above ``KDA_PREFILL_TOKENS`` a KDA layer takes its rows one at a
    time and above ``MOE_BLOCK_ROWS`` an expert layer a block of rows at a
    time (the 4 x 8,192 bucket's float32 in flight): same logits, same
    state, same latent rows."""
    rng = np.random.default_rng(3)
    lengths = np.asarray([30, 9, 0, 17], np.int32)
    tokens = np.zeros((4, 32), np.int32)
    for r, n in enumerate(lengths):
        tokens[r, :n] = rng.integers(1, 300, size=n)
    bt = np.zeros((4, PPS), np.int32)
    bt[0, :4], bt[1, :2], bt[3, :3] = [1, 2, 3, 4], [5, 6], [7, 8, 9]

    def run():
        k, v = make_kv_pages(MC, PAGES, PAGE, jnp.float32)
        return jax.jit(MODEL.prefill)(PARAMS, tokens, lengths, k, v, bt)

    plain = run()
    monkeypatch.setattr(hybrid, "KDA_PREFILL_TOKENS", 16)
    monkeypatch.setattr(hybrid, "MOE_BLOCK_ROWS", 16)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(run())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=0)


def test_a_big_bucket_at_many_heads_expands_its_rows_one_at_a_time(monkeypatch):
    """Above ``MLA_PREFILL_HEAD_TOKENS`` (tokens x heads) an MLA layer
    raises keys and values for one row of the batch at a time (128 heads:
    the 4 x 4,096 bucket), and an expert layer's block of rows halves
    with the hidden size: same logits, same latent rows."""
    f = family("pangu")
    rng = np.random.default_rng(3)
    lengths = np.asarray([30, 9, 0, 17], np.int32)
    tokens = np.zeros((4, 32), np.int32)
    for r, n in enumerate(lengths):
        tokens[r, :n] = rng.integers(1, 300, size=n)
    bt = np.zeros((4, PPS), np.int32)
    bt[0, :4], bt[1, :2], bt[3, :3] = [1, 2, 3, 4], [5, 6], [7, 8, 9]

    def run():
        k, v = make_kv_pages(f.mc, PAGES, PAGE, jnp.float32)
        return jax.jit(f.model.prefill)(f.params, tokens, lengths, k, v, bt)

    plain = run()
    monkeypatch.setattr(hybrid, "MLA_PREFILL_HEAD_TOKENS", 16)
    monkeypatch.setattr(hybrid, "MOE_BLOCK_ROWS", 16)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(run())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=0)


def _reference_err(control, n=40, k=8, name="ling", measure=None):
    from benchmark.correct import logit_err

    f = family(name)
    ids = list(np.random.default_rng(9).integers(1, 300, size=n + k - 1))
    positions = list(range(n - 1, n + k - 1))
    ref = np.asarray(f.arch.forward_logits(f.params, f.file_cfg, ids, positions))
    ctrl = np.asarray(f.arch.forward_logits(f.params, f.file_cfg, ids, positions, control))
    return (measure or logit_err)(ctrl, ref)


PANGU_FAULTS = [
    "q_norm_off", "post_attn_norm_off", "post_mlp_norm_off", "scale_off",
    "topk_norm_off", "moe_drop", "moe_drop_first",
]


LFM2_FAULTS = ["qk_norm_off", "bias_off", "bias_weighs", "topk_norm_off", "tail_off", "moe_drop"]


@pytest.mark.parametrize("name, fault", [
    ("ling", "kda_reset"), ("ling", "conv_tail"), ("ling", "moe_drop"), ("ling", "moe_drop_first"),
    *(("pangu", fault) for fault in PANGU_FAULTS),
    *(("lfm2", fault) for fault in LFM2_FAULTS),
])
def test_a_planted_fault_of_the_reference_is_far_from_it(name, fault):
    """The controls that stand for a wrong cache manager or step program
    (a state not carried, tails not carried, a layer's experts left out;
    the query norm, an output norm, the routed scale or the top-8
    normalisation left out) are in ``CONTROLS`` and move the logits by far
    more than rounding: a program that left one out would be hundreds of
    times outside the 2e-4 that
    ``test_prefill_then_decode_matches_the_reference`` holds it to."""
    assert fault in family(name).arch.CONTROLS
    assert _reference_err(fault, name=name) > 0.05
    worst = _reference_err(fault, name=name, measure=lambda a, b: np.abs(a - b).max())
    assert worst > 100 * 2e-4


@pytest.mark.parametrize("name", ["ling", "pangu", "lfm2"])
def test_the_diagnoses_round_and_force_the_float32_choice_of_experts(name):
    rounded = _reference_err("bf16act", name=name)
    routed = _reference_err("bf16act_routed", name=name)
    assert 0 < routed < rounded < 0.2
    arch = family(name).arch
    assert not set(arch.DIAGNOSES) & set(arch.CONTROLS)


def test_the_reference_takes_its_heads_a_group_at_a_time(monkeypatch):
    """``pangu_ultra_moe.py`` expands keys and values for a group of heads
    at a time (128 heads x 3,080 positions do not fit otherwise): two
    groups of two heads give what one group of four gives, int8 control
    included (its scales are per output channel of the whole matrix)."""
    f = family("pangu")
    ids = list(np.random.default_rng(4).integers(1, 300, size=30))
    positions = list(range(20, 30))

    def logits(control):
        jax.clear_caches()
        return np.asarray(f.arch.forward_logits(f.params, f.file_cfg, ids, positions, control))

    whole = {c: logits(c) for c in (None, "int8w")}
    monkeypatch.setattr(f.arch, "_HEAD_GROUP", 2)
    for control, want in whole.items():
        np.testing.assert_allclose(logits(control), want, atol=2e-5, rtol=0)
    jax.clear_caches()


# --- gated short-convolution layers beside GQA layers (``lfm2_moe``) -------


def test_lfm2_from_hf_config_on_the_published_keys():
    from llmq_tpu.models.presets import _LFM2_24B_A2B

    whole = ModelConfig.from_hf_config(_LFM2_24B_A2B)
    assert whole.num_layers == 40 and whole.head_dim_ == 64 and whole.tie_word_embeddings
    assert [a for a, _ in whole.layer_pattern].count("gqa") == 10
    assert [m for _, m in whole.layer_pattern] == ["dense"] * 2 + ["moe"] * 38
    assert whole.layer_pattern[2][0] == "gqa" and whole.layer_pattern[38][0] == "gqa"
    assert (whole.short_conv_kernel_size, whole.rope_theta, whole.rms_norm_eps) == (3, 1e6, 1e-5)
    assert whole.router_bias and whole.router_norm_eps == 1e-6 and whole.qk_norm
    assert whole.shared_expert_intermediate_size is None and whole.experts_held_ == (0, 64)
    stage = get_preset("lfm2-24b-a2b-pp5")
    assert [(g.attn, g.mlp, g.count) for g in hybrid.layer_groups(stage)] == LFM2_GROUPS
    shapes = hybrid.param_shapes(stage)
    assert "lm_head" not in shapes and "shared_gate_proj" not in shapes["stack1"]
    n = sum(
        int(np.prod(shape)) for shape in jax.tree.leaves(
            shapes, is_leaf=lambda x: isinstance(x, tuple)
        )
    )
    assert round(n / 1e6) == 5178  # 10.36 GB of bf16
    # a pool row is a token's V then K: 1,024 values, whole lane tiles
    assert cache.latent_pool_width(stage) == 1024 and cache.paged_rank(stage) == 512
    assert sum(cache.state_bytes(stage, 129, jnp.bfloat16).values()) == 7 * 129 * 2 * 2048 * 2
    for key, value in (("conv_bias", True), ("rope_parameters", {"rope_type": "yarn"})):
        with pytest.raises(ValueError, match="lfm2_moe"):
            ModelConfig.from_hf_config(dict(_LFM2_24B_A2B, **{key: value}))
    with pytest.raises(ValueError, match="one paged kind and one state kind"):
        hybrid.layer_groups(
            dataclasses.replace(stage, layer_pattern=(("gqa", "moe"), ("mla", "moe")))
        )


@pytest.mark.parametrize("n", [9, 16, 21])
def test_lfm2_prefill_of_n_then_one_decode_step_is_prefill_of_n_plus_one(n):
    """A row padded inside a 4-row bucket (another row longer, one
    padded): its tail is taken at its TRUE length and its V and K rows
    are written up to it, so one decode step after a prefill of n tokens
    gives the logits a prefill of n + 1 gives. n = 16: the step opens a
    page of 8."""
    f = family("lfm2")
    ids = list(np.random.default_rng(n).integers(1, 300, size=n + 1))
    other = list(np.random.default_rng(n + 1).integers(1, 300, size=27))

    def bucket(row):
        tokens = np.zeros((4, 32), np.int32)
        tokens[0, : len(other)], tokens[2, : len(row)] = other, row
        lengths = np.asarray([len(other), 0, len(row), 0], np.int32)
        bt = np.zeros((4, PPS), np.int32)
        bt[0, :4], bt[2, :3] = [1, 2, 3, 4], [5, 6, 7]
        k, v = make_kv_pages(f.mc, PAGES, PAGE, jnp.float32)
        logits, k, v = jax.jit(f.model.prefill)(f.params, tokens, lengths, k, v, bt)
        return logits, k, v, bt

    longer, _, _, _ = bucket(ids)
    _, k, v, bt = bucket(ids[:n])
    step, _, _ = jax.jit(f.model.decode)(
        f.params, np.asarray([0, 0, ids[n], 0], np.int32), np.asarray([0, 0, n, 0], np.int32),
        k, v, bt, np.asarray([False, False, True, False]),
    )
    np.testing.assert_allclose(np.asarray(step[2]), np.asarray(longer[2]), atol=2e-4, rtol=0)


def test_lfm2_the_selection_bias_changes_the_choice_and_not_the_weights():
    """A bias large enough to choose experts 0-3 for every token: the
    chosen experts change, and each weighs by its sigmoid score alone
    (over the sum of the four chosen scores + 1e-6), the bias nowhere in
    the weights."""
    f = family("lfm2")
    lp = {name: w[0] for name, w in f.params["stack1"].items()}
    x = jax.random.normal(jax.random.key(1), (6, 64), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(x @ lp["router"]))
    forced = dict(lp, router_bias=jnp.asarray([5.0] * 4 + [0.0] * 4))
    plain = dict(lp, router_bias=jnp.zeros((8,)))

    def by_hand(chosen):
        out = np.zeros((6, 64), np.float32)
        for t in range(6):
            total = scores[t, chosen[t]].sum() + 1e-6
            for e in chosen[t]:
                h = jax.nn.silu(x[t] @ lp["expert_gate_proj"][e]) * (x[t] @ lp["expert_up_proj"][e])
                out[t] += scores[t, e] / total * np.asarray(h @ lp["expert_down_proj"][e])
        return out

    largest = np.argsort(-scores, axis=1)[:, :4]
    assert not all(set(row) == {0, 1, 2, 3} for row in largest)
    out, counts = hybrid.moe_held(x, forced, f.mc)
    np.testing.assert_allclose(np.asarray(out), by_hand([[0, 1, 2, 3]] * 6), atol=2e-5, rtol=0)
    assert counts.tolist() == [24, 4]
    out, _ = hybrid.moe_held(x, plain, f.mc)
    np.testing.assert_allclose(np.asarray(out), by_hand(largest), atol=2e-5, rtol=0)


@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "grouped"])
def test_lfm2_every_expert_held_and_no_shared_expert_is_the_references_whole_layer(
    dense_rows, monkeypatch
):
    """``moe_held`` with all 8 experts held and no shared leaves is the
    reference's routed layer (its router with the seeded bias, its
    weights, every expert), in both forms of the sum."""
    monkeypatch.setattr(hybrid, "DENSE_EXPERT_ROWS", dense_rows)
    f = family("lfm2")
    assert f.mc.experts_held_ == (0, 8) and "shared_gate_proj" not in f.params["stack2"]
    lp = {name: w[1] for name, w in f.params["stack2"].items()}
    assert float(jnp.abs(lp["router_bias"]).max()) > 0
    x = jax.random.normal(jax.random.key(2), (40, 64), jnp.float32)
    z = f.arch._sizes(f.file_cfg)
    with jax.default_matmul_precision("highest"):
        w, picked = f.arch._route(x, lp, z, (4, 1.0, True), None)
        want = sum(
            w[:, e : e + 1] * f.arch._swiglu(
                x, lp["expert_gate_proj"][e], lp["expert_up_proj"][e],
                lp["expert_down_proj"][e], None,
            )
            for e in range(8)
        )
        out, counts = hybrid.moe_held(x, lp, f.mc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=0)
    assert int(counts[0]) == 40 * 4 and int(counts[1]) == int((picked.sum(axis=0) > 0).sum())


@pytest.mark.parametrize("preset, rows, bucket, kernels", [
    ("ling-3.0-flash-ep4", 1, 512, 0),
    ("ling-3.0-flash-ep4", 4, 2048, 0),
    ("openpangu-ultra-moe-718b-ep16", 1, 2048, 2),
])
def test_the_flash_prefill_kernel_is_in_the_programs_its_plan_names(
    preset, rows, bucket, kernels
):
    """``dispatch.mla_prefill_plan`` engages the flash kernel of expanded
    latent attention by ``num_heads x T``: the lowered text of ling's
    prefills at the shapes its cell warms (32 heads under 4,096 positions)
    holds no call of it, openpangu's 1 x 2,048 (128 heads) one a group of
    latent layers (the lead layer's and the expert layers')."""
    from llmq_tpu.models.transformer import init_params

    cfg = get_preset(preset)
    model = build_model(cfg, attn_backend="pallas")
    S = jax.ShapeDtypeStruct
    params = jax.eval_shape(partial(init_params, cfg, dtype=jnp.bfloat16), jax.random.key(0))
    kp, vp = jax.eval_shape(lambda: make_kv_pages(cfg, 64, 128, jnp.bfloat16, state_rows=5))
    text = jax.jit(model.prefill).lower(
        params, S((rows, bucket), jnp.int32), S((rows,), jnp.int32), kp, vp,
        S((rows, 32), jnp.int32), S((rows,), jnp.int32),
    ).as_text()
    assert model.mla_prefill_plan(bucket, jnp.bfloat16) == ("flash" if kernels else "xla")
    assert text.count("call @mla_flash_prefill_attention") == kernels
    assert kernels == sum(g.attn == "mla" for g in hybrid.layer_groups(cfg)) * bool(kernels)


@pytest.fixture(scope="module")
def pangu_block():
    """A pangu block at the head sizes the kernel takes (128 + 64 and 128),
    a padded batch of four rows, and its prefill in float32 by XLA's form:
    (cfg, bf16 params, tokens, lengths, block tables, reference logits)."""
    from llmq_tpu.models.transformer import init_params

    cfg = dataclasses.replace(
        get_preset("openpangu-ultra-moe-tiny"),
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=128,
    )
    params = init_params(cfg, jax.random.key(5), dtype=jnp.bfloat16)
    rng = np.random.default_rng(4)
    lengths = np.asarray([30, 9, 0, 17], np.int32)
    tokens = np.zeros((4, 32), np.int32)
    for r, n in enumerate(lengths):
        tokens[r, :n] = rng.integers(1, 300, size=n)
    bt = np.zeros((4, PPS), np.int32)
    bt[0, :4], bt[1, :2], bt[3, :3] = [1, 2, 3, 4], [5, 6], [7, 8, 9]
    k, v = make_kv_pages(cfg, PAGES, PAGE, jnp.float32)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    want, _, _ = jax.jit(build_model(cfg, attn_backend="xla").prefill)(
        f32, tokens, lengths, k, v, bt
    )
    return cfg, params, tokens, lengths, bt, np.asarray(want, np.float32)


@pytest.mark.parametrize("by_row", [False, True], ids=["one_pass", "row_at_a_time"])
@pytest.mark.parametrize("plan", ["xla", "flash"])
def test_each_plan_serves_the_float32_reference_logits(pangu_block, monkeypatch, plan, by_row):
    """The block's prefill in bf16 under each plan forced (the threshold
    out of the way; the kernel interpreted here), in one pass and a row at
    a time as ``_mla_prefill`` takes a bucket above
    ``MLA_PREFILL_HEAD_TOKENS``: the float32 reference's logits to bf16's
    rounding, the same latent rows to the bit under both plans (they are
    written before any attention), and nothing but numbers in the rows
    that are padding."""
    from llmq_tpu.ops import dispatch

    cfg, params, tokens, lengths, bt, want = pangu_block
    monkeypatch.setattr(dispatch, "MLA_FLASH_HEAD_TOKENS", 0)
    if by_row:
        monkeypatch.setattr(hybrid, "MLA_PREFILL_HEAD_TOKENS", 16)

    def run(backend):
        model = build_model(cfg, attn_backend=backend)
        k, v = make_kv_pages(cfg, PAGES, PAGE, jnp.bfloat16)
        assert model.mla_prefill_plan(32, jnp.bfloat16) == ("flash" if backend == "pallas" else "xla")
        logits, k, _ = jax.jit(model.prefill)(params, tokens, lengths, k, v, bt)
        return np.asarray(logits, np.float32), np.asarray(k, np.float32)

    logits, latent = run("pallas" if plan == "flash" else "xla")
    live = lengths > 0
    assert np.isfinite(logits).all() and np.isfinite(latent).all()
    np.testing.assert_allclose(logits[live], want[live], atol=0.12, rtol=0.05)
    if plan == "flash":
        other, other_latent = run("xla")
        np.testing.assert_allclose(logits[live], other[live], atol=0.06, rtol=0.05)
        np.testing.assert_array_equal(latent[0], other_latent[0])  # before any attention
