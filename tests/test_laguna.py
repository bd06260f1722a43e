"""A pattern with TWO attending kinds (``laguna``: Laguna-S-2.1):
full-attention layers over the paged pool beside sliding-window layers
over a per-sequence ring, a head count and a rotary embedding a kind, a
per-head output gate, softmax-routed experts held by share. CPU, tiny
widths, seeded weights, a window of 8 so that a short test wraps the ring
several times.

The comparison is with the benchmark's plain reference
(``benchmark/architectures/laguna.py``: float32, full-sequence causal
attention with the window as a mask, no cache, nothing imported from the
program): prefill and then decoding through pool and ring must give the
logits of the reference's full forward pass.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import architectures, weights
from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import cache, hybrid
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.presets import _LAGUNA_S_2_1, get_preset
from llmq_tpu.models.transformer import (
    build_model, compute_rope_inv_freq, init_params, make_kv_pages, yarn_correction_range,
)
from llmq_tpu.parallel import make_mesh

W = 8
HF = dict(
    _LAGUNA_S_2_1, vocab_size=304, hidden_size=64, num_hidden_layers=5,
    kept_layers=[0, 1, 2, 3, 4], num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, intermediate_size=128,
    num_attention_heads_per_layer=[6 if i % 4 == 0 else 12 for i in range(48)],
    sliding_window=W, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    experts_held=[4, 8],
)
GROUPS = [("gqa", "dense", 1), ("swa", "moe", 3), ("gqa", "moe", 1)]


def configs(**changed):
    hf = dict(HF, **changed)
    return ModelConfig.from_hf_config(hf), dict(hf, architecture="laguna")


MC, FILE_CFG = configs()
ARCH = architectures.of(FILE_CFG)
PARAMS = weights.make_weights(ARCH, FILE_CFG, 7, None, dtype=jnp.float32)
MODEL = build_model(MC)
PAGE, PAGES, PPS = 8, 40, 8


def test_tree_is_the_one_the_benchmark_describes():
    ours = jax.tree.map(
        tuple, hybrid.param_shapes(MC), is_leaf=lambda x: isinstance(x, tuple)
    )
    assert ours == ARCH.tree_shapes(FILE_CFG)
    assert [(g.attn, g.mlp, g.count) for g in hybrid.layer_groups(MC)] == GROUPS
    assert "q_norm" not in ours["stack0"] and ours["stack1"]["g_proj"] == (3, 64, 12)


def serve(lengths, steps, state_rows=None, model=MODEL):
    """Prefill of a padded batch, then ``steps - 1`` decode steps; the
    logits of every row's served positions, and the sequences."""
    rows = len(lengths)
    rng = np.random.default_rng(rows)
    seqs = [list(rng.integers(1, 300, size=n + steps)) for n in lengths]
    k, v = make_kv_pages(MC, PAGES, PAGE, jnp.float32)
    bt = np.zeros((rows, PPS), np.int32)
    free = iter(range(1, PAGES))
    for r, n in enumerate(lengths):
        if n:
            need = -(-(n + steps) // PAGE)
            bt[r, :need] = [next(free) for _ in range(need)]
    tokens = np.zeros((rows, 32), np.int32)
    for r, n in enumerate(lengths):
        tokens[r, :n] = seqs[r][:n]
    at_prefill = None if state_rows is None else np.arange(1, rows + 1, dtype=np.int32)
    logits, k, v = jax.jit(model.prefill)(
        PARAMS, tokens, np.asarray(lengths, np.int32), k, v, bt, at_prefill
    )
    got = [[np.asarray(logits[r])] for r in range(rows)]
    decode = jax.jit(model.decode, static_argnames=("state_rows",))
    active = np.asarray([n > 0 for n in lengths])
    for j in range(steps - 1):
        toks = np.asarray([s[n + j] if n else 0 for s, n in zip(seqs, lengths)], np.int32)
        ctx = np.asarray([n + j for n in lengths], np.int32)
        logits, k, v = decode(PARAMS, toks, ctx, k, v, bt, active, state_rows=state_rows)
        for r in range(rows):
            got[r].append(np.asarray(logits[r]))
    return got, seqs


@pytest.mark.parametrize("lengths, state_rows", [
    ([19], None), ([19, 7, 0, 30], None), ([19, 7, 0, 30], 1), ([5, 0, 31, 8], 1),
], ids=["one_row", "four_rows", "four_rows_a_run_of_state_rows", "shorter_than_the_window"])
def test_prefill_then_decode_matches_the_reference(lengths, state_rows):
    """Rows of unequal length in one bucket, a padded row, then 19 decode
    steps with an inactive slot: prompts of 19 and 30 have wrapped the
    window of 8 before decode starts, every row wraps it twice more while
    decoding, a prompt of 5 fills its ring for the first time in decode.
    ``state_rows``: the sequence's first page (what the benchmark's direct
    path passes) or a run of rows from 1 (what the engine passes)."""
    steps = 20
    got, seqs = serve(lengths, steps, state_rows)
    for r, n in enumerate(lengths):
        if not n:
            continue
        ref = np.asarray(ARCH.forward_logits(
            PARAMS, FILE_CFG, seqs[r][: n + steps - 1], list(range(n - 1, n + steps - 1))
        ))
        np.testing.assert_allclose(np.stack(got[r]), ref, atol=2e-4, rtol=0)


def test_the_ring_holds_the_last_window_at_position_mod_window():
    """After a prompt of 19 and 5 decode steps (24 positions) ring row
    ``p mod 8`` holds position ``p`` for the last 8 positions, in every
    window layer, and the scratch row is the only other row written."""
    k, v = make_kv_pages(MC, PAGES, PAGE, jnp.float32)
    assert set(v) == {"S", "conv", "ring"} and v["S"].size == 0 and v["conv"].size == 0
    assert v["ring"].shape == (3, PAGES, W, 128)  # a window of 8: one ring page a row
    rng = np.random.default_rng(0)
    seq = rng.integers(1, 300, size=24)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :19] = seq[:19]
    bt = np.arange(1, PPS + 1, dtype=np.int32)[None]
    _, k, v = jax.jit(MODEL.prefill)(PARAMS, tokens, np.asarray([19], np.int32), k, v, bt)
    decode = jax.jit(MODEL.decode)
    for j in range(19, 24):
        _, k, v = decode(
            PARAMS, seq[j : j + 1].astype(np.int32), np.asarray([j], np.int32), k, v, bt,
            np.asarray([True]),
        )
    ring = np.asarray(v["ring"])
    assert not ring[:, 2:].any()  # state row 1 (the first page) and scratch row 0 alone
    # A whole prefill of 24 leaves the same window: positions 16-23.
    k2, v2 = make_kv_pages(MC, PAGES, PAGE, jnp.float32)
    tokens[0, :24] = seq
    _, _, v2 = jax.jit(MODEL.prefill)(PARAMS, tokens, np.asarray([24], np.int32), k2, v2, bt)
    np.testing.assert_allclose(ring[:, 1], np.asarray(v2["ring"])[:, 1], atol=2e-5)


def test_yarn_frequencies_against_hand_worked_values():
    """Published Laguna: 64 rotated values, theta 500,000, factor 128 over
    8,192 positions. Pair i turns ``8192 theta^(-i/32) / (2 pi)`` times over
    the original context: 32 turns at i = 32 ln(8192 / 64 pi) / ln 5e5 =
    9.04 (floor 9), 1 turn at 17.49 (ceil 18). So pairs 0-9 keep
    ``theta^(-i/32)``, pairs 18-31 are divided by 128, pair 13 is 4/9 of
    the way: ``f / 128 * 4/9 + f * 5/9``."""
    assert yarn_correction_range(32, 1, 64, 500000.0, 8192) == (9, 18)
    big = get_preset("laguna-s-2.1-ep4")
    inv = np.asarray(compute_rope_inv_freq(big, 64), np.float64)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], plain[18:] / 128, rtol=1e-6)
    np.testing.assert_allclose(inv[13], plain[13] * (4 / 9 / 128 + 5 / 9), rtol=1e-6)
    assert inv[0] == 1.0 and inv[31] == pytest.approx(500000.0 ** (-31 / 32) / 128, rel=1e-6)
    np.testing.assert_allclose(
        inv, np.asarray(ARCH.yarn_inv_freq(
            _LAGUNA_S_2_1["rope_parameters"]["full_attention"], 64), np.float64),
        rtol=1e-6,
    )
    # the published attention_factor is YaRN's own 0.1 ln(factor) + 1
    assert big.rope_scaling["attention_factor"] == pytest.approx(0.1 * math.log(128) + 1)
    # the other families' frequencies are what they were
    assert np.asarray(compute_rope_inv_freq(get_preset("lfm2-24b-a2b-pp5"))).shape == (32,)


def test_from_hf_config_on_the_published_keys():
    whole = ModelConfig.from_hf_config(_LAGUNA_S_2_1)
    kinds = [a for a, _ in whole.layer_pattern]
    assert len(kinds) == 48 and kinds.count("gqa") == 12 and kinds.count("swa") == 36
    assert kinds[:5] == ["gqa", "swa", "swa", "swa", "gqa"]
    assert [m for _, m in whole.layer_pattern] == ["dense"] + ["moe"] * 47
    assert (whole.num_heads, whole.swa_num_heads, whole.num_kv_heads, whole.head_dim_) == (48, 72, 8, 128)
    assert whole.swa_window == 512 and whole.sliding_window is None
    assert (whole.rope_theta, whole.swa_rope_theta, whole.partial_rotary_factor) == (500000.0, 10000.0, 0.5)
    assert whole.attn_head_gate and not whole.qk_norm and whole.router_scoring == "softmax"
    assert whole.o_proj_barrier and not get_preset("lfm2-24b-a2b-pp5").o_proj_barrier
    assert whole.experts_held_ == (0, 256) and whole.routed_scaling_factor == 2.5
    cut = get_preset("laguna-s-2.1-ep4")
    assert cut.layer_pattern == tuple(zip(["gqa", "swa", "swa", "swa", "gqa"], ["dense"] + ["moe"] * 4))
    assert cut.experts_held_ == (0, 64) and cut.vocab_size == 25088
    n = sum(
        math.prod(s) for s in jax.tree.leaves(
            hybrid.param_shapes(cut), is_leaf=lambda x: isinstance(x, tuple))
    )
    assert round(n / 1e6) == 3002  # the issue's arithmetic: 6.00 GB at 2 bytes


@pytest.mark.parametrize("changed, named", [
    (dict(gating="per-channel"), "gating"),
    (dict(moe_router_logit_softcapping=30), "moe_router_logit_softcapping"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(moe_apply_router_weight_on_input=True), "moe_apply_router_weight_on_input"),
    (dict(num_hidden_layers=4), "kept_layers"),
    (dict(num_attention_heads_per_layer=[6, 12, 10, 12, 6]), "one head count a kind"),
    (dict(rope_parameters={"sliding_attention": {"rope_type": "yarn"}}), "sliding_attention"),
])
def test_a_block_that_is_not_built_is_refused_by_name(changed, named):
    with pytest.raises(ValueError, match=named):
        ModelConfig.from_hf_config(dict(HF, **changed))


def test_o_proj_is_a_value_of_its_own_in_this_family_alone():
    """``_gqa_out``: ``o_proj`` of both attending kinds behind an
    optimization barrier, so that two compilations of a step round alike
    (PERF.md section 6, PR 52: of q, k, v, ``o_proj`` and the shared
    expert's three it is the one that needs it); the families that share
    the code lower without one, as they did."""
    def lowered(cfg):
        model = build_model(cfg)
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0), dtype=jnp.float32))
        k, v = jax.eval_shape(lambda: make_kv_pages(cfg, 12, 8, jnp.float32))
        S = jax.ShapeDtypeStruct
        return jax.jit(model.decode).lower(
            params, S((2,), jnp.int32), S((2,), jnp.int32), k, v, S((2, 4), jnp.int32),
            S((2,), jnp.bool_),
        ).as_text()

    mine = lowered(get_preset("laguna-tiny")).count("optimization_barrier")
    # 5 attention layers in 3 groups, of which one is a scan of 3 layers
    assert mine == 3
    assert "optimization_barrier" not in lowered(get_preset("lfm2-moe-tiny"))


def test_a_pattern_has_one_paged_kind_and_one_state_kind():
    two_states = dataclasses.replace(MC, layer_pattern=(("swa", "moe"), ("conv", "moe")))
    with pytest.raises(ValueError, match="one paged kind and one state kind"):
        hybrid.layer_groups(two_states)


@pytest.mark.parametrize("dense_rows", [256, 0], ids=["dense", "grouped"])
def test_four_shares_of_a_softmax_router_add_up_to_the_uncut_layer(dense_rows, monkeypatch):
    """One expert layer with the published router: 256 outputs, softmax
    over all of them, 10 a token. The partial sums of the four shares
    (experts 0-63, 64-127, 128-191, 192-255), with the shared expert
    (which every chip computes alike) counted once, add up to what the
    uncut reference gives for the whole layer; both forms of the sum."""
    monkeypatch.setattr(hybrid, "DENSE_EXPERT_ROWS", dense_rows)
    whole_mc, whole_cfg = configs(
        num_experts=256, num_experts_per_tok=10, experts_held=[0, 256],
        num_hidden_layers=2, kept_layers=[0, 1],
    )
    arch = architectures.of(whole_cfg)
    whole = weights.make_weights(arch, whole_cfg, 11, None, dtype=jnp.float32)
    lp = {name: w[0] for name, w in whole["stack1"].items()}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(9, 64)), jnp.float32)
    z = arch._sizes(whole_cfg)
    w, picked = arch._route(x, lp, z, (10, 2.5, True), None)
    assert (np.asarray(picked).sum(axis=1) == 10).all()
    np.testing.assert_allclose(np.asarray(w).sum(axis=1), 2.5, rtol=1e-5)
    shared = arch._dense_mlp(
        x, lp["shared_gate_proj"], lp["shared_up_proj"], lp["shared_down_proj"], control=None
    )
    uncut = shared + arch._expert_block(
        x, w, lp["expert_gate_proj"], lp["expert_up_proj"], lp["expert_down_proj"],
        control=None,
    )
    total, assignments = jnp.zeros_like(shared), 0
    for first in (0, 64, 128, 192):
        share = {
            name: leaf[first : first + 64] if name.startswith("expert_") else leaf
            for name, leaf in lp.items()
        }
        mc = dataclasses.replace(whole_mc, experts_held=(first, 64))
        part, counts = hybrid.moe_held(x, share, mc)
        total = total + (part - shared)
        assignments += int(counts[0])
    assert assignments == 9 * 10  # every assignment lands on exactly one share
    np.testing.assert_allclose(total + shared, uncut, atol=2e-5)


@pytest.mark.parametrize("fault", [c for c in ARCH.CONTROLS if c not in ("int8w", "fp8cache")])
def test_a_planted_fault_of_the_reference_is_far_from_it(fault):
    """Each piece of the block the reference can leave out moves the
    logits by far more than the program differs from the reference."""
    rng = np.random.default_rng(5)
    seq = list(rng.integers(1, 300, size=40))
    at = list(range(32, 40))
    ref = np.asarray(ARCH.forward_logits(PARAMS, FILE_CFG, seq, at))
    bad = np.asarray(ARCH.forward_logits(PARAMS, FILE_CFG, seq, at, control=fault))
    assert np.abs(bad - ref).max() > 0.05, fault


def test_the_diagnoses_round_and_force_the_float32_choice_of_experts():
    rng = np.random.default_rng(6)
    seq, at = list(rng.integers(1, 300, size=40)), list(range(32, 40))
    ref = np.asarray(ARCH.forward_logits(PARAMS, FILE_CFG, seq, at))
    forced = np.asarray(ARCH.forward_logits(PARAMS, FILE_CFG, seq, at, control="bf16act_routed"))
    err = np.sqrt(np.mean((forced - ref) ** 2)) / ref.std()
    assert 0 < err < 0.05


# --- the engine -----------------------------------------------------------

TINY = get_preset("laguna-tiny")
TINY_PARAMS = init_params(TINY, jax.random.key(4), dtype=jnp.float32)


def make_core(**engine) -> EngineCore:
    options = dict(
        max_num_seqs=4, max_model_len=96, page_size=8, num_pages=60,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    options.update(engine)
    return EngineCore(
        TINY, TINY_PARAMS, ByteTokenizer(), mesh=make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**options),
    )


def greedy(n=8):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)


def drain(core, outs=None):
    outs = {} if outs is None else outs
    for _ in range(1200):
        for out in core.step():
            outs[out.rid] = out
        if not core.has_work:
            return outs
    raise AssertionError("engine stalled")


_IDLE, _SERVED_ALONE = [], {}


def alone(prompt, n):
    """What an engine with nothing else in it serves (one engine for all
    the comparisons; ``test_idle_serving_is_the_models_own_greedy_loop``
    ties it to the model)."""
    if (prompt, n) not in _SERVED_ALONE:
        if not _IDLE:
            _IDLE.append(make_core())
        _IDLE[0].add_request("x", prompt=prompt, params=greedy(n))
        _SERVED_ALONE[prompt, n] = drain(_IDLE[0])["x"].token_ids
    return _SERVED_ALONE[prompt, n]


# Prompts of 8-48 bytes and 14-26 outputs: every sequence wraps the window
# of 8 in its prompt or in its first steps, and twice more while decoding.
REQUESTS = [(f"r{i}", f"seq {i} " * (i + 1), 14 + 6 * (i % 3)) for i in range(8)]


def test_idle_serving_is_the_models_own_greedy_loop():
    prompt, n = REQUESTS[3][1], REQUESTS[3][2]
    model = build_model(TINY)
    ids = ByteTokenizer().encode(prompt)
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, : len(ids)] = ids
    bt = np.arange(1, 13, dtype=np.int32)[None]
    k, v = make_kv_pages(TINY, 13, 8, jnp.float32)
    logits, k, v = jax.jit(model.prefill)(
        TINY_PARAMS, tokens, np.asarray([len(ids)], np.int32), k, v, bt
    )
    out = [int(np.argmax(logits[0]))]
    decode = jax.jit(model.decode)
    for j in range(n - 1):
        logits, k, v = decode(
            TINY_PARAMS, np.asarray(out[-1:], np.int32),
            np.asarray([len(ids) + j], np.int32), k, v, bt, np.asarray([True]),
        )
        out.append(int(np.argmax(logits[0])))
    assert alone(prompt, n) == out


def test_slots_and_rings_reused_after_finish_serve_what_an_idle_engine_serves():
    """8 requests through 4 slots, run-ahead steps in flight: every ring
    is overwritten by its next prefill while the other rows decode."""
    core = make_core()
    for rid, prompt, n in REQUESTS:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(core)
    assert core.stats()["prefills"] == len(REQUESTS)
    for rid, prompt, n in REQUESTS:
        assert outs[rid].token_ids == alone(prompt, n), rid


def test_slot_reused_after_cancel():
    core = make_core(max_num_seqs=2)
    core.add_request("gone", prompt="to be cancelled " * 2, params=greedy(40))
    core.add_request("stays", prompt="stays here", params=greedy(20))
    for _ in range(4):
        core.step()
    core.cancel_request("gone")
    core.add_request("next", prompt="takes the freed slot", params=greedy(17))
    outs = drain(core)
    assert outs["stays"].token_ids == alone("stays here", 20)
    assert outs["next"].token_ids == alone("takes the freed slot", 17)
    assert core.stats()["cancellations"] == 1


def test_recompute_preemption_rebuilds_the_ring():
    """A pool too small for all three: a victim is preempted and prefilled
    again over prompt + output, which leaves its ring the last window of
    that longer prompt, in whatever slot is free."""
    prompts = [(f"r{i}", f"pr {i} " * 3, 20) for i in range(3)]
    core = make_core(num_pages=11, page_size=4, max_model_len=48)
    for rid, prompt, n in prompts:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(core)
    assert core.stats()["preemptions"] >= 1
    for rid, prompt, n in prompts:
        assert outs[rid].token_ids == alone(prompt, n), rid


def test_decode_block_and_batched_prefill_serve_the_same_tokens():
    core = make_core(decode_block=4)
    for rid, prompt, n in REQUESTS[:4]:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(core)
    for rid, prompt, n in REQUESTS[:4]:
        assert outs[rid].token_ids == alone(prompt, n), rid


def test_the_ring_does_not_grow_with_max_model_len_and_a_page_spans_the_full_layers():
    """3 window layers x 5 state rows x 8 ring rows x 128 values, whatever
    ``max_model_len`` is; the paged pool has the 2 full-attention layers
    alone, and ``stats()`` says both."""
    short, long = make_core(max_model_len=96), make_core(max_model_len=480, num_pages=300)
    for core in (short, long):
        assert core.cache.state_kind == "swa" and core.cache.state_rows == 5
        assert core.v_pages["ring"].shape == (3, 5, 8, 128)
        assert core.k_pages.shape[0] == 2 and core.k_pages.shape[2:] == (8, 128)
        stats = core.stats()
        assert stats["kv_pool_layers"] == 2
        assert stats["swa_ring_bytes"] == 3 * 5 * 8 * 128 * 4
        assert stats["swa_ring_bytes"] == cache.state_bytes(TINY, 5, jnp.float32)["ring"]
        assert core.cache.fixed_bytes == stats["swa_ring_bytes"]
    assert long._pages_per_seq == 60 and short._pages_per_seq == 12
    big = get_preset("laguna-s-2.1-ep4")
    assert cache.ring_pages(big) == (128, 4)
    assert cache.state_bytes(big, 129, jnp.bfloat16)["ring"] == 129 * 3 * 512 * 4096  # 0.81 GB
    assert cache.latent_pool_width(big) == 2048 and cache.paged_rank(big) == 1024
    assert cache.count_layers(big, *cache.PAGED_KINDS) == 2  # 128 x 2 x 4,096 B a page


def test_the_dispatch_span_counts_the_window_layers_rows_beside_the_full_layers_pages():
    core = make_core()
    core.spans.set(True)
    for rid, prompt, n in REQUESTS[:3]:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    drain(core)
    dispatches = [s for s in core.spans.dump()["spans"] if s["name"] == "decode_dispatch"]
    assert dispatches
    for s in dispatches:
        assert 1 <= s["state_rows"] <= 3
        assert s["window_rows"] <= W * s["rows"]  # min(len, 8) a sequence
        assert s["live_pages"] >= s["rows"]  # pages of the full layers: every token's
    late = dispatches[-1]
    assert late["window_rows"] == W * late["rows"]  # every context is past the window
    assert late["live_pages"] > late["rows"]
    stats = core.stats()
    steps = stats["decode_steps"]
    # 4 expert layers, 4 slots a step, 4 experts a token of which 8 of 16 held
    assert 0 < stats["moe_assignments_held"] <= steps * 4 * 4 * 4
    assert 0 < stats["moe_experts_hit"] <= steps * 4 * 8


REFUSED_AT_BUILD = {  # option -> the name the refusal gives
    "prefix_caching": (dict(enable_prefix_caching=True), "enable_prefix_caching"),
    "prefix_host_tier": (dict(prefix_host_gb=0.01), "prefix_host_gb"),
    "spec_tokens": (dict(spec_tokens=2), "spec_tokens=2"),
    "preempt_swap": (dict(preempt_mode="swap"), "preempt_mode=swap"),
    "mixed_step": (dict(mixed_step="on"), "mixed_step=on"),
    "prefill_chunk_size": (dict(prefill_chunk_size=16), "prefill_chunk_size"),
    "quantised_cache": (dict(kv_dtype="fp8"), "kv_dtype"),
}


@pytest.mark.parametrize("option, named", REFUSED_AT_BUILD.values(), ids=REFUSED_AT_BUILD.keys())
def test_refused_at_build_for_the_reason_that_holds_for_a_ring(option, named):
    with pytest.raises(ValueError, match="layer pattern") as refused:
        make_core(**option)
    assert str(refused.value).startswith(named)
    assert "a ring of the last window's rows cannot be shared by a prefix" in str(refused.value)
    assert "KDA" not in str(refused.value) and "convolution" not in str(refused.value)


def test_refuses_snapshots_the_prefill_role_and_a_cut_cache_by_the_same_reason():
    core = make_core()
    with pytest.raises(NotImplementedError, match="extract_all.*ring"):
        core.extract_all()
    with pytest.raises(NotImplementedError, match="prefill role.*ring"):
        core.add_request("p", prompt="x", params=greedy(2), prefill_only=True)
    with pytest.raises(NotImplementedError, match="as a ring, which cannot be cut at a chunk"):
        MODEL.prefill_chunk()
    with pytest.raises(NotImplementedError, match="takes no .*'h'.*as a ring"):
        MODEL.decode(None, None, None, None, None, None, None, h=1)


def test_worker_refuses_the_disaggregated_roles(monkeypatch):
    from llmq_tpu.workers.tpu_worker import TPUWorker

    monkeypatch.setenv("LLMQ_WORKER_ROLE", "prefill")
    monkeypatch.setenv("LLMQ_BROKER_URL", "memory://laguna-role")
    worker = TPUWorker("q", model="preset://laguna-s-2.1-ep4")
    with pytest.raises(ValueError, match="role=prefill is not supported"):
        worker._build_core()
