"""The engine serving a model with a layer pattern (``models/hybrid.py``):
slots and their state rows reused while run-ahead steps are in flight,
what it refuses at build, and its counters; and a pattern with no state
layer at all (``pangu_ultra_moe``: latent attention alone), whose state
pool is empty. CPU, tiny widths."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.snapshot import RequestSnapshot
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import quant as qm
from llmq_tpu.models import cache, hybrid
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.presets import get_preset
from llmq_tpu.models.transformer import build_model, init_params, make_kv_pages
from llmq_tpu.ops.attention import latent_decode_pages_visited
from llmq_tpu.parallel import make_mesh

CFG = ModelConfig(
    vocab_size=304, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=4,
    head_dim=16, intermediate_size=128, eos_token_ids=(0,),
    model_type="bailing_hybrid",
    layer_pattern=(("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe")),
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, experts_held=(4, 8),
)
PARAMS = init_params(CFG, jax.random.key(0), dtype=jnp.float32)
# A pattern with NO state layer: latent attention alone (a query LoRA,
# sandwich norms, one group of experts), as the worker's preset builds it.
STATELESS = get_preset("openpangu-ultra-moe-tiny")
STATELESS_PARAMS = init_params(STATELESS, jax.random.key(0), dtype=jnp.float32)


def make_core(params=PARAMS, mesh=None, cfg=CFG, **engine) -> EngineCore:
    options = dict(
        max_num_seqs=4, max_model_len=96, page_size=8, num_pages=60,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    options.update(engine)
    return EngineCore(
        cfg, params, ByteTokenizer(), mesh=mesh or make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**options),
    )


def make_stateless(**engine) -> EngineCore:
    return make_core(params=STATELESS_PARAMS, cfg=STATELESS, **engine)


def greedy(n=8):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)


def drain(core, outs=None):
    outs = {} if outs is None else outs
    for _ in range(800):
        for out in core.step():
            outs[out.rid] = out
        if not core.has_work:
            return outs
    raise AssertionError("engine stalled")


_IDLE = []  # one engine that serves the comparisons one at a time
_SERVED_ALONE = {}


def alone(prompt, n):
    """What an engine with nothing else in it serves (one engine for all
    the comparisons, so that its programs compile once; its first request
    finds it fresh, and `test_idle_engine_is_a_fresh_engine` ties the
    rest to that)."""
    if (prompt, n) not in _SERVED_ALONE:
        if not _IDLE:
            _IDLE.append(make_core())
        _IDLE[0].add_request("x", prompt=prompt, params=greedy(n))
        _SERVED_ALONE[prompt, n] = drain(_IDLE[0])["x"].token_ids
    return _SERVED_ALONE[prompt, n]


def test_idle_engine_is_a_fresh_engine():
    alone("warm the comparisons' engine", 5)
    fresh = make_core()
    fresh.add_request("x", prompt=REQUESTS[3][1], params=greedy(REQUESTS[3][2]))
    assert drain(fresh)["x"].token_ids == alone(*REQUESTS[3][1:])


REQUESTS = [(f"r{i}", f"seq {i} " * (i + 2), 6 + 3 * (i % 3)) for i in range(8)]


def test_slots_reused_after_finish_serve_what_a_fresh_engine_serves():
    """8 requests through 4 slots: every state row is overwritten by its
    next prefill while decode steps of the other rows are in flight."""
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
    core.add_request("stays", prompt="stays here", params=greedy(12))
    for _ in range(4):
        core.step()
    core.cancel_request("gone")
    core.add_request("next", prompt="takes the freed slot", params=greedy(9))
    outs = drain(core)
    assert outs["stays"].token_ids == alone("stays here", 12)
    assert outs["next"].token_ids == alone("takes the freed slot", 9)
    assert core.stats()["cancellations"] == 1


def test_recompute_preemption_serves_the_same_tokens():
    """A pool too small for all three: a victim is preempted, prefilled
    again over prompt + output into whatever slot is free, and goes on
    from a state rebuilt whole."""
    prompts = [(f"r{i}", f"pr {i} " * 3, 14) for i in range(3)]
    core = make_core(num_pages=9, page_size=4, max_model_len=48)
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


def test_counters_ride_the_token_fetch_and_the_span():
    core = make_core()
    core.spans.set(True)
    for rid, prompt, n in REQUESTS[:3]:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    drain(core)
    stats = core.stats()
    steps = stats["decode_steps"]
    # 3 expert layers, 4 slots a step, 4 experts a token of which 8 of 16 held
    assert 0 < stats["moe_assignments_held"] <= steps * 3 * 4 * 4
    assert 0 < stats["moe_experts_hit"] <= min(
        stats["moe_assignments_held"], steps * 3 * 8
    )
    dispatches = [s for s in core.spans.dump()["spans"] if s["name"] == "decode_dispatch"]
    assert dispatches and all(1 <= s["state_rows"] <= 3 for s in dispatches)
    assert all(s["latent_pages_visited"] >= s["live_pages"] for s in dispatches)


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
def test_refused_at_build_by_name(option, named):
    with pytest.raises(ValueError, match="layer pattern") as refused:
        make_core(**option)
    assert str(refused.value).startswith(named)
    assert "per-sequence KDA state" in str(refused.value)


@pytest.mark.parametrize("option, named", REFUSED_AT_BUILD.values(), ids=REFUSED_AT_BUILD.keys())
def test_a_pattern_without_state_is_refused_for_the_reason_that_holds(option, named):
    """No state layer: the option is refused because the path is not built
    for a layer pattern, not for a state the model does not have."""
    with pytest.raises(ValueError, match="not built for a layer pattern") as refused:
        make_stateless(**option)
    assert str(refused.value).startswith(named)
    assert "KDA" not in str(refused.value)


def test_a_pattern_without_state_layers_has_an_empty_state_pool():
    """Zero KDA layers: both leaves of the state pool are empty, the pool
    sizing takes nothing out of the budget for them, and the engine's
    cache is the latent pool alone."""
    assert hybrid.count_layers(STATELESS, "kda") == 0
    assert sum(cache.state_bytes(STATELESS, 129, jnp.bfloat16).values()) == 0
    core = make_stateless()
    assert core.cache.state_rows == 5 and core.cache.state_kind is None
    assert {k: v.shape[0] for k, v in core.v_pages.items()} == {"S": 0, "conv": 0}
    assert all(v.size == 0 for v in core.v_pages.values())
    latent = core.k_pages
    assert latent.shape == (3, 60, 8, 128)  # 3 MLA layers; rows of 40 kept in 128
    assert core.kv_pool_bytes == latent.size * latent.dtype.itemsize


def test_a_pattern_without_state_layers_is_served_as_a_fresh_engine_serves_it():
    """8 requests through 4 slots, slots reused while run-ahead steps are
    in flight, then a pool too small for three (recompute preemption):
    every request gets the tokens an engine with nothing else in it
    serves."""
    idle = make_stateless()

    def alone_stateless(prompt, n):
        idle.add_request("x", prompt=prompt, params=greedy(n))
        return drain(idle)["x"].token_ids

    core = make_stateless()
    for rid, prompt, n in REQUESTS:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(core)
    assert core.stats()["prefills"] == len(REQUESTS)
    for rid, prompt, n in REQUESTS:
        assert outs[rid].token_ids == alone_stateless(prompt, n), rid
    prompts = [(f"r{i}", f"pr {i} " * 3, 14) for i in range(3)]
    small = make_stateless(num_pages=9, page_size=4, max_model_len=48)
    for rid, prompt, n in prompts:
        small.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(small)
    assert small.stats()["preemptions"] >= 1
    for rid, prompt, n in prompts:
        assert outs[rid].token_ids == alone_stateless(prompt, n), rid


def test_a_pattern_without_state_layers_reports_what_the_latent_loop_gathers(monkeypatch):
    """``decode_dispatch`` carries ``latent_pages_visited`` (every row of
    the step x the longest row's passes x the pages a pass) beside
    ``live_pages``, no ``state_rows``; the expert counters ride the token
    fetch; ``stats()["decode_kernel"]`` says what it says for any latent
    pool: the XLA loop, whatever schedule a K/V pool of its head counts
    would get on the chip."""
    core = make_stateless(max_model_len=96, page_size=8)  # 12 page places: 3 passes of 4
    core.spans.set(True)
    for rid, prompt, n in REQUESTS[:3]:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    drain(core)
    monkeypatch.setattr(
        "llmq_tpu.ops.dispatch.decode_kernel_plan", lambda *a, **k: "live"
    )
    stats = core.stats()
    assert stats["decode_kernel"] == make_core().stats()["decode_kernel"] == "xla"
    steps = stats["decode_steps"]
    # 2 expert layers, 4 slots a step, 4 experts a token of which 8 of 16 held
    assert 0 < stats["moe_assignments_held"] <= steps * 2 * 4 * 4
    assert 0 < stats["moe_experts_hit"] <= min(stats["moe_assignments_held"], steps * 2 * 8)
    dispatches = [s for s in core.spans.dump()["spans"] if s["name"] == "decode_dispatch"]
    assert dispatches and all("state_rows" not in s for s in dispatches)
    for s in dispatches:
        # 4 slots x passes x 4 pages a pass; a pass covers 32 tokens
        assert s["latent_pages_visited"] in (16, 32, 48)
        assert s["latent_pages_visited"] >= s["live_pages"]
    # the host's view of the longest row lags the steps in flight
    longest = max(len(p) + n for _, p, n in REQUESTS[:3])
    assert max(s["latent_pages_visited"] for s in dispatches) <= 16 * -(-longest // 32)
    # rows x the longest row's passes x pages a pass, capped at the block table
    def visited(rows, longest, places, page):
        return latent_decode_pages_visited("xla", [longest], rows, places, page)

    assert visited(128, 3584, 32, 128) == 128 * 7 * 4 == 3584
    assert visited(128, 1, 32, 128) == visited(128, 0, 32, 128) == 512
    assert visited(4, 33, 12, 8) == 4 * 2 * 4 and visited(4, 10**6, 12, 8) == 4 * 3 * 4
    assert visited(2, 5, 3, 8) == 2 * 1 * 3  # fewer page places than a pass takes


@pytest.mark.parametrize(
    "contexts,places,page,own",
    [
        ([3584] * 128, 32, 128, 128 * 28),  # every row at the longest: 3,584 as the loop
        ([2560, 3584, 3000, 0, 1], 32, 128, 20 + 28 + 24 + 0 + 1),
        ([129], 64, 128, 2),
        ([], 32, 128, 0),
        ([33, 8, 9], 12, 8, 5 + 1 + 2),
    ],
)
def test_the_kernels_plan_visits_each_rows_own_pages(contexts, places, page, own):
    """``latent_decode_pages_visited`` for the kernel's plan: the sum of
    each row's own pages (the kernel copies a page a copy: its granule is
    one page), whatever the longest row and the block table's width; at
    least what is live, at most what the XLA loop gathers for the slots."""
    slots = max(len(contexts), 1)
    kernel = latent_decode_pages_visited("latent_live", contexts, slots, places, page)
    assert kernel == own == sum(-(-n // page) for n in contexts)
    assert kernel <= latent_decode_pages_visited("xla", contexts, slots, places, page)


def test_a_latent_pool_the_kernel_reads_is_served_by_it(monkeypatch):
    """Where the plan names the kernel (pallas, interpreted here; a rank
    and rows of whole lane tiles, float32 pages of 8 rows), the decode
    step runs it: the tokens are the XLA loop's, ``stats()`` names the
    plan's schedule, and ``latent_pages_visited`` is what is live."""
    import dataclasses

    from llmq_tpu.ops import dispatch

    cfg = dataclasses.replace(STATELESS, kv_lora_rank=128)
    params = init_params(cfg, jax.random.key(1), dtype=jnp.float32)
    served = {}
    for backend, plan in (("xla", "xla"), ("pallas", "latent_live")):
        monkeypatch.setenv("LLMQ_ATTN_BACKEND", backend)
        core = make_core(params=params, cfg=cfg)
        assert dispatch.latent_decode_kernel_plan(
            128, *core.k_pages.shape[2:], core.k_pages.dtype
        ) == plan
        core.spans.set(True)
        for rid, prompt, n in REQUESTS[:3]:
            core.add_request(rid, prompt=prompt, params=greedy(n))
        served[backend] = {rid: out.token_ids for rid, out in drain(core).items()}
        assert core.stats()["decode_kernel"] == plan
        dispatches = [s for s in core.spans.dump()["spans"] if s["name"] == "decode_dispatch"]
        assert dispatches
        for s in dispatches:
            if plan == "xla":
                assert s["latent_pages_visited"] in (16, 32, 48) and s["live_pages"] < 16
            else:
                assert s["latent_pages_visited"] == s["live_pages"] > 0
    assert served["pallas"] == served["xla"]
    assert all(len(ids) == n for ids, (_, _, n) in zip(served["xla"].values(), REQUESTS))


def test_kda_states_the_kernel_updates_in_place_are_served_by_it(monkeypatch):
    """A ling-shaped pattern with heads of 128 (a head's float32 state is
    whole lane tiles): with the pallas backend (interpreted here) the
    decode step updates the state rows by the one-pass kernel,
    ``stats()["kda_decode_plan"]`` says so, the tokens are the XLA
    path's, slots reused and an inactive slot beside live ones included,
    and the same requests served again give the same tokens (the
    benchmark's ``repeat_diff`` is exact). A pattern with no KDA layer
    names no plan."""
    import dataclasses

    cfg = dataclasses.replace(CFG, head_dim=128)
    params = init_params(cfg, jax.random.key(2), dtype=jnp.float32)
    requests = REQUESTS[:5]  # five through four slots
    served = {}
    for backend, plan in (("xla", "xla"), ("pallas", "inplace"), ("pallas", "inplace")):
        monkeypatch.setenv("LLMQ_ATTN_BACKEND", backend)
        core = make_core(params=params, cfg=cfg)
        assert core.stats()["kda_decode_plan"] == plan
        for rid, prompt, n in requests:
            core.add_request(rid, prompt=prompt, params=greedy(n))
        served.setdefault(backend, []).append(
            {rid: out.token_ids for rid, out in drain(core).items()}
        )
    assert served["pallas"][0] == served["xla"][0]
    assert served["pallas"][1] == served["pallas"][0]
    assert all(len(served["xla"][0][rid]) == n for rid, _, n in requests)
    # heads of 16 are not whole lane tiles: the XLA form, whatever the backend
    assert make_core().stats()["kda_decode_plan"] == "xla"
    assert "kda_decode_plan" not in make_stateless().stats()


def test_stats_name_the_plan_of_every_prefill_bucket(monkeypatch):
    """``stats()["mla_prefill_plan"]`` (a pattern with latent layers
    only) names, for every prefill bucket the engine has, the form a 1-row
    program of it takes for expanded latent attention
    (``dispatch.mla_prefill_plan``): the flash kernel where the backend is
    pallas, the rows bf16, the head sizes whole lane tiles and ``num_heads
    x bucket`` at or above the threshold; XLA's blocked form for the tiny
    heads, float32 rows, the CPU's backend, and the buckets under it; read
    once at build, so a heartbeat during a swap of the weights finds it.
    What is served under the flash plan (interpreted here) is what the XLA
    plan serves."""
    import dataclasses

    from llmq_tpu.ops import dispatch

    stateless = make_stateless()
    buckets = stateless._buckets
    assert buckets == sorted(buckets) and len(buckets) > 1
    assert stateless.stats()["mla_prefill_plan"] == {"flash": [], "xla": buckets}
    assert make_core().stats()["mla_prefill_plan"] == {"flash": [], "xla": buckets}
    json.dumps(stateless.stats()["mla_prefill_plan"])  # goes out in heartbeats
    gqa = get_preset("lfm2-moe-tiny")
    no_latent = make_core(
        params=init_params(gqa, jax.random.key(0), dtype=jnp.float32), cfg=gqa
    )
    assert "mla_prefill_plan" not in no_latent.stats()

    cfg = dataclasses.replace(
        STATELESS, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=128,
    )
    monkeypatch.setattr(dispatch, "MLA_FLASH_HEAD_TOKENS", cfg.num_heads * buckets[1])
    served = {}
    for backend, dtype, plans in (
        ("xla", jnp.bfloat16, {"flash": [], "xla": buckets}),
        ("pallas", jnp.float32, {"flash": [], "xla": buckets}),
        ("pallas", jnp.bfloat16, {"flash": buckets[1:], "xla": buckets[:1]}),
    ):
        monkeypatch.setenv("LLMQ_ATTN_BACKEND", backend)
        core = make_core(
            params=init_params(cfg, jax.random.key(1), dtype=dtype), cfg=cfg,
            kv_dtype=jnp.bfloat16,
        )
        assert core.stats()["mla_prefill_plan"] == plans
        # the benchmark swaps the weights under a live worker, whose
        # heartbeats call stats() meanwhile: nothing there reads them
        core.params, held = None, core.params
        assert core.stats()["mla_prefill_plan"] == plans
        core.params = held
        if dtype == jnp.bfloat16:
            for rid, prompt, n in REQUESTS[:3]:
                core.add_request(rid, prompt=prompt, params=greedy(n))
            served[backend] = {rid: out.token_ids for rid, out in drain(core).items()}
    assert all(len(ids) == n for ids, (_, _, n) in zip(served["pallas"].values(), REQUESTS))
    # r1 and r2 are prefilled in a bucket the kernel takes
    assert served["pallas"] == served["xla"]


@pytest.mark.parametrize(
    "mesh", [dict(tensor_parallel=2), dict(tensor_parallel=1, pipeline_parallel=2)],
    ids=["tp", "pp"],
)
def test_refuses_to_be_split_over_devices(mesh):
    with pytest.raises(ValueError, match="(tp|pp)=2 is not supported"):
        make_core(mesh=make_mesh(**mesh))


def test_refuses_quantised_weights():
    with pytest.raises(ValueError, match="layer pattern"):
        init_params(CFG, jax.random.key(0), quantize=True)
    quantised = dict(PARAMS, lm_head=qm.quantize_array(PARAMS["lm_head"], axis=-2))
    with pytest.raises(ValueError, match="quantised weights"):
        make_core(params=quantised)


def test_refuses_env_pins_of_refused_options(monkeypatch):
    monkeypatch.setenv("LLMQ_PREEMPT_MODE", "swap")
    with pytest.raises(ValueError, match="preempt_mode=swap"):
        make_core()


def test_refuses_snapshots_and_the_prefill_role():
    core = make_core()
    core.add_request("r", prompt="some prompt", params=greedy(20))
    for _ in range(3):
        core.step()
    with pytest.raises(NotImplementedError, match="extract_request"):
        core.extract_request("r")
    with pytest.raises(NotImplementedError, match="extract_all"):
        core.extract_all()
    with pytest.raises(NotImplementedError, match="insert_request"):
        core.insert_request(RequestSnapshot.__new__(RequestSnapshot))
    with pytest.raises(NotImplementedError, match="prefill role"):
        core.add_request("p", prompt="x", params=greedy(2), prefill_only=True)
    assert drain(core)["r"].completion_tokens == 20
    stateless = make_stateless()
    with pytest.raises(NotImplementedError, match="extract_all.*not built for a layer pattern"):
        stateless.extract_all()


@pytest.mark.parametrize("preset", ["ling-3.0-flash-ep4", "openpangu-ultra-moe-718b-ep16"])
def test_worker_refuses_the_disaggregated_roles(monkeypatch, preset):
    from llmq_tpu.workers.tpu_worker import TPUWorker

    monkeypatch.setenv("LLMQ_WORKER_ROLE", "prefill")
    monkeypatch.setenv("LLMQ_BROKER_URL", "memory://hybrid-role")
    worker = TPUWorker("q", model=f"preset://{preset}")
    with pytest.raises(ValueError, match="role=prefill is not supported"):
        worker._build_core()


# --- gated short-convolution layers beside GQA layers over a K/V paged pool
# (``lfm2_moe``): a tail-only state, the pool's row a token's V then K.
LFM2 = get_preset("lfm2-moe-tiny")
LFM2_PARAMS = init_params(LFM2, jax.random.key(2), dtype=jnp.float32)
LFM2_PARAMS["stack1"]["router_bias"] = 0.3 * jax.random.normal(
    jax.random.key(3), LFM2_PARAMS["stack1"]["router_bias"].shape
)


def make_lfm2(**engine) -> EngineCore:
    return make_core(params=LFM2_PARAMS, cfg=LFM2, **engine)


def direct_greedy(prompt, n, page=8, places=12):
    """Greedy tokens of the model itself: one prefill, then a decode step
    a token, through a scratch pool of its own."""
    model = build_model(LFM2)
    ids = ByteTokenizer().encode(prompt)
    bucket = max(16, 1 << (len(ids) - 1).bit_length())
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, : len(ids)] = ids
    bt = np.arange(1, places + 1, dtype=np.int32)[None]
    k, v = make_kv_pages(LFM2, places + 1, page, jnp.float32)
    logits, k, v = jax.jit(model.prefill)(
        LFM2_PARAMS, tokens, np.asarray([len(ids)], np.int32), k, v, bt
    )
    out = [int(np.argmax(logits[0]))]
    decode = jax.jit(model.decode)
    for j in range(n - 1):
        logits, k, v = decode(
            LFM2_PARAMS, np.asarray(out[-1:], np.int32),
            np.asarray([len(ids) + j], np.int32), k, v, bt, np.asarray([True]),
        )
        out.append(int(np.argmax(logits[0])))
    return out


def test_a_conv_gqa_pattern_is_served_as_the_model_itself_decodes():
    """8 requests through 4 slots (every tail row overwritten by its next
    prefill while run-ahead steps are in flight), then a pool too small
    for three (recompute preemption): every request gets the tokens of
    the direct model's own greedy loop."""
    core = make_lfm2()
    for rid, prompt, n in REQUESTS:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(core)
    assert core.stats()["prefills"] == len(REQUESTS)
    for rid, prompt, n in REQUESTS:
        assert outs[rid].token_ids == direct_greedy(prompt, n), rid
    prompts = [(f"r{i}", f"pr {i} " * 3, 14) for i in range(3)]
    small = make_lfm2(num_pages=9, page_size=4, max_model_len=48)
    for rid, prompt, n in prompts:
        small.add_request(rid, prompt=prompt, params=greedy(n))
    outs = drain(small)
    assert small.stats()["preemptions"] >= 1
    for rid, prompt, n in prompts:
        assert outs[rid].token_ids == direct_greedy(prompt, n), rid


def test_a_conv_gqa_pattern_keeps_tails_alone_beside_a_pool_of_v_and_k():
    """The first cache place holds the 2 attention layers' rows (V then K:
    2 x 2 heads x 16, kept in 128), the second the 7 conv layers' tails
    (2 rows of 64 a sequence) and an empty ``S``; the dispatch span carries
    ``state_rows`` and ``live_pages``; the plan is the paged pool's."""
    assert hybrid.count_layers(LFM2, "gqa") == 2 and hybrid.count_layers(LFM2, "conv") == 7
    assert cache.paged_rank(LFM2) == 32
    assert cache.state_leaves(LFM2, 129, jnp.bfloat16)["conv"][0][-1] == 64
    assert sum(cache.state_bytes(LFM2, 129, jnp.bfloat16).values()) == 129 * 7 * 2 * 64 * 2
    core = make_lfm2()
    assert core.cache.state_kind == "conv" and core.cache.state_rows == 5
    assert core.k_pages.shape == (2, 60, 8, 128)
    assert core.v_pages["S"].size == 0
    assert core.v_pages["conv"].shape == (7, 5, 2, 64)
    core.spans.set(True)
    for rid, prompt, n in REQUESTS[:3]:
        core.add_request(rid, prompt=prompt, params=greedy(n))
    drain(core)
    stats = core.stats()
    assert stats["decode_kernel"] == "xla"
    steps = stats["decode_steps"]
    # 8 expert layers, 4 slots a step, 4 experts a token, all 8 held
    assert 0 < stats["moe_assignments_held"] <= steps * 8 * 4 * 4
    assert 0 < stats["moe_experts_hit"] <= steps * 8 * 8
    dispatches = [s for s in core.spans.dump()["spans"] if s["name"] == "decode_dispatch"]
    assert dispatches and all(1 <= s["state_rows"] <= 3 for s in dispatches)
    assert all(s["latent_pages_visited"] >= s["live_pages"] > 0 for s in dispatches)


@pytest.mark.parametrize("option, named", REFUSED_AT_BUILD.values(), ids=REFUSED_AT_BUILD.keys())
def test_a_conv_gqa_pattern_is_refused_for_the_reason_that_holds(option, named):
    with pytest.raises(ValueError, match="layer pattern") as refused:
        make_lfm2(**option)
    assert str(refused.value).startswith(named)
    assert "per-sequence convolution tail cannot be shared by a prefix" in str(refused.value)
    assert "KDA" not in str(refused.value) and "latent" not in str(refused.value)


def test_a_conv_gqa_pattern_refuses_snapshots_by_the_same_reason():
    core = make_lfm2()
    with pytest.raises(NotImplementedError, match="extract_all.*convolution tail"):
        core.extract_all()
    with pytest.raises(NotImplementedError, match="prefill role.*convolution tail"):
        core.add_request("p", prompt="x", params=greedy(2), prefill_only=True)


def test_worker_refuses_the_disaggregated_roles_for_a_conv_gqa_pattern(monkeypatch):
    from llmq_tpu.workers.tpu_worker import TPUWorker

    monkeypatch.setenv("LLMQ_WORKER_ROLE", "prefill")
    monkeypatch.setenv("LLMQ_BROKER_URL", "memory://hybrid-role")
    worker = TPUWorker("q", model="preset://lfm2-24b-a2b-pp5")
    with pytest.raises(ValueError, match="role=prefill is not supported"):
        worker._build_core()


def test_a_uniform_engine_imports_nothing_of_a_layer_pattern():
    """A ``qwen2``-like engine, built, served and asked for its stats in a
    process of its own, has imported neither ``models/hybrid`` nor
    ``ops/delta_rule``: nothing a layer pattern needs is on its path
    (they are imported where ``layer_pattern`` is not None)."""
    script = """
import json, sys
import jax, jax.numpy as jnp
from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.presets import get_preset
from llmq_tpu.models.transformer import init_params
from llmq_tpu.parallel import make_mesh
cfg = get_preset("tiny")
core = EngineCore(
    cfg, init_params(cfg, jax.random.key(0), dtype=jnp.float32), ByteTokenizer(),
    mesh=make_mesh(tensor_parallel=1),
    engine_config=EngineConfig(max_num_seqs=2, max_model_len=64, page_size=8,
                               num_pages=20, kv_dtype=jnp.float32),
)
core.add_request("a", prompt="hello", params=SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True))
while core.has_work:
    core.step()
core.stats()
print(json.dumps(sorted(m for m in sys.modules if m.startswith("llmq_tpu"))))
"""
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    modules = json.loads(out.stdout.strip().splitlines()[-1])
    assert "llmq_tpu.engine.engine" in modules
    assert not [m for m in modules if m.endswith((".hybrid", ".delta_rule"))]
