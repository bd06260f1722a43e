"""The engine serving a model with a layer pattern (``models/hybrid.py``):
slots and their state rows reused while run-ahead steps are in flight,
what it refuses at build, and its counters. CPU, tiny widths."""

import jax
import jax.numpy as jnp
import pytest

from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.snapshot import RequestSnapshot
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models import quant as qm
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
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


def make_core(params=PARAMS, mesh=None, **engine) -> EngineCore:
    options = dict(
        max_num_seqs=4, max_model_len=96, page_size=8, num_pages=60,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    options.update(engine)
    return EngineCore(
        CFG, params, ByteTokenizer(), mesh=mesh or make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**options),
    )


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


def test_worker_refuses_the_disaggregated_roles(monkeypatch):
    from llmq_tpu.workers.tpu_worker import TPUWorker

    monkeypatch.setenv("LLMQ_WORKER_ROLE", "prefill")
    monkeypatch.setenv("LLMQ_BROKER_URL", "memory://hybrid-role")
    worker = TPUWorker("q", model="preset://ling-3.0-flash-ep4")
    with pytest.raises(ValueError, match="role=prefill is not supported"):
        worker._build_core()
