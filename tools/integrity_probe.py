"""End-to-end probe of the silent-data-corruption defense layer.

Three legs, each printing a ``probe: <leg> ok`` line:

1. **guard-trip** — a NaN is flipped into the lm_head mid-run with the
   on-device logit guard armed: the guard flags the dispatch (no extra
   host sync), the failure classifies as ``numerical_fault``, the
   engine rebuilds on pristine weights, and greedy output is
   token-identical to a fault-free run.
2. **weight-audit** — a finite (guard-invisible) bit-flip corrupts a
   weight shard: the digest audit names the corrupted leaf against the
   build-time baseline, the KV spot-check stays clean, and the core
   reports integrity "suspect".
3. **canary** — the deterministic golden-prompt self-test: it passes on
   a clean core, then a NaN weight flip makes the replay diverge from
   the golden tokens and the failure is counted.

Runs on CPU (preflight) and on device (hardware_session rungs)
identically — corruption is injected via the engine's dispatch hook.

    python tools/integrity_probe.py
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from llmq_tpu.broker.chaos import BitFlipInjector
from llmq_tpu.core.faults import FAULT_NUMERICAL
from llmq_tpu.engine.engine import AsyncEngine, EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.presets import get_preset
from llmq_tpu.models.transformer import init_params
from llmq_tpu.parallel import make_mesh

N_JOBS = 6
MAX_TOKENS = 24

_model_config = get_preset("tiny")
_params = init_params(_model_config, jax.random.key(0), dtype=jnp.float32)


def build_core(**overrides) -> EngineCore:
    cfg = EngineConfig(
        max_num_seqs=4,
        max_model_len=96,
        page_size=8,
        num_pages=64,
        kv_dtype=jnp.float32,
        **overrides,
    )
    return EngineCore(
        _model_config,
        _params,
        ByteTokenizer(),
        mesh=make_mesh(tensor_parallel=1),
        engine_config=cfg,
    )


def probe_jobs():
    return [
        (f"r{i}", "integrity probe " + "ab " * (i + 1)) for i in range(N_JOBS)
    ]


def sampling():
    return SamplingParams(
        max_tokens=MAX_TOKENS, temperature=0.0, ignore_eos=True
    )


def run_baseline() -> dict:
    """Fault-free greedy tokens, computed once on a plain core."""
    core = build_core()
    for rid, prompt in probe_jobs():
        core.add_request(rid, prompt=prompt, params=sampling())
    outs = {}
    while core.has_work:
        for out in core.step():
            outs[out.rid] = list(out.token_ids)
    core.stop_watchdog()
    return outs


def check_parity(outs: dict, baseline: dict, leg: str) -> None:
    assert set(outs) == set(baseline), (
        f"{leg}: result set {sorted(outs)} != {sorted(baseline)}"
    )
    for rid, tokens in baseline.items():
        assert outs[rid] == tokens, (
            f"{leg}: {rid} diverged from the fault-free run"
        )


async def run_guard_trip_leg(baseline: dict):
    make = lambda: build_core(logit_guard="on")  # noqa: E731
    engine = AsyncEngine(make())
    engine.rebuild_core = make
    # Transient corruption: the rebuild reloads pristine params, so the
    # suspect request re-runs clean and is device-blamed, not poisoned.
    injector = BitFlipInjector(
        "logit", mode="nan", seed=7, after_range=(2, 4)
    ).bind(engine.core)
    try:
        outs = {
            out.rid: list(out.token_ids)
            for out in await asyncio.gather(
                *(
                    engine.generate(rid=rid, prompt=prompt, params=sampling())
                    for rid, prompt in probe_jobs()
                )
            )
        }
    finally:
        engine.shutdown()
    assert injector.fired, "guard: no dispatch matched the injector"
    assert engine.engine_rebuilds == 1, (
        f"guard: engine_rebuilds={engine.engine_rebuilds}, want 1"
    )
    assert engine.last_fault_reason == FAULT_NUMERICAL, (
        engine.last_fault_reason
    )
    check_parity(outs, baseline, "guard")
    print(
        "probe: guard-trip leg ok — NaN logits classified as "
        f"numerical_fault, one rebuild, {len(outs)} results "
        "token-identical to fault-free"
    )


def run_weight_audit_leg():
    core = build_core(weight_audit_every=600.0)
    # Finite corruption: invisible to the logit guard (no NaN, bounded
    # magnitude) — exactly the class only the digest audit catches.
    injector = BitFlipInjector(
        "weight", mode="flip", seed=8, after_range=(1, 2)
    ).bind(core)
    for rid, prompt in probe_jobs():
        core.add_request(rid, prompt=prompt, params=sampling())
    while core.has_work:
        core.step()
    assert injector.fired, "audit: no dispatch matched the injector"
    mismatched = core.audit_weights()
    assert mismatched, "audit: digest sweep missed the corrupted leaf"
    spots = core.kv_spot_check()
    assert spots == [], f"audit: KV spot-check false positive: {spots}"
    assert core.weight_audit_mismatches >= 1
    assert core.integrity_status() == "suspect", core.integrity_status()
    core.stop_watchdog()
    print(
        "probe: weight-audit leg ok — flipped shard named by the digest "
        f"sweep ({mismatched[0]}), KV pages read-stable, status suspect"
    )


def run_canary_leg():
    core = build_core(canary_every=600.0)
    assert core._canary_golden, "canary: no golden recorded at build"
    assert core.run_canary(), "canary: clean replay failed"
    injector = BitFlipInjector(
        "logit", mode="nan", seed=9, after_range=(1, 1)
    ).bind(core)
    ok = core.run_canary()
    assert injector.fired, "canary: replay fired no dispatches"
    assert not ok, "canary: corrupted replay still matched the golden"
    assert core.canary_failures >= 1
    assert core.integrity_status() == "suspect", core.integrity_status()
    core.stop_watchdog()
    print(
        "probe: canary leg ok — clean replay bit-exact, NaN-corrupted "
        "replay diverged from golden and was counted"
    )


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    baseline = run_baseline()
    asyncio.run(run_guard_trip_leg(baseline))
    run_weight_audit_leg()
    run_canary_leg()
    print("metric: integrity_probe_ok legs=3")


if __name__ == "__main__":
    main()
