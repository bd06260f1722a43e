"""End-to-end probe of the device-fault containment layer.

Three legs, each printing a ``probe: <leg> ok`` line:

1. **hang** — a decode dispatch wedges (injected sleep past the
   watchdog deadline): the watchdog detects it from the side thread,
   the recovery path rebuilds the EngineCore in-process, every request
   restores from its snapshot, and greedy output is token-identical to
   a fault-free run.
2. **oom-ladder** — HBM allocation failures degrade in ladder order
   (demote prefix pages, shrink run-ahead, preempt-with-swap) before
   any rebuild: a fresh engine absorbs its first OOM on the
   run-ahead rung with zero rebuilds and fault-free parity.
3. **xla-error** — a classified XLA runtime error mid-decode rebuilds
   the engine; the recovery event records the snapshot-restore vs
   republish split (everything restorable restores; nothing requeues).

Runs on CPU (preflight) and on device (hardware_session rungs)
identically — faults are injected via the engine's dispatch hook.

    python tools/engine_fault_probe.py
"""

import asyncio
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from llmq_tpu.broker.chaos import DeviceFaultInjector
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
        (f"r{i}", "fault probe " + "ab " * (i + 1)) for i in range(N_JOBS)
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
    return outs


async def drive_through_fault(engine: AsyncEngine) -> dict:
    results = await asyncio.gather(
        *(
            engine.generate(rid=rid, prompt=prompt, params=sampling())
            for rid, prompt in probe_jobs()
        )
    )
    return {out.rid: list(out.token_ids) for out in results}


def check_parity(outs: dict, baseline: dict, leg: str) -> None:
    assert set(outs) == set(baseline), (
        f"{leg}: result set {sorted(outs)} != {sorted(baseline)}"
    )
    for rid, tokens in baseline.items():
        assert outs[rid] == tokens, (
            f"{leg}: {rid} diverged from the fault-free run"
        )


async def run_hang_leg(baseline: dict):
    # Deadline = max(2.0, p99 * 2): a first dispatch that reads its
    # program from the compile cache (main) stays under it, the injected
    # 4.5 s sleep does not.
    make = lambda: build_core(watchdog_mult=2.0, watchdog_min_s=2.0)  # noqa: E731
    engine = AsyncEngine(make())
    engine.rebuild_core = make
    injector = DeviceFaultInjector(
        "decode", "hang", seed=7, after_range=(2, 4), hang_s=4.5
    )
    engine.core.on_dispatch = injector
    try:
        outs = await drive_through_fault(engine)
    finally:
        engine.shutdown()
    assert injector.fired, "hang: no decode dispatch matched"
    assert engine.watchdog_trips == 1, (
        f"hang: watchdog_trips={engine.watchdog_trips}, want 1"
    )
    assert engine.engine_rebuilds == 1, (
        f"hang: engine_rebuilds={engine.engine_rebuilds}, want 1"
    )
    assert engine.last_fault_reason == "hung_dispatch"
    check_parity(outs, baseline, "hang")
    print(
        "probe: hang leg ok — watchdog tripped once, one in-process "
        f"rebuild, {len(outs)} results token-identical to fault-free"
    )


async def run_oom_ladder_leg(baseline: dict):
    engine = AsyncEngine(build_core())
    engine.rebuild_core = build_core
    injector = DeviceFaultInjector("decode", "oom", seed=8, after_range=(2, 4))
    engine.core.on_dispatch = injector
    try:
        outs = await drive_through_fault(engine)
        stats = engine.stats()
    finally:
        engine.shutdown()
    assert injector.fired, "oom: no decode dispatch matched"
    assert engine.engine_rebuilds == 0, (
        "oom: ladder should absorb the first fault without a rebuild, "
        f"got {engine.engine_rebuilds} rebuild(s)"
    )
    assert stats.get("hbm_oom_events") == 1, stats.get("hbm_oom_events")
    # No prefix cold tier on this core, so the first live rung is the
    # run-ahead shrink; preempt-with-swap stays in reserve.
    assert stats.get("oom_degradations") == ["shrink_runahead"], (
        stats.get("oom_degradations")
    )
    check_parity(outs, baseline, "oom")

    # Ladder ORDER, driven directly: with the pipeline live the rungs
    # must come out shrink_runahead -> preempt_swap -> dry (no prefix
    # store configured), never reordered, never repeating a rung.
    core = build_core()
    for rid, prompt in probe_jobs():
        core.add_request(rid, prompt=prompt, params=sampling())
    for _ in range(4):
        core.step()
    rungs = [core.degrade_for_oom() for _ in range(3)]
    core.stop_watchdog()
    assert rungs == ["shrink_runahead", "preempt_swap", None], rungs
    print(
        "probe: oom-ladder leg ok — first fault absorbed on the "
        "run-ahead rung (0 rebuilds, parity held); direct ladder order "
        "shrink_runahead -> preempt_swap -> dry"
    )


async def run_xla_error_leg(baseline: dict):
    engine = AsyncEngine(build_core())
    engine.rebuild_core = build_core
    injector = DeviceFaultInjector(
        "decode", "xla_error", seed=9, after_range=(2, 4)
    )
    engine.core.on_dispatch = injector
    try:
        outs = await drive_through_fault(engine)
        # The rebuild event records the snapshot-recover vs republish
        # split; every row here snapshots cleanly, so nothing requeues.
        events = [
            (name, fields)
            for rid, _ in probe_jobs()
            for name, _t, fields in engine.pop_fault_events(rid)
        ]
    finally:
        engine.shutdown()
    assert injector.fired, "xla: no decode dispatch matched"
    assert engine.engine_rebuilds == 1, (
        f"xla: engine_rebuilds={engine.engine_rebuilds}, want 1"
    )
    assert engine.last_fault_reason == "xla_runtime_error"
    check_parity(outs, baseline, "xla")
    rebuilt = [f for name, f in events if name == "engine_rebuilt"]
    assert rebuilt, "xla: no engine_rebuilt fault event recorded"
    restored = rebuilt[0].get("restored", 0)
    requeued = rebuilt[0].get("requeued", 0)
    assert restored >= 1 and requeued == 0, (restored, requeued)
    faults = [f for name, f in events if name == "device_fault"]
    assert faults and faults[0].get("reason") == "xla_runtime_error"
    print(
        "probe: xla-error leg ok — classified xla_runtime_error, one "
        f"rebuild, {restored} restored from snapshots / {requeued} "
        "republished, parity held"
    )


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    with tempfile.TemporaryDirectory(prefix="llmq_fault_probe_") as scratch:
        # A rebuilt core compiles its programs again. A worker's reads
        # them from the persistent cache; a CPU run has none, and there a
        # prefill's compile outlasts the hang leg's 2 s floor, so that the
        # rebuilt engine trips in its turn, without end. So a CPU run gets
        # a cache for the length of the probe, which the baseline warms.
        if enable_compile_cache() is None:  # before the first compile
            jax.config.update("jax_compilation_cache_dir", scratch)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        baseline = run_baseline()
        asyncio.run(run_hang_leg(baseline))
        asyncio.run(run_oom_ladder_leg(baseline))
        asyncio.run(run_xla_error_leg(baseline))
    print("metric: engine_fault_probe_ok legs=3")


if __name__ == "__main__":
    main()
