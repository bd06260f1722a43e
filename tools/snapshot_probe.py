"""End-to-end probe of the durable-request-state plane.

Three legs, each printing a ``probe: <leg> ok`` line:

1. **roundtrip** — extract a request mid-decode, serialize → base64 →
   deserialize (digest-verified wire form), insert into a FRESH engine,
   and assert the greedy continuation is bit-identical to a run that was
   never interrupted.
2. **swap** — tight KV pool forcing pool-exhaustion preemption; swap-to-
   host mode (restore from captured snapshot) must produce exactly the
   recompute-mode tokens while the swap path measurably engages.
3. **kill-resume** — seeded mini-chaos on the memory broker: a TPU worker
   is killed mid-decode via the engine dispatch hook (SIGTERM drain-with-
   handoff), a second worker resumes the handed-off snapshots, and every
   job yields exactly one result, token-identical to a kill-free fleet.

Runs on CPU (preflight) and on device (hardware_session rungs)
identically — snapshots are host-side state either way.

    python tools/snapshot_probe.py
"""

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.snapshot import snapshot_from_b64, snapshot_to_b64
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.parallel import make_mesh

CFG = ModelConfig.tiny(vocab_size=304)


def make_core(**overrides):
    defaults = dict(
        max_num_seqs=4, max_model_len=64, page_size=8, num_pages=40,
        kv_dtype=jnp.float32, min_prefill_bucket=16,
    )
    defaults.update(overrides)
    return EngineCore(
        CFG,
        init_params(CFG, jax.random.key(0), dtype=jnp.float32),
        ByteTokenizer(),
        mesh=make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(**defaults),
    )


def greedy(max_tokens):
    return SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True
    )


def run_all(core, requests):
    for rid, prompt, params in requests:
        core.add_request(rid, prompt=prompt, params=params)
    outs = {}
    for _ in range(2000):
        for out in core.step():
            outs[out.rid] = out
        if not core.has_work:
            break
    assert len(outs) == len(requests), "engine stalled"
    return outs


def run_roundtrip_leg():
    prompt = "snapshot probe request"
    baseline = run_all(make_core(), [("r0", prompt, greedy(16))])["r0"]

    src = make_core()
    src.add_request("r0", prompt=prompt, params=greedy(16))
    for _ in range(2000):
        src.step()
        seq = src.scheduler.running.get("r0")
        if seq is not None and len(seq.output_ids) >= 5:
            break
    snap = src.extract_request("r0")
    assert snap.kv_valid > 0, "extract captured no KV mid-decode"
    wire = snapshot_to_b64(snap)
    dst = make_core()
    dst.insert_request(snapshot_from_b64(wire))
    outs = {}
    for _ in range(2000):
        for out in dst.step():
            outs[out.rid] = out
        if not dst.has_work:
            break
    assert outs["r0"].token_ids == baseline.token_ids, (
        f"continuation diverged: {baseline.token_ids} -> "
        f"{outs['r0'].token_ids}"
    )
    print(
        f"probe: roundtrip leg ok — {len(wire)} b64 chars, "
        f"{snap.kv_valid} KV positions, bit-identical continuation"
    )


def run_swap_leg():
    tight = dict(num_pages=11, max_num_seqs=3, max_model_len=96)
    reqs = [
        (f"s{i}", "hello request %d " % i + "ab" * (4 * i), greedy(30))
        for i in range(3)
    ]
    rec = make_core(preempt_mode="recompute", **tight)
    rec_outs = run_all(rec, list(reqs))
    assert rec.scheduler.preemptions > 0, "pool not tight enough"
    swap = make_core(preempt_mode="swap", **tight)
    swap_outs = run_all(swap, list(reqs))
    assert swap.swap_preempts > 0, "swap path never engaged"
    for rid, _, _ in reqs:
        assert swap_outs[rid].token_ids == rec_outs[rid].token_ids, (
            f"{rid}: swap diverged from recompute"
        )
    print(
        f"probe: swap leg ok — {swap.swap_preempts} swap preempts, "
        f"{swap.kv_restores} restores, recompute parity"
    )


async def run_kill_resume_leg():
    from llmq_tpu.broker.chaos import WorkerKillSwitch
    from llmq_tpu.broker.manager import BrokerManager
    from llmq_tpu.core.config import Config
    from llmq_tpu.core.models import Job
    from llmq_tpu.workers.tpu_worker import TPUWorker

    def worker_for(ns, queue):
        return TPUWorker(
            queue,
            config=Config(
                broker_url=f"memory://{ns}", max_redeliveries=1000
            ),
            concurrency=8,
            model="preset://tiny",
            tensor_parallel=1,
            max_model_len=96,
            num_pages=64,
            page_size=8,
            dtype="float32",
            max_num_seqs=4,
        )

    jobs = [
        Job(
            id=f"c{i}",
            prompt="chaos probe " + "cd " * (i + 1),
            temperature=0.0,
            max_tokens=24,
            ignore_eos=True,
        )
        for i in range(4)
    ]

    async def collect(mgr, queue, want):
        payloads, quiet = [], None
        deadline = asyncio.get_running_loop().time() + 300.0
        while True:
            msg = await mgr.broker.get(queue)
            if msg is not None:
                payloads.append(json.loads(msg.body))
                await msg.ack()
                quiet = None
                continue
            now = asyncio.get_running_loop().time()
            if want <= {p["id"] for p in payloads}:
                if quiet is None:
                    quiet = now + 1.0
                elif now >= quiet:
                    return payloads
            else:
                assert now < deadline, "results missing"
            await asyncio.sleep(0.05)

    want = {j.id for j in jobs}

    # Kill-free fleet: the parity reference.
    async with BrokerManager(
        Config(broker_url="memory://snap-probe-base", max_redeliveries=1000)
    ) as mgr:
        await mgr.setup_queue_infrastructure("pq")
        for j in jobs:
            await mgr.publish_job("pq", j)
        ref_worker = worker_for("snap-probe-base", "pq")
        task = asyncio.ensure_future(ref_worker.run())
        try:
            baseline = {
                p["id"]: p["result"]
                for p in await collect(mgr, "pq.results", want)
            }
        finally:
            ref_worker.request_shutdown()
            await asyncio.wait_for(task, timeout=120.0)

    # Chaos fleet: worker 1 dies on an early decode dispatch, worker 2
    # resumes the handoffs. Worker 1 is driven manually (initialize +
    # consume, no run() loop) so the drain starts the instant the kill
    # switch fires — the run loop's 1 s poll would let fast CPU decodes
    # finish before anything could be handed off.
    async with BrokerManager(
        Config(broker_url="memory://snap-probe", max_redeliveries=1000)
    ) as mgr:
        await mgr.setup_queue_infrastructure("pq")
        for j in jobs:
            await mgr.publish_job("pq", j)
        w1 = worker_for("snap-probe", "pq")
        switch = WorkerKillSwitch(
            "decode", w1.request_shutdown, seed=3, after_range=(1, 2)
        )
        orig_build = w1._build_engine

        def build_with_switch():
            engine = orig_build()
            engine.core.on_dispatch = switch
            return engine

        w1._build_engine = build_with_switch
        await w1.initialize()
        w1.running = True
        w1._consumer_tag = await w1.broker.consume_jobs(
            "pq", w1._process_message, prefetch=w1.concurrency
        )
        kill_deadline = asyncio.get_running_loop().time() + 120.0
        while w1.running:
            assert (
                asyncio.get_running_loop().time() < kill_deadline
            ), "kill switch never fired"
            await asyncio.sleep(0.01)
        await w1.shutdown()
        assert switch.fired, "kill switch never fired"

        w2 = worker_for("snap-probe", "pq")
        t2 = asyncio.ensure_future(w2.run())
        try:
            payloads = await collect(mgr, "pq.results", want)
        finally:
            w2.request_shutdown()
            await asyncio.wait_for(t2, timeout=120.0)

    ids = [p["id"] for p in payloads]
    assert sorted(ids) == sorted(set(ids)), f"duplicate results: {ids}"
    assert set(ids) == want, f"wrong result set: {ids}"
    for p in payloads:
        assert p["result"] == baseline[p["id"]], (
            f"{p['id']}: kill-resume output diverged from kill-free run"
        )
    resumed = sum(1 for p in payloads if p.get("resume_offset", 0) > 0)
    assert resumed > 0, "no job resumed from a snapshot (all re-prefilled?)"
    print(
        f"probe: kill-resume leg ok — {len(payloads)} results, "
        f"0 duplicates, {resumed} resumed mid-stream, kill-free parity"
    )


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    run_roundtrip_leg()
    run_swap_leg()
    asyncio.run(run_kill_resume_leg())
    print("metric: snapshot_probe_ok legs=3")


if __name__ == "__main__":
    main()
