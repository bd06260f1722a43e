"""End-to-end probe of the fleet-wide prefix-cache plane.

Three legs, each printing a ``probe: <leg> ok`` line:

1. **reuse** — intra-engine reuse: templated prompts through a
   prefix-caching engine must register page hits and skip prefill
   positions while staying greedy-bit-identical to a cache-free engine.
2. **host-tier** — demote→promote: flush the device cache to the
   host-RAM cold tier, then admit a prompt walking the same chain; the
   promoted pages must reproduce a cold prefill's tokens exactly.
3. **ship** — cross-worker: worker A builds pages from templated
   traffic and advertises them; worker B fetches the missing pages over
   the memory broker, lands them in its host tier, and serves the job
   with promoted (not recomputed) KV — token-identical to A.

Runs on CPU (preflight) and on device (hardware_session rungs)
identically — the KV gathers/scatters go through the same dispatch ops
either way.

    python tools/prefix_cache_probe.py
"""

import asyncio
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.parallel import make_mesh

CFG = ModelConfig.tiny(vocab_size=304)

# ≥256 chars so text_prefix_chain yields a digest for affinity routing
# in the ship leg; the engine legs only need the shared token prefix.
TEMPLATE = ("SYSTEM: you are a careful assistant. " * 8)[:280]


def make_core(**overrides):
    defaults = dict(
        max_num_seqs=4, max_model_len=512, page_size=8, num_pages=120,
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


def run_reuse_leg():
    reqs = [
        (f"r{i}", TEMPLATE + f" question {i}", greedy(12)) for i in range(3)
    ]
    plain = make_core()
    base = {}
    for req in reqs:  # sequential, same order as the cached run
        base.update(run_all(plain, [req]))
    cached = make_core(enable_prefix_caching=True, prefill_chunk_size=8)
    outs = {}
    for req in reqs:
        outs.update(run_all(cached, [req]))
    for rid, _, _ in reqs:
        assert outs[rid].token_ids == base[rid].token_ids, (
            f"{rid}: cached run diverged from cache-free run"
        )
    assert cached.scheduler.prefix_hits > 0, "no page ever hit"
    saved = plain.prefill_tokens - cached.prefill_tokens
    assert saved > 0, "cache skipped no prefill positions"
    print(
        f"probe: reuse leg ok — {cached.scheduler.prefix_hits} page hits, "
        f"{saved} prefill positions skipped, cache-free parity"
    )


def run_host_tier_leg():
    warm_prompt = TEMPLATE + " second visitor"
    base = run_all(make_core(), [("h1", warm_prompt, greedy(12))])["h1"]
    core = make_core(
        enable_prefix_caching=True, prefill_chunk_size=8,
        prefix_host_gb=0.05,
    )
    run_all(core, [("h0", TEMPLATE + " first visitor", greedy(12))])
    dropped = core.flush_prefix_to_host()
    assert dropped > 0, "nothing demoted — device cache was empty"
    assert len(core.prefix_store) > 0 and core.prefix_demotes > 0
    outs = run_all(core, [("h1", warm_prompt, greedy(12))])
    assert core.prefix_promotes > 0, "host tier never promoted"
    assert outs["h1"].token_ids == base.token_ids, (
        "promoted pages diverged from a cold prefill"
    )
    print(
        f"probe: host-tier leg ok — {dropped} pages demoted, "
        f"{core.prefix_promotes} promoted, cold-prefill parity"
    )


async def run_ship_leg():
    from llmq_tpu.broker.manager import BrokerManager, job_affinity_text
    from llmq_tpu.core.config import Config
    from llmq_tpu.core.models import Job

    queue = "pfx-q"

    def worker_for():
        from llmq_tpu.workers.tpu_worker import TPUWorker

        return TPUWorker(
            queue,
            config=Config(
                broker_url="memory://pfx-probe", prefix_affinity=True
            ),
            concurrency=4,
            model="preset://tiny",
            tensor_parallel=1,
            max_model_len=512,
            num_pages=120,
            page_size=8,
            dtype="float32",
            max_num_seqs=4,
            prefill_chunk_size=8,
            enable_prefix_caching=True,
            prefix_host_gb=0.05,
        )

    def job_for(rid, tail):
        return Job(
            id=rid, prompt=TEMPLATE + tail, temperature=0.0,
            max_tokens=8, ignore_eos=True,
        )

    mgr = BrokerManager(
        Config(broker_url="memory://pfx-probe", prefix_affinity=True)
    )
    await mgr.connect()
    await mgr.setup_queue_infrastructure(queue)
    worker_a = worker_for()
    task_a = asyncio.ensure_future(worker_a.run())
    worker_b = None
    try:
        deadline = asyncio.get_running_loop().time() + 300.0
        while worker_a._kv_consumer_tag is None:
            assert (
                asyncio.get_running_loop().time() < deadline
            ), "worker A never started its kv-fetch server"
            await asyncio.sleep(0.05)
        jobs = [job_for(f"warm-{i}", f" item {i}") for i in range(2)]
        for job in jobs:
            await mgr.publish_job(queue, job)
        got = set()
        while got < {j.id for j in jobs}:
            assert (
                asyncio.get_running_loop().time() < deadline
            ), "warm jobs never finished on A"
            msg = await mgr.broker.get(queue + ".results")
            if msg is None:
                await asyncio.sleep(0.05)
                continue
            import json as _json

            got.add(_json.loads(msg.body)["id"])
            await msg.ack()
        assert worker_a._prefix_chains(), "A advertises no chains"
        await worker_a._publish_heartbeat()

        worker_b = worker_for()
        # Same process as A: disambiguate the host-pid-derived worker id
        # BEFORE the queues keyed on it are declared.
        worker_b.worker_id = worker_b.worker_id + "-b"
        await worker_b.initialize()
        await worker_b._start_extra_consumers()
        store_b = worker_b.engine.core.prefix_store
        assert store_b is not None and len(store_b) == 0
        job = job_for("cold-on-b", " item 99")
        await worker_b._maybe_fetch_prefix(job, job_affinity_text(job))
        assert worker_b.prefix_chunks_fetched > 0, "B fetched nothing"
        assert worker_a.prefix_chunks_served >= worker_b.prefix_chunks_fetched
        out_b = await worker_b._process_job(job)
        assert worker_b.engine.core.prefix_promotes > 0, (
            "shipped pages never promoted — B recomputed the prefix"
        )
        # Token parity across workers: A (holding the original pages)
        # must answer the same prompt identically to B (holding only
        # the shipped copies).
        out_a = await worker_a._process_job(job_for("ref-99", " item 99"))
        assert out_b == out_a, "shipped-page output diverged from A"
        print(
            f"probe: ship leg ok — {worker_b.prefix_chunks_fetched} chunks "
            f"shipped A->B, {worker_b.engine.core.prefix_promotes} promoted, "
            "cross-worker parity"
        )
    finally:
        if worker_b is not None:
            await worker_b.shutdown()
        worker_a.request_shutdown()
        await asyncio.wait_for(task_a, timeout=120.0)
        await mgr.disconnect()


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    run_reuse_leg()
    run_host_tier_leg()
    asyncio.run(run_ship_leg())
    print("metric: prefix_cache_probe_ok legs=3")


if __name__ == "__main__":
    main()
