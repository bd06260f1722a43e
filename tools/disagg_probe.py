"""End-to-end probe of the disaggregated prefill/decode plane.

Three legs, each printing a ``probe: <leg> ok`` line:

1. **handoff** — a prefill-role worker and a decode-role worker split a
   unified fleet's job: prompt KV ships over the ``<q>.kv.<peer>``
   adoption handshake (the decode peer's heartbeat is awaited first, so
   the ship path is actually exercised), the decode side adopts and
   samples from the re-derived key chain — greedy output bit-identical
   to a single unified worker.
2. **fallback** — the same jobs with NO decode peer alive at handoff
   time: every prefill-complete job takes the snapshot-fallback
   republish onto ``<q>.decode``; a decode worker started afterwards
   drains the pool with the same unified parity.
3. **autoswitch** — an ``auto``-role worker under synthetic depth skew
   (dwell and check-interval zeroed): a decode-pool backlog flips it
   prefill -> decode, and after the pool drains a shared-queue backlog
   flips it back, with both queues fully served across the switches.

Runs on CPU (preflight) and on device (hardware_session rungs)
identically — the handshake and snapshot wire forms are host-side
either way.

    python tools/disagg_probe.py
"""

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from llmq_tpu.broker.manager import BrokerManager, decode_queue_name
from llmq_tpu.core.config import Config
from llmq_tpu.core.models import Job

QUEUE = "pq"


def probe_jobs():
    return [
        Job(
            id=f"d{i}",
            prompt="disagg probe " + "ab " * (i + 1),
            temperature=0.0,
            max_tokens=24,
            ignore_eos=True,
        )
        for i in range(6)
    ]


def worker_for(ns, queue, role):
    from llmq_tpu.workers.tpu_worker import TPUWorker

    w = TPUWorker(
        queue,
        config=Config(
            broker_url=f"memory://{ns}",
            max_redeliveries=1000,
            worker_role=role,
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
    # Same host + pid => same generated id; disambiguate per role or the
    # prefill side discards the decode peer as "itself" and every
    # handoff silently takes the snapshot fallback.
    w.worker_id = f"{w.worker_id}-{role}"
    return w


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


def assert_parity(payloads, want, baseline, leg):
    ids = [p["id"] for p in payloads]
    assert sorted(ids) == sorted(set(ids)), f"{leg}: duplicate results: {ids}"
    assert set(ids) == want, f"{leg}: wrong result set: {ids}"
    for p in payloads:
        assert p["result"] == baseline[p["id"]], (
            f"{leg}: job {p['id']} diverged from the unified run"
        )


async def unified_baseline(jobs, want):
    """The parity reference: one unified worker serving the same jobs."""
    async with BrokerManager(
        Config(broker_url="memory://disagg-probe-base", max_redeliveries=1000)
    ) as mgr:
        await mgr.setup_queue_infrastructure(QUEUE)
        for j in jobs:
            await mgr.publish_job(QUEUE, j)
        w = worker_for("disagg-probe-base", QUEUE, "unified")
        task = asyncio.ensure_future(w.run())
        try:
            return {
                p["id"]: p["result"]
                for p in await collect(mgr, QUEUE + ".results", want)
            }
        finally:
            w.request_shutdown()
            await asyncio.wait_for(task, timeout=120.0)


async def run_handoff_leg(jobs, want, baseline):
    ns = "disagg-probe-ship"
    async with BrokerManager(
        Config(broker_url=f"memory://{ns}", max_redeliveries=1000)
    ) as mgr:
        await mgr.setup_queue_infrastructure(QUEUE)
        wd = worker_for(ns, QUEUE, "decode")
        td = asyncio.ensure_future(wd.run())
        # The prefill side discovers decode peers from heartbeats; wait
        # for the decode worker's first beat so the offer handshake (not
        # the snapshot fallback) carries the KV.
        deadline = asyncio.get_running_loop().time() + 120.0
        while not any(
            h.role == "decode"
            for h in (await mgr.get_worker_health(QUEUE)).values()
        ):
            assert (
                asyncio.get_running_loop().time() < deadline
            ), "decode heartbeat never appeared"
            await asyncio.sleep(0.1)
        wp = worker_for(ns, QUEUE, "prefill")
        tp = asyncio.ensure_future(wp.run())
        for j in jobs:
            await mgr.publish_job(QUEUE, j)
        try:
            payloads = await collect(mgr, QUEUE + ".results", want)
        finally:
            wp.request_shutdown()
            wd.request_shutdown()
            await asyncio.wait_for(asyncio.gather(tp, td), timeout=120.0)
    assert_parity(payloads, want, baseline, "handoff")
    assert wp.handoffs_shipped > 0, "no handoff took the ship path"
    assert wd.jobs_adopted >= len(jobs), (
        f"decode side adopted {wd.jobs_adopted}/{len(jobs)}"
    )
    print(
        f"probe: handoff leg ok — {wp.handoffs_shipped} shipped / "
        f"{wp.handoffs_fallback} fallback, {wd.jobs_adopted} adopted, "
        f"unified parity"
    )


async def run_fallback_leg(jobs, want, baseline):
    ns = "disagg-probe-fb"
    async with BrokerManager(
        Config(broker_url=f"memory://{ns}", max_redeliveries=1000)
    ) as mgr:
        await mgr.setup_queue_infrastructure(QUEUE)
        wp = worker_for(ns, QUEUE, "prefill")
        tp = asyncio.ensure_future(wp.run())
        for j in jobs:
            await mgr.publish_job(QUEUE, j)
        # No decode peer exists: every prefill-complete job must take the
        # snapshot fallback onto <q>.decode before we start the drainer.
        deadline = asyncio.get_running_loop().time() + 300.0
        while wp.handoffs_fallback < len(jobs):
            assert (
                asyncio.get_running_loop().time() < deadline
            ), f"fallbacks stuck at {wp.handoffs_fallback}/{len(jobs)}"
            await asyncio.sleep(0.1)
        assert wp.handoffs_shipped == 0, "shipped without a decode peer?"
        wd = worker_for(ns, QUEUE, "decode")
        td = asyncio.ensure_future(wd.run())
        try:
            payloads = await collect(mgr, QUEUE + ".results", want)
        finally:
            wp.request_shutdown()
            wd.request_shutdown()
            await asyncio.wait_for(asyncio.gather(tp, td), timeout=120.0)
    assert_parity(payloads, want, baseline, "fallback")
    assert wp.handoffs_fallback == len(jobs)
    assert wd.jobs_adopted >= len(jobs)
    print(
        f"probe: fallback leg ok — {wp.handoffs_fallback} snapshot "
        f"fallbacks, {wd.jobs_adopted} adopted, unified parity"
    )


async def run_autoswitch_leg():
    """Auto-role controller under synthetic depth skew. A DummyWorker
    carries the controller (it lives on BaseWorker, the same code the
    TPU worker runs) so the leg isolates role mechanics from inference.
    Dwell/check-interval are zeroed — the hysteresis TEETH are the fleet
    twin's regression; this leg proves the switch machinery itself."""
    from llmq_tpu.workers.dummy import DummyWorker

    ns = "disagg-probe-auto"
    w = DummyWorker(
        "aq",
        delay=0.01,
        config=Config(
            broker_url=f"memory://{ns}",
            max_redeliveries=1000,
            worker_role="auto",
            role_dwell_s=0.0,
            role_check_interval_s=0.0,
        ),
    )
    await w.initialize()
    w.running = True
    assert w.role == "auto" and w.role_active == "prefill"
    async with BrokerManager(
        Config(broker_url=f"memory://{ns}", max_redeliveries=1000)
    ) as mgr:
        # Skew 1: decode-pool backlog, shared queue empty — the depth
        # ratio (0+1)/(8+1) crosses role_switch_lo -> flip to decode.
        first = [Job(id=f"a{i}", prompt=f"auto {i}", max_tokens=8) for i in range(8)]
        for j in first:
            await mgr.publish_job(decode_queue_name("aq"), j)
        await w._maybe_switch_role()
        assert w.role_active == "decode" and w.role_switches == 1, (
            f"expected prefill->decode flip, got {w.role_active}"
        )
        await collect(mgr, "aq.results", {j.id for j in first})
        # Skew 2: shared-queue backlog, decode pool drained — the ratio
        # (8+1)/(0+1) crosses role_switch_hi -> flip back to prefill.
        second = [Job(id=f"b{i}", prompt=f"auto {i}", max_tokens=8) for i in range(8)]
        for j in second:
            await mgr.publish_job("aq", j)
        await w._maybe_switch_role()
        assert w.role_active == "prefill" and w.role_switches == 2, (
            f"expected decode->prefill flip, got {w.role_active}"
        )
        await collect(mgr, "aq.results", {j.id for j in second})
    await w.shutdown()
    print(
        "probe: autoswitch leg ok — prefill->decode->prefill on depth "
        "skew, both pools drained across the switches"
    )


async def main_async():
    jobs = probe_jobs()
    want = {j.id for j in jobs}
    baseline = await unified_baseline(jobs, want)
    await run_handoff_leg(probe_jobs(), want, baseline)
    await run_fallback_leg(probe_jobs(), want, baseline)
    await run_autoswitch_leg()
    print("metric: disagg_probe_ok legs=3")


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    asyncio.run(main_async())


if __name__ == "__main__":
    main()
