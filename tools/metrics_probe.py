"""End-to-end probe of the observability plane: exporter + trace round trip.

Builds a tiny engine, runs a handful of requests so the latency histograms
have samples, starts the Prometheus exporter (LLMQ_METRICS_PORT, defaults
to an ephemeral port here), scrapes its own /metrics over HTTP, and asserts
the core series are present and well-formed. Then runs a DummyWorker job
through a memory broker and asserts the lifecycle trace rides the result
with a monotone timeline.

Runs on CPU (preflight) and on device (hardware_session / chip_watch
rungs) identically — the plane under test is host-side only.

    LLMQ_METRICS_PORT=0 python tools/metrics_probe.py
"""

import asyncio
import os
import sys
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Exporter port for the scrape leg: respect an explicit operator choice,
# default to 0 (ephemeral) so parallel rungs never collide.
os.environ.setdefault("LLMQ_METRICS_PORT", "0")

import jax
import jax.numpy as jnp

from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.obs import get_registry, maybe_start_exporter, stop_exporter
from llmq_tpu.obs.trace import timeline, trace_from_payload
from llmq_tpu.parallel import make_mesh

REQUIRED_SERIES = (
    "llmq_ttft_seconds_bucket",
    "llmq_itl_seconds_bucket",
    "llmq_engine_tokens_per_sec",
    "llmq_engine_kv_page_utilization",
    "llmq_engine_batch_occupancy",
    "llmq_queue_wait_seconds_bucket",
    "llmq_dispatch_seconds_bucket",
)


def run_engine_leg():
    cfg = ModelConfig.tiny(vocab_size=304)
    params = init_params(cfg, jax.random.key(0), dtype=jnp.float32)
    core = EngineCore(
        cfg, params, ByteTokenizer(),
        mesh=make_mesh(tensor_parallel=1),
        engine_config=EngineConfig(
            max_num_seqs=4, max_model_len=64, page_size=8, num_pages=65,
            kv_dtype=jnp.float32, min_prefill_bucket=16, max_prefill_batch=2,
        ),
    )
    for i in range(6):
        core.add_request(
            f"probe-{i}",
            prompt=f"metrics probe request {i} " + "x" * (4 * i),
            params=SamplingParams(
                temperature=0.0, max_tokens=6, ignore_eos=True
            ),
        )
    done = 0
    while done < 6:
        done += len(core.step())
    stats = core.stats()
    for key in ("ttft_p50_ms", "itl_p50_ms"):
        assert stats.get(key) is not None, f"engine stats missing {key}"
    print(
        f"probe: engine leg ok — ttft_p50 {stats['ttft_p50_ms']} ms, "
        f"itl_p50 {stats['itl_p50_ms']} ms"
    )
    return stats


def run_scrape_leg():
    exporter = maybe_start_exporter()
    assert exporter is not None, (
        "exporter did not start (LLMQ_METRICS_PORT unset or port taken)"
    )
    url = f"http://127.0.0.1:{exporter.port}/metrics"
    with urllib.request.urlopen(url, timeout=10) as resp:
        assert resp.status == 200, f"/metrics returned {resp.status}"
        body = resp.read().decode("utf-8")
    missing = [s for s in REQUIRED_SERIES if s not in body]
    assert not missing, f"/metrics missing series: {missing}"
    # Minimal Prometheus text-format sanity: every non-comment line is
    # "name{labels} value" with a float-parseable value.
    for line in body.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part, f"malformed series line: {line!r}"
        float(value)
    print(
        f"probe: scrape leg ok — {len(body)} bytes from {url}, "
        f"{len(REQUIRED_SERIES)} required series present"
    )
    return body


async def run_trace_leg():
    from llmq_tpu.broker.manager import BrokerManager, results_queue_name
    from llmq_tpu.core.config import Config
    from llmq_tpu.core.models import Job
    from llmq_tpu.workers.dummy import DummyWorker

    cfg = Config(broker_url="memory://metrics-probe")
    async with BrokerManager(cfg) as mgr:
        await mgr.setup_queue_infrastructure("probe-q")
        await mgr.publish_job("probe-q", Job(id="probe-job", prompt="hello"))
        worker = DummyWorker("probe-q", config=cfg, delay=0.0)
        task = asyncio.create_task(worker.run())
        try:
            payload = None
            for _ in range(200):
                msg = await mgr.broker.get(results_queue_name("probe-q"))
                if msg is not None:
                    import json

                    payload = json.loads(msg.body)
                    await msg.ack()
                    break
                await asyncio.sleep(0.05)
        finally:
            await worker.shutdown()
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
    assert payload is not None, "no result arrived on the results queue"
    trace = trace_from_payload(payload)
    assert trace is not None, "result carries no trace record"
    rows = timeline(trace)
    names = [r["name"] for r in rows]
    for needed in ("submitted", "claimed", "finished"):
        assert needed in names, f"trace missing '{needed}': {names}"
    walls = [r["t_wall"] for r in rows]
    assert walls == sorted(walls), f"timeline not monotone: {names}"
    print(f"probe: trace leg ok — {len(rows)} events: {' -> '.join(names)}")


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    run_engine_leg()
    run_scrape_leg()
    asyncio.run(run_trace_leg())
    stop_exporter()
    summary = get_registry().summary()
    print(
        "metric: obs_probe_ok "
        f"series={len(REQUIRED_SERIES)} histograms={len(summary)}"
    )


if __name__ == "__main__":
    main()
