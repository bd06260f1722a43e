#!/usr/bin/env python
"""End-to-end performance benchmark for llmq-tpu.

TPU-native counterpart of the reference's ``performance_benchmark.py``
(reference performance_benchmark.py:33-693): drives the FULL stack —
broker daemon + real worker subprocess + submit + receive — and reports
throughput and latency per batch-size operating point.

Differences from the reference, on purpose:
- the broker is llmq-tpu's own daemon (in-process asyncio server or the
  native C++ one via --native-broker), not an external RabbitMQ;
- token counts come from the worker's actual tokenizer (Result.usage),
  not a tiktoken estimate — chars/4 only as a fallback;
- worker readiness is detected via broker stats (consumer_count > 0),
  not by grepping log lines;
- the sweep dimension is the engine's ``max_num_seqs`` (continuous-batch
  slots), the knob that governs TPU batch occupancy.

Metrics per operating point (reference parity:
performance_benchmark.py:329-366):
  jobs/sec, input/output/total tokens/sec, p50/p95/p99 end-to-end
  latency, mean worker processing ms, batching overhead ms
  (end-to-end mean minus processing mean).

Usage:
  python performance_benchmark.py --model preset://qwen2.5-0.5b \
      --samples 200 --batch-sizes 16,64,128 --max-tokens 64 \
      --output benchmark_results.json
  python performance_benchmark.py --worker dummy --samples 50   # no TPU
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import statistics
import subprocess
import sys
import time
import uuid
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional


def _repo_root() -> str:
    return os.path.dirname(os.path.abspath(__file__))


sys.path.insert(0, _repo_root())


@dataclass
class RequestTiming:
    job_id: str
    submitted_at: float
    completed_at: float = 0.0
    processing_ms: float = 0.0
    prompt_tokens: int = 0
    completion_tokens: int = 0

    @property
    def e2e_ms(self) -> float:
        return (self.completed_at - self.submitted_at) * 1000.0


@dataclass
class BenchmarkResult:
    batch_size: int
    num_jobs: int
    wall_seconds: float
    jobs_per_sec: float
    input_tokens_per_sec: float
    output_tokens_per_sec: float
    total_tokens_per_sec: float
    latency_p50_ms: float
    latency_p95_ms: float
    latency_p99_ms: float
    processing_mean_ms: float
    batching_overhead_ms: float
    failures: int = 0


def percentile(values: List[float], pct: float) -> float:
    if not values:
        return 0.0
    ordered = sorted(values)
    k = min(len(ordered) - 1, max(0, int(round(pct / 100.0 * (len(ordered) - 1)))))
    return ordered[k]


def _fallback_tokens(text: str) -> int:
    return max(1, len(text) // 4)  # reference TokenCounter fallback (91-97)


_INVENTORY_SRC = (
    "import json, jax; d = jax.devices(); "
    "print(json.dumps({'platform': d[0].platform, 'device_count': len(d), "
    "'device_kind': d[0].device_kind}))"
)


def device_inventory() -> Dict[str, object]:
    """TPU counterpart of the reference's nvidia-smi inventory (114-154).

    Asked of a short-lived child, never of this process: a chip belongs
    to one process at a time, and a harness that had touched JAX would
    hold the chip its worker subprocesses need. Call it only while no
    worker is running (``run`` does, after the last point)."""
    proc = subprocess.run(
        [sys.executable, "-c", _INVENTORY_SRC],
        capture_output=True, text=True, timeout=300,
    )
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        return {"platform": "unavailable", "error": proc.stderr[-400:]}
    return json.loads(lines[-1])


class PerformanceBenchmark:
    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self.queue = f"bench-{uuid.uuid4().hex[:8]}"
        self.server = None
        self.port: Optional[int] = None
        self.worker_proc: Optional[subprocess.Popen] = None
        self._native_proc: Optional[subprocess.Popen] = None

    # --- broker -----------------------------------------------------------
    async def start_broker(self) -> str:
        if self.args.native_broker:
            from llmq_tpu.broker.native import ensure_brokerd

            binary = ensure_brokerd()
            if binary is None:
                raise RuntimeError("native brokerd unavailable")
            import socket as s

            with s.socket() as probe:
                probe.bind(("127.0.0.1", 0))
                self.port = probe.getsockname()[1]
            self._native_proc = subprocess.Popen(
                [str(binary), "--host", "127.0.0.1", "--port", str(self.port)],
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            )
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                try:
                    with s.create_connection(("127.0.0.1", self.port), 0.2):
                        break
                except OSError:
                    await asyncio.sleep(0.05)
        else:
            from llmq_tpu.broker.tcp import BrokerServer

            self.server = BrokerServer("127.0.0.1", 0)
            await self.server.start()
            self.port = self.server._server.sockets[0].getsockname()[1]
        return f"tcp://127.0.0.1:{self.port}"

    # --- worker -----------------------------------------------------------
    def start_worker(self, url: str, batch_size: int) -> None:
        # Prepend (never replace) PYTHONPATH: the worker keeps whatever
        # site dirs this process was started with.
        pypath = os.environ.get("PYTHONPATH", "")
        pypath = _repo_root() + (os.pathsep + pypath if pypath else "")
        env = dict(os.environ, LLMQ_BROKER_URL=url,
                   PYTHONPATH=pypath,
                   LLMQ_QUEUE_PREFETCH=str(self.args.prefetch or batch_size * 2))
        if self.args.worker == "dummy":
            cmd = [sys.executable, "-m", "llmq_tpu", "worker", "dummy",
                   self.queue, "--delay", "0.05"]
        else:
            cmd = [sys.executable, "-m", "llmq_tpu", "worker", "run",
                   self.args.model, self.queue,
                   "--max-num-seqs", str(batch_size)]
            if self.args.max_model_len:
                cmd += ["--max-model-len", str(self.args.max_model_len)]
            if self.args.dtype:
                cmd += ["--dtype", self.args.dtype]
            if self.args.kv_dtype:
                cmd += ["--kv-dtype", self.args.kv_dtype]
        log = open(f"/tmp/llmq_bench_worker_{batch_size}.log", "w")
        self.worker_proc = subprocess.Popen(
            cmd, env=env, stdout=log, stderr=log
        )

    async def wait_worker_ready(self, broker, timeout: float) -> None:
        """Ready = the worker's consumer shows up on the job queue
        (replaces the reference's log-line grep, 506-534)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.worker_proc is not None and self.worker_proc.poll() is not None:
                raise RuntimeError(
                    f"worker exited (rc={self.worker_proc.returncode}); "
                    f"see /tmp/llmq_bench_worker_*.log"
                )
            stats = await broker.stats(self.queue)
            if (stats.consumer_count or 0) > 0:
                return
            await asyncio.sleep(0.5)
        raise RuntimeError("worker did not become ready in time")

    def stop_worker(self) -> None:
        if self.worker_proc is not None:
            self.worker_proc.send_signal(signal.SIGTERM)
            try:
                self.worker_proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.worker_proc.kill()
                self.worker_proc.wait()
            self.worker_proc = None

    # --- one operating point ---------------------------------------------
    async def run_point(self, url: str, batch_size: int) -> BenchmarkResult:
        from llmq_tpu.broker.manager import BrokerManager
        from llmq_tpu.core.models import Job, Result

        manager = BrokerManager(url=url)
        await manager.connect()
        await manager.setup_queue_infrastructure(self.queue)
        self.start_worker(url, batch_size)
        try:
            await self.wait_worker_ready(
                manager.broker, self.args.worker_timeout
            )

            timings: Dict[str, RequestTiming] = {}
            failures = 0
            done = asyncio.Event()

            async def on_result(msg) -> None:
                nonlocal failures
                try:
                    result = Result.model_validate_json(
                        msg.body.decode("utf-8")
                    )
                    t = timings.get(result.id)
                    if t is not None:
                        t.completed_at = time.monotonic()
                        t.processing_ms = result.duration_ms or 0.0
                        usage = result.usage or {}
                        t.prompt_tokens = usage.get(
                            "prompt_tokens", _fallback_tokens(result.prompt)
                        )
                        t.completion_tokens = usage.get(
                            "completion_tokens",
                            _fallback_tokens(result.result),
                        )
                except Exception:  # noqa: BLE001
                    failures += 1
                finally:
                    await msg.ack()
                    if sum(1 for t in timings.values() if t.completed_at) + \
                            failures >= self.args.samples:
                        done.set()

            await manager.broker.consume(
                f"{self.queue}.results", on_result, prefetch=256
            )

            start = time.monotonic()
            text = self.args.prompt_text
            for i in range(self.args.samples):
                job = Job(
                    id=f"bench-{i}",
                    prompt=text,
                    max_tokens=self.args.max_tokens,
                    ignore_eos=True,
                )
                timings[job.id] = RequestTiming(
                    job_id=job.id, submitted_at=time.monotonic()
                )
                await manager.publish_job(self.queue, job)
            await asyncio.wait_for(done.wait(), self.args.point_timeout)
            wall = time.monotonic() - start

            completed = [t for t in timings.values() if t.completed_at]
            e2e = [t.e2e_ms for t in completed]
            proc = [t.processing_ms for t in completed]
            in_tok = sum(t.prompt_tokens for t in completed)
            out_tok = sum(t.completion_tokens for t in completed)
            return BenchmarkResult(
                batch_size=batch_size,
                num_jobs=len(completed),
                wall_seconds=round(wall, 3),
                jobs_per_sec=round(len(completed) / wall, 3),
                input_tokens_per_sec=round(in_tok / wall, 1),
                output_tokens_per_sec=round(out_tok / wall, 1),
                total_tokens_per_sec=round((in_tok + out_tok) / wall, 1),
                latency_p50_ms=round(percentile(e2e, 50), 1),
                latency_p95_ms=round(percentile(e2e, 95), 1),
                latency_p99_ms=round(percentile(e2e, 99), 1),
                processing_mean_ms=round(
                    statistics.mean(proc) if proc else 0.0, 1
                ),
                batching_overhead_ms=round(
                    (statistics.mean(e2e) - statistics.mean(proc))
                    if e2e and proc
                    else 0.0,
                    1,
                ),
                failures=failures,
            )
        finally:
            self.stop_worker()
            await manager.broker.purge(self.queue)
            await manager.broker.purge(f"{self.queue}.results")
            await manager.disconnect()

    # --- orchestration ----------------------------------------------------
    async def run(self) -> Dict[str, object]:
        url = await self.start_broker()
        results: List[BenchmarkResult] = []
        try:
            for batch_size in self.args.batch_sizes:
                print(
                    f"=== operating point: batch_size={batch_size}, "
                    f"{self.args.samples} jobs ===",
                    file=sys.stderr,
                )
                try:
                    point = await self.run_point(url, batch_size)
                except Exception as exc:  # noqa: BLE001 — next point may work
                    print(
                        f"point batch_size={batch_size} FAILED: "
                        f"{type(exc).__name__}: {exc}",
                        file=sys.stderr,
                    )
                    self.stop_worker()
                    continue
                results.append(point)
                print(json.dumps(asdict(point)), file=sys.stderr)
        finally:
            if self.server is not None:
                await self.server.stop()
            if self._native_proc is not None:
                self._native_proc.terminate()
                self._native_proc.wait(timeout=10)
        return {
            "model": self.args.model,
            "worker": self.args.worker,
            "samples": self.args.samples,
            "max_tokens": self.args.max_tokens,
            "devices": device_inventory(),
            "results": [asdict(r) for r in results],
        }


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--model", default="preset://qwen2.5-0.5b",
                   help="HF checkpoint dir or preset://<name>")
    p.add_argument("--worker", choices=["tpu", "dummy"], default="tpu")
    p.add_argument("--samples", type=int, default=200)
    p.add_argument("--batch-sizes", default="16,64",
                   type=lambda s: [int(x) for x in s.split(",")])
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--max-model-len", type=int, default=1024)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--kv-dtype", default=None,
                   choices=["auto", "bf16", "fp8", "fp8_e5m2"],
                   help="KV cache dtype for the tpu worker (fp8 = e5m2)")
    p.add_argument("--prefetch", type=int, default=None)
    p.add_argument("--prompt-text",
                   default="Translate to Dutch: the quick brown fox jumps "
                           "over the lazy dog. " * 4)
    p.add_argument("--native-broker", action="store_true",
                   help="Benchmark against the C++ broker daemon")
    p.add_argument("--worker-timeout", type=float, default=600.0)
    p.add_argument("--point-timeout", type=float, default=1800.0)
    p.add_argument("--output", default=None, help="JSON results path")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    report = asyncio.run(PerformanceBenchmark(args).run())
    out = json.dumps(report, indent=2)
    if args.output:
        from pathlib import Path as _Path

        _Path(args.output).parent.mkdir(parents=True, exist_ok=True)
        with open(args.output, "w") as f:
            f.write(out + "\n")
        print(f"results written to {args.output}", file=sys.stderr)
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
