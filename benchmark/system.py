"""The system under test, started the way a deployment starts it, and the
few places where the benchmark reads the program's own stamps.

Broker, worker and engine run in this one process (one process per chip):
the in-process memory broker, the worker exactly as ``llmq-tpu worker run
preset://<model>`` builds it, and jobs in the schema the gateway publishes.
From the program the benchmark takes the system itself, the stamps of
``RequestOutput.timing`` (one monotonic clock, this process), the counters
of ``engine.stats()``, the ``CompileMeter`` and the names in the device
trace. Two thin recorders are hung on the running program, and neither
changes what it does:

- around ``AsyncEngine.generate``: keeps each request's ``timing``;
- around ``EngineCore._prefill_chunk``: notes the instant, the (rows,
  padded batch, bucket) and the request ids of every prefill dispatch.
"""

from __future__ import annotations

import asyncio
import inspect
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional

QUEUE = "bench"
# Keys of a configuration's ``engine`` that are the file's comments: any
# that ends in ``_why``, and these (a new file uses ``_why``).
ENGINE_COMMENTS = ("everything_else", "attention_path", "pool")


def worker_options(engine_cfg: Dict[str, Any], signature: inspect.Signature) -> Dict[str, Any]:
    """Every key of ``engine`` as an option of the worker: what the file
    does not give is left to the worker's default, and a key that is
    neither an option of ``signature`` nor a comment is an error."""
    taken = {
        name for name, p in signature.parameters.items() if p.kind is p.KEYWORD_ONLY
    }
    options = {
        key: value for key, value in engine_cfg.items()
        if not key.endswith("_why") and key not in ENGINE_COMMENTS
    }
    unknown = sorted(set(options) - taken)
    if unknown:
        raise SystemExit(
            f"the configuration's engine gives {unknown}, which the worker does "
            f"not take (it takes {sorted(taken)}; a comment's key ends in _why)"
        )
    return options


class System:
    def __init__(self, config: Dict[str, Any], cell: str) -> None:
        self.config = config
        self.cell = cell
        self.worker = None
        self.wtask: Optional[asyncio.Future] = None
        self.mgr = None
        self.timings: Dict[str, Dict[str, Any]] = {}
        self.prefill_log: List[tuple] = []  # (t, rows, batch, bucket)
        self.prefill_rids: List[List[str]] = []  # the same dispatches' request ids, row by row
        self.results: Dict[str, float] = {}  # job id -> instant received
        self.on_result: Optional[Callable[[str], None]] = None

    # --- start -----------------------------------------------------------
    async def start(self) -> None:
        engine_cfg = self.config["engine"]
        for key, value in self.config.get("env", {}).items():
            os.environ[key] = str(value)
        os.environ["LLMQ_BROKER_URL"] = f"memory://bench-{self.cell}"

        from llmq_tpu.cli.worker import build_tpu_worker
        from llmq_tpu.utils.logging import setup_logging

        self.worker = build_tpu_worker(
            self.config["program_model"],
            QUEUE,
            **worker_options(engine_cfg, inspect.signature(build_tpu_worker)),
        )
        setup_logging(structured=False, level="WARNING")  # stdout is results
        self.wtask = asyncio.ensure_future(self.worker.run())
        while not self.worker.running:
            if self.wtask.done():
                self.wtask.result()
                raise RuntimeError("the worker stopped before it was ready")
            await asyncio.sleep(0.05)
        self._hang_recorders()

        from llmq_tpu.broker.manager import BrokerManager
        from llmq_tpu.core.config import get_config

        self.mgr = BrokerManager(get_config())
        await self.mgr.connect()
        await self.mgr.setup_queue_infrastructure(QUEUE)
        await self.mgr.consume_results(
            QUEUE, self._on_result_message, prefetch=4096
        )

    @property
    def engine(self):
        return self.worker.engine

    @property
    def core(self):
        return self.worker.engine.core

    def _hang_recorders(self) -> None:
        engine, core = self.engine, self.core
        generate = engine.generate
        timings = self.timings

        async def recording_generate(**kwargs):
            out = await generate(**kwargs)
            timing = getattr(out, "timing", None)
            if timing:
                timings[kwargs["rid"]] = dict(
                    timing,
                    prompt_tokens=out.prompt_tokens,
                    completion_tokens=out.completion_tokens,
                )
            return out

        engine.generate = recording_generate
        prefill_chunk = core._prefill_chunk
        log, rids = self.prefill_log, self.prefill_rids

        def recording_prefill_chunk(chunk, bucket):
            rows = len(chunk)
            batch = 1 if rows == 1 else core.cfg.max_prefill_batch
            log.append((time.monotonic(), rows, batch, bucket))
            rids.append([seq.rid for seq in chunk])
            return prefill_chunk(chunk, bucket)

        core._prefill_chunk = recording_prefill_chunk

    # --- weights from --seed ---------------------------------------------
    def serve_weights_from_seed(self, seed: int) -> None:
        """Replace the worker's fixed-seed random weights by the
        benchmark's own, made on the device from ``--seed``. The old tree
        is dropped first, so the two never lie side by side."""
        import jax

        from . import architectures, weights

        arch = architectures.of(self.config)
        core = self.core
        shardings = core._param_shardings

        def swap():
            old = core.params
            layout = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), old
            )
            core.params = None
            for leaf in jax.tree.leaves(old):
                leaf.delete()
            new = weights.make_weights(arch, self.config, seed, shardings)
            weights.check_same_layout(new, layout)
            core.params = new
            jax.block_until_ready(new)

        self.engine.call_on_engine(swap, timeout=600.0)

    # --- client side -----------------------------------------------------
    async def _on_result_message(self, message) -> None:
        now = time.monotonic()
        try:
            rid = json.loads(message.body)["id"]
        except Exception:  # noqa: BLE001 - counted as a failure below
            rid = message.message_id
        self.results[rid] = now
        await message.ack()
        if self.on_result is not None:
            self.on_result(rid)

    async def publish(self, rid: str, prompt: str, max_tokens: int) -> float:
        """One job in the schema the gateway publishes; returns the
        instant just before the publish."""
        from llmq_tpu.core.models import Job

        job = Job(
            id=rid,
            prompt=prompt,
            temperature=0.0,
            max_tokens=max_tokens,
            ignore_eos=True,
        )
        t = time.monotonic()
        await self.mgr.publish_job(QUEUE, job)
        return t

    async def dead_letters(self) -> Dict[str, int]:
        from llmq_tpu.broker.manager import FAILED_SUFFIX, QUARANTINE_SUFFIX

        return {
            s: (await self.mgr.get_queue_stats(QUEUE + s)).message_count or 0
            for s in (FAILED_SUFFIX, QUARANTINE_SUFFIX)
        }

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def inflight(self) -> Dict[str, Any]:
        """The engine's running sequences by request id (their stamps are
        the ones ``RequestOutput.timing`` will carry). Read from outside
        the engine thread, so a copy that raced a change is taken again."""
        running = self.core.scheduler.running
        for _ in range(8):
            try:
                return dict(running)
            except RuntimeError:
                continue
        return {}

