"""Worker base: lifecycle + message loop + error policy.

Counterpart of reference ``llmq/workers/base.py:15-275``. A worker:

1. initialises its processor (e.g. compiles the TPU engine),
2. connects to the broker and sets prefetch = concurrency,
3. consumes jobs; per message: parse → process → Result (with extra-field
   passthrough) → publish (direct or pipeline-routed) → ack,
4. on ValueError: ack-and-drop with an error result policy (malformed job —
   retrying can't help; reference base.py:228-235),
5. on any other exception: reject-requeue (broker dead-letters past the
   redelivery cap — the reference requeued forever),
6. SIGINT/SIGTERM → graceful drain and cleanup.

Additions over the reference: periodic WorkerHealth heartbeats published to
``<queue>.health`` (the reference declared the model but nothing produced
it), engine stats surfaced through them, and a robustness layer:

- per-job timeout (``Config.job_timeout_s``): a hung engine step becomes
  reject-requeue (dead-letters via the redelivery cap) instead of wedging a
  prefetch slot forever,
- unparseable payloads dead-letter to ``<queue>.failed`` with an ``x-error``
  header instead of vanishing,
- broker outages don't kill the worker: the BrokerManager's resilient
  session reconnects and re-establishes the consumer; heartbeats pause
  while the transport is down and resume after.
"""

from __future__ import annotations

import abc
import asyncio
import json
import logging
import signal
from collections import deque
from typing import Optional

from llmq_tpu.broker.base import DeliveredMessage
from llmq_tpu.broker.manager import (
    FAILED_SUFFIX,
    HEALTH_SUFFIX,
    HEARTBEAT_INTERVAL_S,
    QUARANTINE_SUFFIX,
    BrokerManager,
    affinity_queue_name,
    decode_adopt_queue_name,
    decode_queue_name,
    interactive_queue_name,
    kv_fetch_queue_name,
)
from llmq_tpu.core.config import Config, get_config
from llmq_tpu.core.faults import DeviceFaultError, StepCompileError
from llmq_tpu.core.models import Job, Result, WorkerHealth, utcnow
from llmq_tpu.core.pipeline import PipelineConfig
from llmq_tpu.obs import (
    TRACE_FIELD,
    Gauge,
    emit_trace_event,
    get_registry,
    maybe_start_exporter,
    new_trace,
    trace_event,
    trace_from_payload,
)
from llmq_tpu.obs.spans import LoopLag, SpanRing, spans_path, stall_stacks_path
from llmq_tpu.utils import clock
from llmq_tpu.utils.logging import ContextLogAdapter
from llmq_tpu.workers.resume import (
    RESUME_FIELD,
    JobHandoff,
    PrefillDone,
    ResultDeduper,
    resume_offset,
)

#: Valid LLMQ_WORKER_ROLE values. "unified" is the monolith default;
#: "auto" workers start as prefill and switch on fleet queue depths.
WORKER_ROLES = ("unified", "prefill", "decode", "auto")

HEALTH_TTL_MS = 120_000

# HEARTBEAT_INTERVAL_S now lives in broker.manager (the janitor and the
# monitor share it); re-exported here for existing importers.
__all__ = [
    "BaseWorker",
    "DeadlineExceeded",
    "HEALTH_TTL_MS",
    "HEARTBEAT_INTERVAL_S",
]

# Worker-local memory of why recent jobs failed (job_id -> reason), bounded:
# feeds the x-failure-reason header when a job quarantines on this worker.
_FAILURE_MEMORY_CAP = 1024


class DeadlineExceeded(Exception):
    """A job's deadline passed while it was in flight (engine sweep or a
    pre-recovery check). The message loop dead-letters it as
    ``deadline_exceeded`` instead of publishing a result or requeueing."""


class BaseWorker(abc.ABC):
    def __init__(
        self,
        queue: str,
        *,
        config: Optional[Config] = None,
        concurrency: Optional[int] = None,
        pipeline: Optional[PipelineConfig] = None,
        stage_name: Optional[str] = None,
    ) -> None:
        self.queue = queue
        self.config = config or get_config()
        self.concurrency = concurrency or self.config.queue_prefetch
        self.pipeline = pipeline
        self.stage_name = stage_name
        self.worker_id = self._generate_worker_id()
        # Structured log records (LLMQ_LOG_FORMAT=json) carry worker_id
        # on every line; call sites add job_id via extra={...}.
        self.logger = ContextLogAdapter(
            logging.getLogger(f"worker.{self.worker_id}"),
            {"worker_id": self.worker_id},
        )
        self.broker = BrokerManager(self.config)
        self.running = False
        self._stop_requested = False  # a shutdown asked for before run() got going
        self.jobs_processed = 0
        self.jobs_failed = 0
        self.jobs_timed_out = 0
        self.total_duration_ms = 0.0
        self._consumer_tag: Optional[str] = None
        # Prefix-affinity: this worker's private job queue (consumed
        # alongside the shared one when Config.prefix_affinity is on).
        self._affinity_consumer_tag: Optional[str] = None
        self._in_flight = 0
        self._drained = asyncio.Event()
        self._drained.set()
        # Live request traces, keyed by job id, so processors (e.g. the
        # TPU worker) can attach engine lifecycle events to the record
        # that rides back in the Result.
        self._job_traces: dict = {}
        # The event loop's span ring (obs/spans.py; off unless LLMQ_SPANS
        # is set, set_tracing switches it on or a profile is being
        # taken) and its lag mark, which runs from run() to shutdown()
        # whatever the ring does.
        self.spans = SpanRing("worker")
        self._loop_lag = LoopLag(self.spans)
        # Exactly-one-result guard: (job_id, resume offset) pairs this
        # worker already published for. Redelivered or resumed jobs that
        # land on this worker twice publish once.
        self._dedup = ResultDeduper()
        # Fleet self-healing state: per-job failure reasons (bounded FIFO
        # alongside insertion order), consecutive engine failures for the
        # circuit breaker, and robustness counters surfaced in heartbeats.
        self._failure_reasons: dict = {}
        self._consecutive_failures = 0
        self.jobs_deadline_exceeded = 0
        self.jobs_quarantined = 0
        self.breaker_tripped = False
        self._fatal_error: Optional[BaseException] = None
        # Disaggregated serving: the configured role ("unified" runs the
        # monolith path unchanged) and the role currently served (differs
        # from `role` only for "auto", whose controller flips role_active
        # on fleet queue depths with hysteresis).
        role = (self.config.worker_role or "unified").lower()
        if role not in WORKER_ROLES:
            raise ValueError(
                f"LLMQ_WORKER_ROLE must be one of {WORKER_ROLES}, got {role!r}"
            )
        self.role = role
        self.role_active = "prefill" if role == "auto" else role
        self.role_switches = 0
        self.handoffs_shipped = 0  # KV adoptions a decode peer accepted
        self.handoffs_fallback = 0  # snapshot republishes to <q>.decode
        self.jobs_adopted = 0  # handoffs this worker resumed as decoder
        # Handoff publish→adoption latency samples (ms), bounded ring.
        self._handoff_ms: deque = deque(maxlen=512)
        self._role_since = clock.monotonic()
        self._role_checked_at = float("-inf")
        self._decode_consumer_tag: Optional[str] = None
        self._adopt_consumer_tag: Optional[str] = None
        # SLO fast lane: consumer on <q>.interactive (priority_classes
        # fleets only) + per-class shed accounting for goodput math.
        self._interactive_consumer_tag: Optional[str] = None
        self.jobs_deadline_exceeded_interactive = 0

    # --- abstract surface (reference base.py:57-75) -----------------------
    @abc.abstractmethod
    def _generate_worker_id(self) -> str: ...

    @abc.abstractmethod
    async def _initialize_processor(self) -> None: ...

    @abc.abstractmethod
    async def _process_job(self, job: Job) -> str: ...

    @abc.abstractmethod
    async def _cleanup_processor(self) -> None: ...

    # --- lifecycle --------------------------------------------------------
    async def initialize(self) -> None:
        self.logger.info("Initializing worker %s", self.worker_id)
        # Opt-in Prometheus endpoint (LLMQ_METRICS_PORT); serves the
        # process-wide registry the engine/scheduler/broker record into.
        maybe_start_exporter()
        await self._initialize_processor()
        await self.broker.connect()
        if self.pipeline is not None:
            await self.broker.setup_pipeline_infrastructure(self.pipeline)
        else:
            await self.broker.setup_queue_infrastructure(self.queue)
        # Heartbeats expire via TTL; the huge redelivery cap keeps repeated
        # non-destructive health peeks from ever dead-lettering them.
        await self.broker.broker.declare_queue(
            self.queue + HEALTH_SUFFIX,
            ttl_ms=HEALTH_TTL_MS,
            max_redeliveries=1_000_000_000,
        )
        if self.config.prefix_affinity:
            # Private affinity queue: the submit path routes jobs sharing
            # an advertised prefix here. Same TTL/redelivery policy as the
            # shared queue, so a job stranded by this worker dying either
            # expires or dead-letters instead of waiting forever.
            await self.broker.broker.declare_queue(
                affinity_queue_name(self.queue, self.worker_id),
                ttl_ms=self.config.job_ttl_ms,
                max_redeliveries=self.config.max_redeliveries,
            )

    async def run(self) -> None:
        """Main entry: initialize, consume until stopped, then clean up."""
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, self.request_shutdown)
            except (NotImplementedError, RuntimeError):
                pass  # non-main thread or platform without signal support
        try:
            await self.initialize()
            self._start_loop_lag()
            if spans_path() is not None:
                await self.set_tracing(True)
            # A shutdown asked for while the engine was still being built
            # (SIGTERM during start-up) holds: consume nothing, go down.
            self.running = not self._stop_requested
            await self._start_role_consumers()
            await self._start_extra_consumers()
            self.logger.info(
                "Worker %s starting to consume from '%s' (prefetch=%d, role=%s)",
                self.worker_id,
                self.queue,
                self.concurrency,
                self.role_active if self.role != "unified" else "unified",
            )
            # Monotonic clock for the beat cadence: wall time steps (NTP
            # slews, manual clock sets) must not skip or double beats.
            last_beat = clock.monotonic() - HEARTBEAT_INTERVAL_S
            while self.running:
                now = clock.monotonic()
                if now - last_beat >= HEARTBEAT_INTERVAL_S:
                    # Heartbeats pause during a broker outage (publishing
                    # them would just park stale liveness claims in the
                    # reconnect outbox) and resume right after reconnect.
                    if self.broker.transport_connected:
                        await self._publish_heartbeat()
                        last_beat = now
                await self._maybe_switch_role()
                await asyncio.sleep(1.0)
        finally:
            await self.shutdown()
            self._stop_requested = False
        if self._fatal_error is not None:
            raise self._fatal_error

    # --- span rings and the loop-lag mark (obs/spans.py) -------------------
    def _start_loop_lag(self) -> None:
        """From here to shutdown every 100 ms timer on this loop notes how
        late it ran: ``loop_lag_max_ms`` in the heartbeat's stats and on
        ``/metrics``, a warning at once when one ran 0.5 s late. With
        ``LLMQ_STALL_STACKS=<file>`` a loop that stands still that long
        also has every thread's stack written there while it stands."""
        lag = self._loop_lag
        lag.gauge = get_registry().register(
            Gauge(
                "llmq_loop_lag_max_ms",
                "Latest a 100 ms timer ran on the worker's event loop since start",
            )
        )
        sink = stall_stacks_path()
        if sink is not None:
            try:
                lag.stack_sink = open(sink, "a", encoding="utf-8")
            except OSError:  # observability is best-effort
                self.logger.debug("no stack sink", exc_info=True)
        lag.start()

    async def set_tracing(self, on: bool) -> None:
        """Switch this worker's span rings on or off (event-loop thread).
        ``LLMQ_SPANS=<file>`` switches them on at start; the dump is then
        written to that file at shutdown."""
        self.spans.set(on)

    def trace_dump(self) -> dict:
        """``{"spans": [...], "requests": {rid: {stamp: t}}, "counters":
        {...}}`` of this worker's rings, merged."""
        return self.spans.dump()

    def _write_span_dump(self, path: str) -> None:
        """``LLMQ_SPANS``: the rings' dump as one JSON file, at shutdown
        (the loop has nothing left to serve)."""
        try:
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(dict(self.trace_dump(), worker_id=self.worker_id), fh)
        except (OSError, TypeError, ValueError):  # observability is best-effort
            self.logger.debug("span dump not written", exc_info=True)

    def fail_fatally(self, exc: BaseException) -> None:
        """Stop this worker for a fault that no retry cures (the engine
        cannot compile a step program). In-flight jobs drain back to the
        queue as usual; ``run()`` then raises ``exc``, so the process
        exits non-zero instead of idling as a worker that looks up."""
        self.logger.critical("Worker stopping on a fatal error: %s", exc)
        self._fatal_error = exc
        self.request_shutdown()

    def request_shutdown(self) -> None:
        if self.running:
            self.logger.info("Shutdown requested; draining in-flight jobs")
        self.running = False
        self._stop_requested = True

    async def shutdown(self) -> None:
        for attr in (
            "_consumer_tag",
            "_affinity_consumer_tag",
            "_interactive_consumer_tag",
            "_kv_consumer_tag",
            "_ctl_consumer_tag",
            "_decode_consumer_tag",
            "_adopt_consumer_tag",
        ):
            tag = getattr(self, attr, None)
            if tag is not None and self.broker.connected:
                try:
                    # requeue=False: in-flight jobs either finish (and ack)
                    # during the drain below or are republished as resume
                    # snapshots; requeueing them here would double-deliver.
                    await self.broker.cancel(tag, requeue=False)
                except Exception:  # noqa: BLE001 — best-effort teardown
                    pass
                setattr(self, attr, None)
        # Drain-with-handoff: let the processor hand unfinished requests
        # back (the TPU worker extracts engine snapshots here). In-flight
        # _process_message coroutines then settle their messages as
        # resumable republishes instead of waiting out full generations.
        try:
            await self._handoff_in_flight()
        except Exception:  # noqa: BLE001 — fall back to the plain drain
            self.logger.warning("In-flight handoff failed", exc_info=True)
        try:
            await asyncio.wait_for(
                self._drained.wait(), timeout=self.config.drain_timeout_s
            )
        except asyncio.TimeoutError:
            self.logger.warning("Timed out draining %d in-flight jobs", self._in_flight)
        if self.config.prefix_affinity and self.broker.connected:
            await self._retire_affinity_queue()
        if self.role != "unified" and self.broker.connected:
            await self._retire_adopt_queue()
        if spans_path() is not None and self.spans.on:
            self._write_span_dump(spans_path())
        self._loop_lag.stop()
        await self._cleanup_processor()
        if self.broker.connected:
            await self.broker.disconnect()
        self.logger.info(
            "Worker %s stopped (processed=%d failed=%d)",
            self.worker_id,
            self.jobs_processed,
            self.jobs_failed,
        )

    async def _handoff_in_flight(self) -> None:
        """Hook: hand in-flight requests back to the broker as resumable
        jobs during shutdown. Base workers have no partial state worth
        carrying — the plain drain (or redelivery) covers them."""
        return None

    async def _retire_affinity_queue(self) -> None:
        """Graceful-shutdown half of affinity-orphan reclaim: republish
        anything still sitting on this worker's private queue to the
        shared queue, then delete the queue (and the KV-ship RPC queue)
        so nothing can strand on them after the worker is gone. The
        janitor covers crashed workers; this covers the common case
        without waiting out a heartbeat staleness window."""
        aq = affinity_queue_name(self.queue, self.worker_id)
        moved = 0
        try:
            while True:
                msg = await self.broker.broker.get(aq)
                if msg is None:
                    break
                await self.broker.broker.publish(
                    self.queue,
                    msg.body,
                    message_id=msg.message_id,
                    headers=msg.headers,
                )
                await msg.ack()
                moved += 1
            await self.broker.broker.delete_queue(aq)
            await self.broker.broker.delete_queue(
                kv_fetch_queue_name(self.queue, self.worker_id)
            )
        except Exception:  # noqa: BLE001 — the janitor reclaims what's left
            self.logger.warning(
                "Affinity queue retirement incomplete", exc_info=True
            )
        if moved:
            self.logger.info(
                "Returned %d unclaimed jobs from %s to the shared queue",
                moved,
                aq,
            )

    async def _retire_adopt_queue(self) -> None:
        """Graceful-shutdown half of adoption-orphan reclaim: return any
        handoffs still parked on this worker's ``<q>.d.<id>`` queue to the
        shared decode pool, then delete the queue. The janitor covers the
        crashed-worker case."""
        aq = decode_adopt_queue_name(self.queue, self.worker_id)
        try:
            while True:
                msg = await self.broker.broker.get(aq)
                if msg is None:
                    break
                await self.broker.broker.publish(
                    decode_queue_name(self.queue),
                    msg.body,
                    message_id=msg.message_id,
                    headers=msg.headers,
                )
                await msg.ack()
            await self.broker.broker.delete_queue(aq)
        except Exception:  # noqa: BLE001 — the janitor reclaims what's left
            self.logger.warning(
                "Adoption queue retirement incomplete", exc_info=True
            )

    # --- disaggregated roles ----------------------------------------------
    async def _start_role_consumers(self) -> None:
        """Attach the job consumers for the role currently served.

        Prefill (and unified) workers consume the shared queue plus their
        prefix-affinity queue; decode workers consume the shared decode
        pool ``<q>.decode`` plus their private adoption queue ``<q>.d.<id>``
        (accepted KV handoffs are parked there durably before the offer is
        acknowledged). An auto worker holds exactly one of the two sets at
        a time — switching roles swaps the set."""
        if self.role_active == "decode":
            dq = decode_queue_name(self.queue)
            await self.broker.broker.declare_queue(
                dq,
                ttl_ms=self.config.job_ttl_ms,
                max_redeliveries=self.config.max_redeliveries,
            )
            self._decode_consumer_tag = await self.broker.consume_jobs(
                dq, self._process_message, prefetch=self.concurrency
            )
            aq = decode_adopt_queue_name(self.queue, self.worker_id)
            await self.broker.broker.declare_queue(
                aq,
                ttl_ms=self.config.job_ttl_ms,
                max_redeliveries=self.config.max_redeliveries,
            )
            self._adopt_consumer_tag = await self.broker.consume_jobs(
                aq, self._process_message, prefetch=self.concurrency
            )
            return
        if self.config.priority_classes:
            # Fast lane first: interactive deliveries race the shared
            # queue's prefetch window, and the engine's priority-aware
            # admission orders whatever lands concurrently.
            self._interactive_consumer_tag = await self.broker.consume_jobs(
                interactive_queue_name(self.queue),
                self._process_message,
                prefetch=self.concurrency,
            )
        self._consumer_tag = await self.broker.consume_jobs(
            self.queue, self._process_message, prefetch=self.concurrency
        )
        if self.config.prefix_affinity:
            self._affinity_consumer_tag = await self.broker.consume_jobs(
                affinity_queue_name(self.queue, self.worker_id),
                self._process_affinity_message,
                prefetch=self.concurrency,
            )

    async def _stop_role_consumers(self) -> None:
        for attr in (
            "_consumer_tag",
            "_affinity_consumer_tag",
            "_interactive_consumer_tag",
            "_decode_consumer_tag",
            "_adopt_consumer_tag",
        ):
            tag = getattr(self, attr, None)
            if tag is not None:
                try:
                    # requeue=False: in-flight deliveries finish under the
                    # normal settle paths; requeueing would double-deliver.
                    await self.broker.cancel(tag, requeue=False)
                except Exception:  # noqa: BLE001 — best-effort swap
                    pass
                setattr(self, attr, None)

    async def _maybe_switch_role(self) -> None:
        """Auto-role controller: compare shared-queue (prefill demand)
        against decode-pool depth and flip this worker's role when the
        ratio leaves the hysteresis band. Two guards prevent flapping:
        a check cadence (role_check_interval_s) and a minimum dwell in
        the current role (role_dwell_s)."""
        if self.role != "auto" or not self.running:
            return
        now = clock.monotonic()
        if now - self._role_checked_at < self.config.role_check_interval_s:
            return
        self._role_checked_at = now
        if now - self._role_since < self.config.role_dwell_s:
            return
        try:
            shared = await self.broker.get_queue_stats(self.queue)
            decode = await self.broker.get_queue_stats(
                decode_queue_name(self.queue)
            )
        except Exception:  # noqa: BLE001 — no stats, no switch
            return
        dp = shared.message_count_ready
        dd = decode.message_count_ready
        if dp is None or dd is None:
            return
        # +1 smoothing keeps the ratio finite and biases an all-empty
        # fleet toward staying put (ratio 1.0 is inside any sane band).
        ratio = (dp + 1.0) / (dd + 1.0)
        target = None
        if self.role_active == "prefill" and ratio < self.config.role_switch_lo:
            target = "decode"
        elif self.role_active == "decode" and ratio > self.config.role_switch_hi:
            target = "prefill"
        if target is not None:
            await self._switch_role(target, ratio=ratio)

    async def _switch_role(self, target: str, *, ratio: float = 0.0) -> None:
        prev = self.role_active
        await self._stop_role_consumers()
        self.role_active = target
        self.role_switches += 1
        self._role_since = clock.monotonic()
        emit_trace_event(
            self.worker_id,
            "role_switch",
            worker_id=self.worker_id,
            role_from=prev,
            role_to=target,
            depth_ratio=round(ratio, 3),
        )
        self.logger.info(
            "Role switch %s -> %s (shared:decode depth ratio %.2f)",
            prev,
            target,
            ratio,
        )
        await self._start_role_consumers()

    async def _start_extra_consumers(self) -> None:
        """Hook: attach additional consumers after the main job consumer
        is live (the TPU worker serves prefix-page fetch requests here).
        Base workers have none."""
        return None

    async def _process_affinity_message(self, message: DeliveredMessage) -> None:
        """Jobs from this worker's private ``<q>.w.<id>`` queue, with a
        claim-side orphan guard: a job routed here while the worker is
        draining (the submitter's cached fleet view can lag the shutdown
        by ~10 s) bounces straight back to the shared queue instead of
        waiting for the janitor's reclaim pass."""
        if not self.running:
            try:
                await self.broker.broker.publish(
                    self.queue,
                    message.body,
                    message_id=message.message_id,
                    headers=message.headers,
                )
                emit_trace_event(
                    message.message_id or "unknown",
                    "affinity_bounced",
                    worker_id=self.worker_id,
                )
                await message.ack()
            except Exception:  # noqa: BLE001 — transport down: redeliver
                await message.reject(requeue=True)
            return
        await self._process_message(message)

    def _remember_failure(self, job_id: str, reason: str) -> None:
        self._failure_reasons[job_id] = reason
        while len(self._failure_reasons) > _FAILURE_MEMORY_CAP:
            self._failure_reasons.pop(next(iter(self._failure_reasons)))

    def _deadline_expired(self, job: Job) -> bool:
        return job.deadline_at is not None and clock.wall() > job.deadline_at

    async def _dead_letter_deadline(
        self, job: Job, message: DeliveredMessage, trace: dict
    ) -> None:
        """A job whose deadline passed is dead-lettered as
        ``deadline_exceeded`` — explicitly filed on ``<q>.failed``, never
        silently dropped, so the submitter can count and requeue it."""
        self.jobs_deadline_exceeded += 1
        if job.priority_class == "interactive":
            self.jobs_deadline_exceeded_interactive += 1
        trace_event(trace, "deadline_exceeded", worker_id=self.worker_id)
        emit_trace_event(
            job.id, "deadline_exceeded", worker_id=self.worker_id
        )
        headers = dict(message.headers or {})
        headers["x-error"] = "deadline_exceeded"
        headers["x-failure-reason"] = "deadline_exceeded"
        headers["x-worker-id"] = self.worker_id
        headers["x-delivery-count"] = message.delivery_count
        headers.setdefault("x-death-queue", self.queue)
        try:
            await self.broker.broker.publish(
                self.queue + FAILED_SUFFIX,
                message.body,
                message_id=message.message_id,
                headers=headers,
            )
        except Exception:  # noqa: BLE001 — best-effort: never block the loop
            self.logger.warning("Deadline dead-letter failed", exc_info=True)
        finally:
            await message.ack()

    async def _quarantine(
        self, job: Job, message: DeliveredMessage, trace: dict, *, reason: str
    ) -> None:
        """File a poison job on ``<q>.quarantine``: it has crashed workers
        ``quarantine_attempts`` times fleet-wide (the broker's
        delivery_count IS the fleet-wide attempt counter — it rides the
        message, not any one worker). Quarantine keeps it out of the
        redelivery loop without losing the payload or its history."""
        self.jobs_quarantined += 1
        trace_event(
            trace,
            "quarantined",
            worker_id=self.worker_id,
            reason=reason,
            attempts=message.delivery_count + 1,
        )
        emit_trace_event(
            job.id, "quarantined", worker_id=self.worker_id, reason=reason
        )
        headers = dict(message.headers or {})
        headers["x-error"] = f"quarantined after repeated failures: {reason}"
        headers["x-failure-reason"] = reason
        headers["x-worker-id"] = self.worker_id
        headers["x-delivery-count"] = message.delivery_count + 1
        headers.setdefault("x-death-queue", self.queue)
        try:
            await self.broker.broker.publish(
                self.queue + QUARANTINE_SUFFIX,
                message.body,
                message_id=message.message_id,
                headers=headers,
            )
            await message.ack()
        except Exception:  # noqa: BLE001 — transport down: keep at-least-once
            await message.reject(requeue=True)

    def _note_engine_failure(self, reason: str) -> None:
        """Circuit breaker: M consecutive engine failures (not one bad
        job — *every* recent job failing) means this worker is the
        problem. Self-drain via the handoff path so its jobs move to
        healthy peers instead of churning here."""
        self._consecutive_failures += 1
        m = self.config.breaker_failures
        if m > 0 and self._consecutive_failures >= m and not self.breaker_tripped:
            self.breaker_tripped = True
            self.logger.error(
                "Circuit breaker: %d consecutive engine failures "
                "(last: %s); self-draining",
                self._consecutive_failures,
                reason,
            )
            emit_trace_event(
                self.worker_id,
                "breaker_tripped",
                worker_id=self.worker_id,
                failures=self._consecutive_failures,
            )
            self.request_shutdown()

    # --- the hot loop (reference base.py:137-245) -------------------------
    async def _process_message(self, message: DeliveredMessage) -> None:
        self._in_flight += 1
        self._drained.clear()
        start = clock.monotonic()
        try:
            job = Job.model_validate_json(message.body)
        except Exception as exc:  # malformed payload: dead-letter, never requeue
            self.logger.error("Unparseable job dead-lettered: %s", exc)
            self.jobs_failed += 1
            await self._dead_letter_unparseable(message, exc)
            self._settle_in_flight()
            return
        # Lifecycle trace: continue the submit-time record riding in the
        # job payload (or start one for jobs submitted without tracing).
        # A redelivered message re-reads the ORIGINAL payload, so events
        # stamped by a failed attempt never duplicate; the attempt count
        # survives as the broker's delivery_count.
        trace = trace_from_payload(job.extras()) or new_trace(job.id)
        # delivery_count counts PRIOR attempts (0 on first delivery — see
        # DeliveredMessage.redelivered), so it is the redelivery count.
        trace["redeliveries"] = message.delivery_count
        trace_event(
            trace,
            "claimed",
            worker_id=self.worker_id,
            delivery_count=message.delivery_count,
        )
        emit_trace_event(job.id, "claimed", worker_id=self.worker_id)
        self._job_traces[job.id] = trace
        # Claim-time self-healing guards (no-ops at default config):
        if self._deadline_expired(job):
            await self._dead_letter_deadline(job, message, trace)
            self._job_traces.pop(job.id, None)
            self._settle_in_flight()
            return
        n_quarantine = self.config.quarantine_attempts
        if n_quarantine > 0 and message.delivery_count >= n_quarantine:
            # Backstop for the reject-time check below: catches a copy
            # whose Nth failure landed on a worker that died mid-settle
            # (the redelivered message then carries delivery_count >= N).
            await self._quarantine(
                job,
                message,
                trace,
                reason=self._failure_reasons.get(job.id, "repeated_failures"),
            )
            self._job_traces.pop(job.id, None)
            self._settle_in_flight()
            return
        if self.role_active == "prefill" and isinstance(
            job.extras().get(RESUME_FIELD), dict
        ):
            # A prefill worker claimed a job that already carries resume
            # state (janitor reclaim or mid-switch delivery): its prompt
            # KV exists somewhere already — forward it to the decode pool
            # verbatim instead of re-prefilling (and instead of looping it
            # through another prefill_done handoff forever).
            await self._forward_to_decode(job, message)
            self._job_traces.pop(job.id, None)
            self._settle_in_flight()
            return
        try:
            output = await self._run_with_timeout(job)
            duration_ms = (clock.monotonic() - start) * 1000
            trace_event(trace, "finished", duration_ms=round(duration_ms, 3))
            emit_trace_event(
                job.id,
                "finished",
                worker_id=self.worker_id,
                duration_ms=round(duration_ms, 3),
            )
            result = self._build_result(job, output, duration_ms, trace=trace)
            offset = resume_offset(job.extras())
            if self._dedup.seen(job.id, offset):
                # Redelivered after a successful publish (e.g. the ack was
                # lost): the result is already out — publishing again
                # would double-count downstream. Settle silently.
                self.logger.info(
                    "Suppressing duplicate result for job %s (offset %d)",
                    job.id,
                    offset,
                    extra={"job_id": job.id},
                )
                emit_trace_event(
                    job.id, "duplicate_suppressed", worker_id=self.worker_id
                )
            else:
                await self._publish_result(result)
                self._dedup.record(job.id, offset)
            await message.ack()
            self.jobs_processed += 1
            self._consecutive_failures = 0
            self.total_duration_ms += duration_ms
            if self.jobs_processed % 100 == 0:
                self.logger.info(
                    "Processed %d jobs (avg %.0f ms)",
                    self.jobs_processed,
                    self.total_duration_ms / self.jobs_processed,
                )
        except DeadlineExceeded:
            # The deadline passed mid-flight (engine sweep, or a guard in
            # front of an expensive recovery path). Same terminal state as
            # the claim-time check: one explicit dead-letter, no requeue.
            await self._dead_letter_deadline(job, message, trace)
        except PrefillDone as exc:
            # Disaggregated phase boundary: prompt KV is complete; hand
            # the request to the decode pool (adoption offer to a chosen
            # decode peer, snapshot republish to <q>.decode as fallback).
            # Caught before JobHandoff — this is forward progress, and
            # before the failure ladders — it is not a failure.
            await self._handoff_to_decode(job, message, trace, exc)
        except JobHandoff as exc:
            # Drain-with-handoff: the engine resolved this request with a
            # snapshot of its partial progress instead of a completion.
            # Republish the job carrying that snapshot so a peer (or this
            # worker after restart) resumes mid-stream. Must be caught
            # before the generic ladders: a handoff is not a failure.
            await self._republish_for_resume(job, message, trace, exc)
        except (asyncio.TimeoutError, TimeoutError) as exc:
            # Hung engine step / stuck backend: the job slot must come
            # back. Requeue; the broker dead-letters past the redelivery
            # cap, so a deterministically-hanging job can't loop forever.
            self.logger.warning(
                "Job %s exceeded job_timeout_s=%.1fs (delivery %d), requeueing",
                job.id,
                self.config.job_timeout_s or 0.0,
                message.delivery_count,
            )
            self.jobs_failed += 1
            self.jobs_timed_out += 1
            self._remember_failure(job.id, "timeout")
            self._note_engine_failure("timeout")
            if await self._maybe_quarantine(job, message, trace, reason="timeout"):
                return
            emit_trace_event(
                job.id, "requeued", worker_id=self.worker_id, reason="timeout"
            )
            self._note_retry_exhausted(
                job, message.delivery_count, trace, reason="timeout"
            )
            await message.reject(requeue=True)
        except ValueError as exc:
            # Job is semantically invalid — retrying can't fix it. Ack &
            # drop (reference base.py:228-235).
            self.logger.error(
                "Job %s invalid, dropping: %s",
                job.id,
                exc,
                extra={"job_id": job.id},
            )
            self.jobs_failed += 1
            emit_trace_event(
                job.id, "dropped", worker_id=self.worker_id, reason=str(exc)
            )
            await message.ack()
        except StepCompileError as exc:
            # The engine cannot compile a step program: the worker is
            # stopping (fail_fatally) and the job is not at fault.
            # Settling it here would only hand it straight back to this
            # worker until its redeliveries run out, so leave it
            # unsettled, as a crash would: the broker returns it to the
            # queue when this connection closes, for a worker that can
            # compile.
            self.logger.error(
                "Job %s not run, left for redelivery: %s",
                job.id,
                exc,
                extra={"job_id": job.id},
            )
            emit_trace_event(
                job.id, "requeued", worker_id=self.worker_id,
                reason="step_compile_error",
            )
        except DeviceFaultError as exc:
            # Classified device fault the engine could not absorb
            # in-process (rebuild unavailable/failed, OOM ladder dry).
            # Same requeue/quarantine ladder as a generic engine error,
            # but the machine-readable class (hung_dispatch, hbm_oom, ...)
            # rides the dead-letter / quarantine headers so `monitor
            # errors` distinguishes a wedged chip from a bad job.
            self.logger.warning(
                "Job %s hit device fault %s (delivery %d), requeueing: %s",
                job.id,
                exc.failure_reason,
                message.delivery_count,
                exc,
                extra={"job_id": job.id},
            )
            self.jobs_failed += 1
            reason = exc.failure_reason
            self._remember_failure(job.id, reason)
            self._note_engine_failure(reason)
            if await self._maybe_quarantine(job, message, trace, reason=reason):
                return
            emit_trace_event(
                job.id, "requeued", worker_id=self.worker_id, reason=reason
            )
            self._note_retry_exhausted(
                job, message.delivery_count, trace, reason=reason
            )
            await message.reject(requeue=True)
        except Exception as exc:  # noqa: BLE001 — transient: requeue
            self.logger.warning(
                "Job %s failed (delivery %d), requeueing: %s",
                job.id,
                message.delivery_count,
                exc,
                extra={"job_id": job.id},
            )
            self.jobs_failed += 1
            reason = f"engine_error:{type(exc).__name__}"
            self._remember_failure(job.id, reason)
            self._note_engine_failure(reason)
            if await self._maybe_quarantine(job, message, trace, reason=reason):
                return
            emit_trace_event(
                job.id, "requeued", worker_id=self.worker_id, reason=str(exc)
            )
            self._note_retry_exhausted(
                job, message.delivery_count, trace, reason=str(exc)
            )
            await message.reject(requeue=True)
        finally:
            self._job_traces.pop(job.id, None)
            self._settle_in_flight()

    async def _maybe_quarantine(
        self, job: Job, message: DeliveredMessage, trace: dict, *, reason: str
    ) -> bool:
        """Reject-time quarantine check: this failure is attempt
        ``delivery_count + 1``; at the Nth fleet-wide attempt the job
        quarantines (with the in-hand failure reason) instead of
        requeueing. Returns True when the message was settled here."""
        n = self.config.quarantine_attempts
        if n > 0 and message.delivery_count + 1 >= n:
            await self._quarantine(job, message, trace, reason=reason)
            return True
        return False

    def _note_retry_exhausted(
        self, job: Job, delivery_count: int, trace: dict, *, reason: str
    ) -> None:
        """Flag a requeue that the broker will dead-letter (this attempt
        pushed the job past the redelivery cap). The trace record itself
        never ships on a requeue — redelivery re-reads the original
        payload — so `llmq-tpu trace` recovers this moment from the DLQ
        headers; the event here feeds the live metrics plane."""
        if delivery_count + 1 > self.config.max_redeliveries:
            trace_event(
                trace,
                "retry_exhausted",
                worker_id=self.worker_id,
                redeliveries=delivery_count,
                reason=reason,
            )
            emit_trace_event(
                job.id,
                "retry_exhausted",
                worker_id=self.worker_id,
                redeliveries=delivery_count,
            )

    async def _republish_for_resume(
        self,
        job: Job,
        message: DeliveredMessage,
        trace: dict,
        exc: JobHandoff,
    ) -> None:
        """Publish a draining request back to the job queue with its
        engine snapshot riding under ``RESUME_FIELD``, then ack the
        original delivery — at-least-once safe: until the ack lands the
        original message survives, and the result deduper suppresses the
        double-publish if both copies eventually complete. A snapshot-less
        handoff (the request never entered the engine) requeues the
        original message untouched."""
        if exc.snapshot_b64 is None:
            emit_trace_event(
                job.id, "requeued", worker_id=self.worker_id, reason="shutdown"
            )
            await message.reject(requeue=True)
            return
        try:
            payload = json.loads(message.body)
        except Exception:  # noqa: BLE001 — parsed once already; paranoia
            await message.reject(requeue=True)
            return
        trace_event(
            trace,
            "handoff",
            worker_id=self.worker_id,
            emitted=exc.emitted,
        )
        payload[RESUME_FIELD] = {
            "snapshot": exc.snapshot_b64,
            "offset": exc.emitted,
        }
        # The republished copy carries the accumulated trace so the
        # resuming worker's record keeps the full lifecycle (submitted →
        # claimed → handoff → claimed → finished).
        payload[TRACE_FIELD] = trace
        emit_trace_event(
            job.id, "handoff", worker_id=self.worker_id, emitted=exc.emitted
        )
        try:
            body = json.dumps(payload).encode("utf-8")
            # Resume blobs share the host-memory budget (accounted, never
            # refused: refusing one would strand a request mid-drain).
            from llmq_tpu.utils.host_mem import get_governor

            get_governor().note_resume_blob(len(body))
            # A decode-role worker's in-flight requests belong to the
            # decode pool — republishing them to the shared queue would
            # hand KV-complete work back to prefill workers.
            await self.broker.broker.publish(
                self._resume_queue(),
                body,
                message_id=job.id,
            )
        except Exception:  # noqa: BLE001 — transport down mid-shutdown
            # Couldn't ship the snapshot: fall back to plain redelivery
            # (recompute-from-scratch, still exactly-one-result).
            self.logger.warning(
                "Resume republish failed for job %s; requeueing plain",
                job.id,
                exc_info=True,
            )
            await message.reject(requeue=True)
            return
        self.logger.info(
            "Job %s handed off with %d tokens generated",
            job.id,
            exc.emitted,
            extra={"job_id": job.id},
        )
        await message.ack()

    def _resume_queue(self) -> str:
        """Where this worker's resumable handoffs republish: decode-role
        workers keep KV-complete work inside the decode pool; everyone
        else uses the shared queue (monolith behavior)."""
        if self.role_active == "decode":
            return decode_queue_name(self.queue)
        return self.queue

    async def _forward_to_decode(
        self, job: Job, message: DeliveredMessage
    ) -> None:
        """Move a resume-carrying job off a prefill worker onto the decode
        pool, payload untouched (trace and snapshot ride along)."""
        try:
            await self.broker.broker.publish(
                decode_queue_name(self.queue),
                message.body,
                message_id=message.message_id,
                headers=message.headers,
            )
            emit_trace_event(
                job.id, "kv_handoff", worker_id=self.worker_id, path="forward"
            )
            await message.ack()
        except Exception:  # noqa: BLE001 — transport down: redeliver
            await message.reject(requeue=True)

    async def _handoff_to_decode(
        self,
        job: Job,
        message: DeliveredMessage,
        trace: dict,
        exc: PrefillDone,
    ) -> None:
        """Settle a prefill-complete job into the decode pool.

        The prompt-KV snapshot rides under ``RESUME_FIELD`` (offset 0: no
        output token was kept — the adopter re-samples the first token from
        the re-derived key chain, bit-identically). Preferred path: offer
        the payload to a rendezvous-picked decode peer over its
        ``<q>.kv.<peer>`` queue (deepest prefix-affinity match wins); when
        no peer accepts within ``handoff_timeout_s``, republish to the
        shared ``<q>.decode`` queue. Either way the publish lands BEFORE
        the ack, so a crash in the window leaves the original message to
        redeliver and the result deduper collapses the double."""
        try:
            payload = json.loads(message.body)
        except Exception:  # noqa: BLE001 — parsed once already; paranoia
            await message.reject(requeue=True)
            return
        trace_event(trace, "prefill_done", worker_id=self.worker_id)
        emit_trace_event(job.id, "prefill_done", worker_id=self.worker_id)
        payload[RESUME_FIELD] = {
            "snapshot": exc.snapshot_b64,
            "offset": 0,
            # Wall-clock handoff stamp: the adopting decode worker turns
            # it into the handoff-latency sample in its heartbeats.
            "handoff_at": clock.wall(),
        }
        # The boundary event must ride INSIDE the shipped payload (the
        # adopter's result trace is built from it), so stamp it before
        # serializing — optimistically as the ship path, rewritten below
        # if the offer misses and the snapshot fallback carries the KV.
        trace_event(
            trace, "kv_handoff", worker_id=self.worker_id, path="ship"
        )
        payload[TRACE_FIELD] = trace
        body = json.dumps(payload).encode("utf-8")
        from llmq_tpu.utils.host_mem import get_governor

        get_governor().note_resume_blob(len(body))
        shipped = False
        try:
            shipped = await self._ship_to_decode_peer(job, body)
        except Exception:  # noqa: BLE001 — offer failed: take the fallback
            self.logger.debug("Decode adoption offer failed", exc_info=True)
        if shipped:
            self.handoffs_shipped += 1
            emit_trace_event(
                job.id, "kv_handoff", worker_id=self.worker_id, path="ship"
            )
            await message.ack()
            return
        trace["events"][-1]["path"] = "snapshot"
        body = json.dumps(payload).encode("utf-8")
        try:
            await self.broker.broker.publish(
                decode_queue_name(self.queue), body, message_id=job.id
            )
        except Exception:  # noqa: BLE001 — transport down
            self.logger.warning(
                "Decode-pool republish failed for job %s; requeueing plain",
                job.id,
                exc_info=True,
            )
            await message.reject(requeue=True)
            return
        self.handoffs_fallback += 1
        emit_trace_event(
            job.id, "kv_handoff", worker_id=self.worker_id, path="snapshot"
        )
        await message.ack()

    async def _ship_to_decode_peer(self, job: Job, body: bytes) -> bool:
        """Hook: offer a prefill-complete payload to a decode peer for
        direct adoption; True only once a peer durably holds it. Base
        workers have no peer discovery — the snapshot fallback covers
        them."""
        return False

    async def _run_with_timeout(self, job: Job) -> str:
        timeout = self.config.job_timeout_s
        if timeout is None or timeout <= 0:
            return await self._process_job(job)
        return await asyncio.wait_for(self._process_job(job), timeout=timeout)

    async def _dead_letter_unparseable(
        self, message: DeliveredMessage, exc: Exception
    ) -> None:
        """Corrupt payloads can't round-trip the normal redelivery path
        (they never parse into a Job), but they must not vanish either —
        file them in ``<queue>.failed`` so `llmq-tpu errors` can show what
        arrived and why. Settles the message on every path (reject without
        requeue: the copy now lives in the DLQ)."""
        headers = dict(message.headers or {})
        headers["x-error"] = f"unparseable job payload: {exc}"
        headers["x-worker-id"] = self.worker_id
        headers.setdefault("x-death-queue", self.queue)
        emit_trace_event(
            message.message_id or "unparseable",
            "dead_lettered",
            worker_id=self.worker_id,
            reason=str(exc),
        )
        try:
            await self.broker.broker.publish(
                self.queue + FAILED_SUFFIX,
                message.body,
                message_id=message.message_id,
                headers=headers,
            )
        except Exception:  # noqa: BLE001 — best-effort: never block the loop
            self.logger.warning(
                "Could not dead-letter unparseable payload", exc_info=True
            )
        finally:
            await message.reject(requeue=False)

    def _settle_in_flight(self) -> None:
        self._in_flight -= 1
        if self._in_flight <= 0:
            self._drained.set()

    def _build_result(
        self,
        job: Job,
        output: str,
        duration_ms: float,
        trace: Optional[dict] = None,
    ) -> Result:
        """Result with extra-field passthrough (reference base.py:164-186).

        Built dict-first so a job extra named like a Result field (e.g. a
        dataset with a ``result`` column) can't TypeError the hot loop —
        Result's own fields win, the colliding extra is preserved under
        ``job_<name>``.
        """
        prompt_repr = (
            job.get_formatted_prompt() if job.prompt is not None else ""
        )
        payload = dict(job.extras())
        # The resume blob must not ride into the result (it is large and
        # spent); keep only the offset the resumed run started from.
        resume = payload.pop(RESUME_FIELD, None)
        if isinstance(resume, dict):
            payload["resume_offset"] = resume_offset({RESUME_FIELD: resume})
        reserved = {
            "id": job.id,
            "prompt": prompt_repr,
            "result": output,
            "worker_id": self.worker_id,
            "duration_ms": duration_ms,
        }
        for key in (*reserved, "timestamp", "usage"):
            if key in payload:
                payload[f"job_{key}"] = payload.pop(key)
        payload.update(reserved)
        if trace is not None:
            # The accumulated record (submit-time events + this worker's)
            # supersedes the job-carried copy in the passthrough.
            payload[TRACE_FIELD] = trace
        return Result.model_validate(payload)

    async def _publish_result(self, result: Result) -> None:
        if self.pipeline is not None and self.stage_name is not None:
            await self.broker.publish_pipeline_result(
                self.pipeline, self.stage_name, result
            )
        else:
            await self.broker.publish_result(self.queue, result)

    # --- heartbeats -------------------------------------------------------
    async def _publish_heartbeat(self) -> None:
        stats = self.broker.session_stats
        health = WorkerHealth(
            worker_id=self.worker_id,
            status="running" if self.running else "stopping",
            last_seen=utcnow(),
            jobs_processed=self.jobs_processed,
            avg_duration_ms=(
                self.total_duration_ms / self.jobs_processed
                if self.jobs_processed
                else None
            ),
            queue=self.queue,
            engine_stats=self._stats_with_robustness(),
            reconnects=stats.reconnects if stats is not None else None,
            metrics=get_registry().summary() or None,
            prefix_chains=self._prefix_chains(),
            last_dispatch_ok_age_s=self._dispatch_ok_age(),
            integrity=self._integrity_status(),
            role=self._worker_role(),
        )
        try:
            # The liveness/integrity/role fields are excluded (not
            # serialized as null) when their machinery is off, so
            # default-config heartbeat payloads stay byte-identical to
            # older workers.
            unset = {
                name
                for name in ("last_dispatch_ok_age_s", "integrity", "role")
                if getattr(health, name) is None
            }
            await self.broker.broker.publish(
                self.queue + HEALTH_SUFFIX,
                health.model_dump_json(exclude=unset or None).encode(
                    "utf-8"
                ),
            )
        except Exception:  # noqa: BLE001 — heartbeats are best-effort
            self.logger.debug("Heartbeat publish failed", exc_info=True)

    def _engine_stats(self) -> Optional[dict]:
        """Subclasses may surface engine metrics (batch occupancy etc.)."""
        return None

    def _dispatch_ok_age(self) -> Optional[float]:
        """Seconds since the engine's last clean device dispatch, or None
        when no watchdog is running (the default — the heartbeat field is
        then omitted entirely)."""
        return None

    def _integrity_status(self) -> Optional[str]:
        """Subclasses advertise the engine's numerics-integrity verdict
        ('ok' / 'suspect') so the affinity janitor can reclaim a worker
        whose device keeps failing canaries; None when every integrity
        knob is off (the default — the field is omitted entirely)."""
        return None

    def _stats_with_robustness(self) -> Optional[dict]:
        """Engine stats plus fleet self-healing counters (superset-only:
        nothing is added until a counter moves, so pre-existing heartbeat
        consumers see unchanged payloads at default config)."""
        stats = dict(self._engine_stats() or {})
        for name in (
            "jobs_deadline_exceeded",
            "jobs_deadline_exceeded_interactive",
            "jobs_quarantined",
        ):
            value = getattr(self, name, 0)
            if value:
                stats[name] = value
        if self.breaker_tripped:
            stats["breaker_tripped"] = True
        if self._loop_lag.ticks:
            # A wedged event loop is an operator's signal.
            stats["loop_lag_max_ms"] = round(self._loop_lag.max_ms, 3)
        # Disaggregated-serving counters (superset-only, like the rest).
        if self.role == "auto":
            stats["role_mode"] = "auto"
        for name in (
            "role_switches",
            "handoffs_shipped",
            "handoffs_fallback",
            "jobs_adopted",
        ):
            value = getattr(self, name, 0)
            if value:
                stats[name] = value
        if self._handoff_ms:
            vals = sorted(self._handoff_ms)
            stats["handoff_ms_p50"] = round(vals[len(vals) // 2], 3)
            stats["handoff_ms_p95"] = round(
                vals[min(len(vals) - 1, int(len(vals) * 0.95))], 3
            )
        return stats or None

    def _worker_role(self) -> Optional[str]:
        """The role advertised in heartbeats: the currently-served role
        for disaggregated workers, None (field omitted) for unified."""
        return None if self.role == "unified" else self.role_active

    def _prefix_chains(self) -> Optional[list]:
        """Subclasses may advertise hot prefix-chain digests (hex) for
        prefix-affinity routing; None omits the field entirely."""
        return None
