"""TPU inference worker (reference: ``llmq/workers/vllm_worker.py:11-201``).

Where the reference constructed a vLLM ``AsyncLLMEngine`` on CUDA GPUs,
this worker builds the native engine on the local TPU slice:

- auto-TP parity (``vllm_worker.py:62-89``): no ``-tp`` flag → the worker
  claims every device JAX exposes, divided by the data-parallel degree;
- model spec: a local HF checkpoint directory (safetensors), or
  ``preset://<name>`` for a random-weight architecture preset (tests and
  hardware benchmarks without downloads);
- per-job sampling overrides (temperature/top_p/top_k/max_tokens/stop/seed
  via Job extra fields) — the reference hardcoded temp 0.7;
- engine stats ride the worker heartbeat (batch occupancy, KV-page
  utilization, tokens/sec).
"""

from __future__ import annotations

import asyncio
import hashlib
import itertools
import json
import os
import socket
import time
from collections import OrderedDict
from functools import partial
from pathlib import Path
from typing import Any, Callable, List, Optional

from llmq_tpu.broker.manager import (
    ctl_queue_name,
    decode_adopt_queue_name,
    job_affinity_text,
    kv_fetch_queue_name,
    rendezvous_pick,
    stream_queue_name,
)
from llmq_tpu.core.models import Job
from llmq_tpu.obs import emit_trace_event, trace_event, trace_event_at
from llmq_tpu.obs.spans import merge_dumps
from llmq_tpu.utils import clock
from llmq_tpu.utils.hashing import (
    text_prefix_chain,
    token_fold,
    token_prefix_chain,
)
from llmq_tpu.utils.aio import spawn
from llmq_tpu.utils.host_mem import get_governor
from llmq_tpu.workers.base import BaseWorker, DeadlineExceeded
from llmq_tpu.workers.resume import RESUME_FIELD, JobHandoff, PrefillDone

PRESET_SCHEMES = ("preset://", "dummy://", "random://")

# Prefix-affinity plumbing: how many text-chain digests this worker tracks
# (LRU of per-chunk hit counters), how many it advertises per heartbeat,
# and how long a cross-worker page fetch may stall a job before the worker
# gives up and recomputes the prefix locally.
CHAIN_TRACK_CAP = 512
CHAIN_ADVERTISE_N = 8
PREFIX_FETCH_TIMEOUT_S = 2.0

# A peer that timed out a fetch is skipped for this long (negative cache):
# its queue may be an orphan the janitor hasn't reclaimed yet, and every
# fetch against it stalls a job by the full fetch timeout.
PEER_NEGATIVE_CACHE_S = 30.0


def _chunk_digest(chunk: str) -> str:
    """Transport-level digest of one serialized prefix chunk. The chunk
    codec self-verifies its *payload* on ingest; this outer digest lets
    the requester reject a corrupted ship before paying deserialization."""
    return hashlib.blake2b(chunk.encode("utf-8"), digest_size=16).hexdigest()


class TPUWorker(BaseWorker):
    def __init__(
        self,
        queue: str,
        *,
        model: str,
        tensor_parallel: Optional[int] = None,
        data_parallel: int = 1,
        sequence_parallel: int = 1,
        pipeline_parallel: Optional[int] = None,
        max_num_seqs: Optional[int] = None,
        max_model_len: Optional[int] = None,
        dtype: str = "bfloat16",
        kv_dtype: Optional[str] = None,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        prefill_chunk_size: Optional[int] = None,
        enable_prefix_caching: bool = False,
        prefix_host_gb: Optional[float] = None,
        decode_block: Optional[int] = None,
        spec_tokens: Optional[int] = None,
        tp_overlap: Optional[str] = None,
        mixed_step: Optional[str] = None,
        engine_factory: Optional[Callable[["TPUWorker"], Any]] = None,
        **kwargs,
    ) -> None:
        self.model = model
        self.tensor_parallel = tensor_parallel
        self.data_parallel = data_parallel
        self.sequence_parallel = sequence_parallel
        # Stage count of the two-tier (pp outer over hosts, dp/sp/tp
        # inner per host) deployment shape; flag > LLMQ_PIPELINE_PARALLEL
        # env > 1 (classic single-stage mesh).
        self.pipeline_parallel = int(
            pipeline_parallel
            or os.environ.get("LLMQ_PIPELINE_PARALLEL", "1")
            or 1
        )
        self._max_num_seqs = max_num_seqs
        self._max_model_len = max_model_len
        self._dtype = dtype
        self._kv_dtype = kv_dtype
        self._page_size = page_size
        self._num_pages = num_pages
        self._prefill_chunk_size = prefill_chunk_size
        self._enable_prefix_caching = enable_prefix_caching
        self._prefix_host_gb = prefix_host_gb
        self._decode_block = decode_block
        self._spec_tokens = spec_tokens
        self._tp_overlap = tp_overlap
        self._mixed_step = mixed_step
        # Test/sim seam: a callable(worker) -> engine replaces the whole
        # JAX engine build (and skips the tp-overlap probe), so the
        # full worker control plane runs with a stub engine and no
        # accelerator. None (the default) builds the real AsyncEngine.
        self._engine_factory = engine_factory
        self.engine = None
        self._usage: dict = {}
        # Terminal finish_reason held between generate() and
        # _build_result, which pops it onto the result as an extra so the
        # gateway's blocking path reports the same reason ("length",
        # "cancelled", ...) the stream done frame carries.
        self._finish_reasons: dict = {}
        # Result-payload integrity (LLMQ_RESULT_DIGEST): emitted token
        # ids held between generate() and _build_result, which pops them
        # onto the result with their blake2b digest.
        self._result_tokens: dict = {}
        # Checkpoint-load checksum ledger (weights.py streams it in);
        # written once per _build_core, so bounded by the tensor count.
        self._load_checksums: dict = {}
        # Prefix-affinity state: text-chain digest → times a processed job
        # walked that chunk (capped LRU; the top advertises in heartbeats),
        # the kv-fetch consumer tag, ship counters, and a lock serializing
        # peer fetches (one shared reply queue per worker).
        self._chain_hits: "OrderedDict[str, int]" = OrderedDict()
        self._kv_consumer_tag: Optional[str] = None
        self._fetch_lock = asyncio.Lock()
        self.prefix_chunks_served = 0
        self.prefix_chunks_fetched = 0
        self.prefix_fetch_timeouts = 0
        # KV-ship hardening state: per-requester in-flight serve counts
        # (capped by Config.peer_serve_concurrency), a short negative
        # cache of peers that timed out (peer -> monotonic expiry), and
        # failure-class counters surfaced via heartbeats.
        self._peer_serving: dict = {}
        self._dead_peers: dict = {}
        self.kv_fetch_failures = 0
        self.kv_serve_busy_rejects = 0
        # Online-serving plane: per-job token-delta stream state (jobs
        # that carried a truthy ``stream`` extra), the control-queue
        # consumer tag (gateway-published cancels), background flush
        # tasks, and serving counters for heartbeats.
        self._streams: dict = {}
        self._stream_tasks: set = set()
        self._ctl_consumer_tag: Optional[str] = None
        self.stream_frames_published = 0
        self.jobs_cancelled = 0
        super().__init__(queue, **kwargs)
        # Prefetch must exceed the continuous batch's slot count or the
        # engine starves: with slots=192 and the default prefetch=100,
        # occupancy silently caps at 52%. When the user didn't pass an
        # explicit -c, keep ~1.5x slots in flight (the reference's tuned
        # ratio: VLLM_QUEUE_PREFETCH=1250 for 750 slots).
        slots = max_num_seqs or self.config.max_num_seqs
        if kwargs.get("concurrency") is None and slots:
            self.concurrency = max(self.concurrency, slots + slots // 2)
        # Fail the config contradiction NOW — EngineCore would also raise,
        # but only after minutes of checkpoint streaming.
        if (self._enable_prefix_caching or self.config.enable_prefix_caching) and not (
            self._prefill_chunk_size or self.config.prefill_chunk_size
        ):
            raise ValueError(
                "--prefix-caching requires --prefill-chunk (or "
                "LLMQ_PREFILL_CHUNK): only chunked prefill can start "
                "mid-prompt"
            )
        if self._prefix_host_gb and not (
            self._enable_prefix_caching or self.config.enable_prefix_caching
        ):
            raise ValueError(
                "--prefix-host-gb requires --prefix-caching: the host "
                "tier parks pages the device prefix cache evicts"
            )
        if (self._mixed_step or self.config.mixed_step or "off").lower() == "on" and not (
            self._prefill_chunk_size or self.config.prefill_chunk_size
        ):
            raise ValueError(
                "--mixed-step on requires --prefill-chunk (or "
                "LLMQ_PREFILL_CHUNK): the fused dispatch piggybacks "
                "fixed-size prefill chunks"
            )

    # --- identity (reference vllm_worker.py:39-50) ------------------------

    # In-process instance counter: host+pid alone is NOT unique — disagg
    # tests (and any embedder) run a prefill and a decode worker in one
    # process, and identical ids made peer discovery treat the pair as
    # one worker, so KV handoff silently took the snapshot fallback
    # every time (PERF_NOTES round 16). Role + a per-process nonce keeps
    # the id unique AND self-describing in heartbeat/queue names.
    _instance_counter = itertools.count()

    def _generate_worker_id(self) -> str:
        tp = self.tensor_parallel or "auto"
        role = (self.config.worker_role or "unified").lower()
        nonce = next(TPUWorker._instance_counter)
        return (
            f"tpu-worker-{socket.gethostname()}-{os.getpid()}"
            f"-tp{tp}-dp{self.data_parallel}-{role}-i{nonce}"
        )

    # --- engine lifecycle -------------------------------------------------
    async def _initialize_processor(self) -> None:
        # Engine construction compiles XLA programs and possibly loads a
        # multi-GB checkpoint: run off the event loop so broker heartbeats
        # and signals stay live. A ``tp_overlap=auto`` probe runs FIRST,
        # while no JAX backend is initialised in this process: a chip
        # belongs to one process, so the probing child can only have it
        # before we do (kernel_autotune refuses, loudly, once we hold it).
        loop = asyncio.get_running_loop()
        if self._engine_factory is None:
            await loop.run_in_executor(None, self._autotune_tp_overlap)
        self.engine = await loop.run_in_executor(None, self._build_engine)
        # The fault callbacks fire on the engine thread; breaker
        # accounting and shutdown belong on the event loop.
        self.engine.on_device_fault = (
            lambda reason: loop.call_soon_threadsafe(
                self._note_device_fault, reason
            )
        )
        self.engine.on_fatal = lambda exc: loop.call_soon_threadsafe(
            self.fail_fatally, exc
        )
        # The engine's ring goes on for as long as a profile of the
        # process is being taken; the loop's ring goes with it.
        self.engine.on_tracing = lambda on: loop.call_soon_threadsafe(
            self.spans.follow_profiler, on
        )
        self.logger.info("Engine ready: %s", self._engine_stats())

    def _model_config_host(self):
        """Resolve the model architecture host-side (no device contact):
        preset lookup or the checkpoint's config.json."""
        try:
            if self.model.startswith(PRESET_SCHEMES):
                from llmq_tpu.models.presets import get_preset

                return get_preset(self.model.split("://", 1)[1] or "tiny")
            from llmq_tpu.models.config import ModelConfig

            return ModelConfig.from_pretrained(Path(self.model))
        except Exception:  # noqa: BLE001 — _build_engine reports properly
            return None

    def _autotune_tp_overlap(self) -> None:
        """Resolve ``tp_overlap=auto`` by A/B-ing the ppermute rings
        against GSPMD on this host's chips — run HERE, before any JAX
        backend initialises in this process, because the probing child
        needs exclusive libtpu. Exports the choice via ``LLMQ_TP_OVERLAP``
        so ``resolve_tp_overlap`` inside the engine picks it up without
        re-probing. No-op unless the configured mode is 'auto' (an
        explicit env pin already wins everywhere)."""
        if os.environ.get("LLMQ_TP_OVERLAP"):
            return
        mode = (self._tp_overlap or self.config.tp_overlap or "off").lower()
        if mode != "auto":
            return
        cfg = self._model_config_host()
        if cfg is None:
            return
        from llmq_tpu.engine.kernel_autotune import autotune_tp_overlap

        choice = autotune_tp_overlap(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            max_seqs=self._max_num_seqs or self.config.max_num_seqs or 192,
            logger=self.logger,
        )
        if choice is not None:
            os.environ["LLMQ_TP_OVERLAP"] = choice

    def _build_core(self):
        """Construct a fresh EngineCore (mesh, params, compiled programs)
        — the unit the device-fault recovery path rebuilds in-process.
        First build and post-fault rebuilds share this exact code so a
        recovered engine is configured identically to the original."""
        import jax
        import jax.numpy as jnp

        from llmq_tpu.engine.engine import EngineConfig, EngineCore
        from llmq_tpu.engine.tokenizer import ByteTokenizer, HFTokenizer
        from llmq_tpu.models.transformer import init_params
        from llmq_tpu.parallel import make_mesh
        from llmq_tpu.parallel.sharding import param_shardings
        from llmq_tpu.utils.platform import on_tpu

        mesh = make_mesh(
            tensor_parallel=self.tensor_parallel,
            data_parallel=self.data_parallel,
            sequence_parallel=self.sequence_parallel,
            pipeline_parallel=self.pipeline_parallel,
        )
        # int8 = weight-only quantization: weights stored int8 (half the
        # HBM footprint/bandwidth — what fits a ~9B model on one 16 GB
        # chip), compute and KV stay bf16 (models/quant.py). int4 =
        # AWQ-style group quantization of the layer weights (quarter the
        # bytes; embed/lm_head stay int8).
        quantize = self._dtype if self._dtype in ("int8", "int4") else False
        dtype = {
            "bfloat16": jnp.bfloat16,
            "float32": jnp.float32,
            "int8": jnp.bfloat16,
            "int4": jnp.bfloat16,
        }[self._dtype]

        spec = self.model
        if spec.startswith(PRESET_SCHEMES):
            from llmq_tpu.models.presets import get_preset

            name = spec.split("://", 1)[1] or "tiny"
            model_config = get_preset(name)
            if model_config.layer_pattern is not None and self.role != "unified":
                raise ValueError(
                    f"role={self.role} is not supported for a model with a "
                    "layer pattern: it has no snapshot to hand from a "
                    "prefill pool to a decode pool (a per-sequence state "
                    "cannot be moved; moving a latent pool is not built)"
                )
            self.logger.info("Preset model %s (random weights)", name)
            init = partial(
                init_params, model_config, dtype=dtype, quantize=quantize
            )
            if quantize or self.pipeline_parallel > 1:
                # Eager: quantize-at-init frees each full-precision
                # tensor by donation as it goes (a 9B int8 tree fits a
                # chip its bf16 tree does not), and pp stages place their
                # own slices in EngineCore.
                params = init(jax.random.key(0))
            else:
                # Build every weight already on its shards: unsharded on
                # the default device, a model sized for the whole mesh
                # (qwen2.5-7b bf16 is 15.2 GB) cannot start on one 16 GB
                # chip. Same values either way — the random bits do not
                # depend on the sharding.
                params = jax.jit(
                    init, out_shardings=param_shardings(mesh, model_config)
                )(jax.random.key(0))
            tokenizer = ByteTokenizer()
        else:
            from llmq_tpu.engine.weights import load_checkpoint
            from llmq_tpu.models.config import ModelConfig

            path = Path(spec)
            model_config = ModelConfig.from_pretrained(path)
            # mesh-aware streaming: each tensor lands on its shards
            # directly; host RSS stays ~one tensor (weights.py docstring).
            # The ledger records what the checkpoint bytes hashed to at
            # load — the provenance record a weight-audit mismatch is
            # compared against when deciding load-vs-HBM corruption.
            self._load_checksums = {}
            params = load_checkpoint(
                path,
                model_config,
                dtype=dtype,
                mesh=mesh,
                quantize=quantize,
                checksum_ledger=self._load_checksums,
            )
            tokenizer = HFTokenizer(spec)

        overrides = {}
        if self._max_num_seqs or self.config.max_num_seqs:
            overrides["max_num_seqs"] = self._max_num_seqs or self.config.max_num_seqs
        max_len = self._max_model_len or self.config.max_model_len
        if max_len:
            overrides["max_model_len"] = min(
                max_len, model_config.max_position_embeddings
            )
        else:
            overrides["max_model_len"] = min(
                8192, model_config.max_position_embeddings
            )
        if self._page_size:
            overrides["page_size"] = self._page_size
        elif on_tpu():
            # 128-token pages: the decode kernel moves one page per
            # grid step, and 16 KB transfers are latency-bound ~6x
            # off the HBM bandwidth floor (measured round 2); 128
            # tokens make them 64 KB and quarter the grid. The
            # engine's 32-token default is CPU-test-friendly only.
            overrides["page_size"] = 128
        if self._num_pages:
            overrides["num_pages"] = self._num_pages
        chunk = self._prefill_chunk_size or self.config.prefill_chunk_size
        if chunk:
            overrides["prefill_chunk_size"] = chunk
        if self._enable_prefix_caching or self.config.enable_prefix_caching:
            overrides["enable_prefix_caching"] = True
        # Host-RAM cold tier for evicted prefix pages: per-worker flag >
        # LLMQ_PREFIX_HOST_GB env (the engine resolves the env pin).
        if self._prefix_host_gb:
            overrides["prefix_host_gb"] = self._prefix_host_gb
        # Fused decode blocks: per-worker flag > LLMQ_DECODE_BLOCK env >
        # default 1 (per-token dispatch).
        block = self._decode_block or self.config.decode_block
        if block and block > 1:
            overrides["decode_block"] = block
        # Lossless speculative decoding: per-worker flag > LLMQ_SPEC_TOKENS
        # env > default 0 (off). stats()/heartbeats then carry
        # spec_proposed/spec_accepted/acceptance_rate automatically.
        spec = self._spec_tokens or self.config.spec_tokens
        if spec and spec > 0:
            overrides["spec_tokens"] = spec
        # Tensor-parallel overlap: per-worker flag > LLMQ_TP_OVERLAP env >
        # default off. The engine resolves 'auto' (and reports the
        # resolved mode in stats() → heartbeats).
        ov = (self._tp_overlap or self.config.tp_overlap or "off").lower()
        if ov != "off":
            overrides["tp_overlap"] = ov
        # Piggyback scheduling: per-worker flag > LLMQ_MIXED_STEP env >
        # default off. The engine re-checks the prefill-chunk requirement
        # and reports mixed_steps/mixed_prefill_tokens in stats().
        mx = (self._mixed_step or self.config.mixed_step or "off").lower()
        if mx != "off":
            overrides["mixed_step"] = mx
        # KV cache dtype: per-worker flag > LLMQ_KV_DTYPE env > the
        # compute dtype. "fp8" stores pages as float8_e5m2 (half the KV
        # bytes; kernels convert on-chip) — vLLM kv-cache-dtype parity.
        kv = self._kv_dtype or self.config.kv_dtype
        engine_config = EngineConfig(
            hbm_utilization=self.config.hbm_utilization,
            kv_dtype=dtype if kv in (None, "", "auto") else kv,
            **overrides,
        )
        return EngineCore(
            model_config,
            params,
            tokenizer,
            mesh=mesh,
            engine_config=engine_config,
        )

    def _build_engine(self):
        if self._engine_factory is not None:
            return self._engine_factory(self)
        from llmq_tpu.engine.engine import AsyncEngine
        from llmq_tpu.utils.platform import enable_compile_cache

        cache_dir = enable_compile_cache()  # before the first compile
        self.logger.info("Compile cache: %s", cache_dir or "off")
        engine = AsyncEngine(self._build_core())
        # Device-fault containment wiring: the engine thread calls
        # rebuild_core() to replace a faulted EngineCore in-process.
        # on_device_fault feeds the circuit breaker from the event loop
        # (set in _initialize_processor, where the loop is known).
        engine.rebuild_core = self._rebuild_core
        return engine

    def _rebuild_core(self):
        """Called on the engine thread by the fault-recovery path: drop
        the compiled programs referencing the faulted backend, then build
        a fresh EngineCore through the same path as startup."""
        import jax

        try:
            jax.clear_caches()
        except Exception:  # noqa: BLE001 — stale cache entries are inert
            self.logger.debug("jax.clear_caches failed", exc_info=True)
        return self._build_core()

    async def set_tracing(self, on: bool) -> None:
        """Both rings: the event loop's and the engine thread's."""
        self.spans.set(on)
        if self.engine is not None:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self.engine.set_tracing, on)

    def trace_dump(self) -> dict:
        """The loop's ring and the engine's, merged; the engine's adds
        ``scopes`` (``EngineCore._dump_scopes``)."""
        dumps = [self.spans.dump()]
        if self.engine is not None:
            dumps.append(self.engine.trace_dump())
        return merge_dumps(dumps)

    def _note_device_fault(self, reason: str) -> None:
        """Event-loop side of a device fault: count it against the
        circuit breaker so repeated rebuilds self-drain this worker even
        when every individual recovery succeeds."""
        self.logger.error("Engine reported device fault: %s", reason)
        self._note_engine_failure(reason)

    async def _handoff_in_flight(self) -> None:
        """SIGTERM drain-with-handoff: extract every unfinished request
        from the engine as a snapshot. Their pending generate()/resume()
        awaits resolve with HandoffOutputs, which _process_job turns into
        JobHandoff republishes — partial progress goes back to the broker
        instead of being recomputed from scratch elsewhere."""
        if self.engine is None:
            return
        loop = asyncio.get_running_loop()
        handoffs = await loop.run_in_executor(None, self.engine.handoff)
        if handoffs:
            self.logger.info(
                "Drained %d in-flight request(s) as resumable snapshots",
                len(handoffs),
            )

    async def _cleanup_processor(self) -> None:
        if self.engine is not None:
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, self.engine.shutdown)
            self.engine = None

    # --- prefix affinity: advertise / serve / fetch -----------------------
    def _prefix_enabled(self) -> bool:
        """Cross-worker prefix plumbing is live only when routing is on
        AND this engine can actually hold shipped pages (host tier up)."""
        return (
            self.config.prefix_affinity
            and self.engine is not None
            and self.engine.core.cfg.enable_prefix_caching
        )

    def _note_prefix_chain(self, text: str) -> None:
        """Count the text-chain chunks this job walked; the hottest
        digests ride the next heartbeat as this worker's advertisement."""
        for digest in text_prefix_chain(text):
            self._chain_hits[digest] = self._chain_hits.get(digest, 0) + 1
            self._chain_hits.move_to_end(digest)
        while len(self._chain_hits) > CHAIN_TRACK_CAP:
            self._chain_hits.popitem(last=False)

    def _prefix_chains(self) -> Optional[List[str]]:
        if not self.config.prefix_affinity or not self._chain_hits:
            return None
        ranked = sorted(
            self._chain_hits.items(), key=lambda kv: kv[1], reverse=True
        )
        return [digest for digest, _ in ranked[:CHAIN_ADVERTISE_N]]

    async def _start_extra_consumers(self) -> None:
        """Attach the prefix-page fetch server: peers ask for chunks on
        ``<queue>.kv.<worker_id>`` and get chunk blobs on their reply
        queue. Requests are ephemeral (short TTL, single delivery) — a
        requester that timed out has already recomputed.

        The same RPC queue carries KV adoption offers in a disaggregated
        fleet, so decode-capable workers (decode or auto role) attach it
        even without prefix shipping.

        Priority-class fleets also attach the per-worker control queue
        ``<q>.ctl.<worker_id>``: the streaming gateway publishes
        ``{"cancel": job_id}`` there when a client disconnects mid-stream,
        and the engine frees the request's pages instead of decoding for
        nobody. Requests are ephemeral like kv fetches — a cancel that
        outlives its 30 s TTL targets a job that already finished."""
        if self.config.priority_classes:
            ctl_q = ctl_queue_name(self.queue, self.worker_id)
            await self.broker.broker.declare_queue(
                ctl_q, ttl_ms=30_000, max_redeliveries=1
            )
            self._ctl_consumer_tag = await self.broker.broker.consume(
                ctl_q, self._serve_ctl, prefetch=4
            )
        if not (self._prefix_enabled() or self.role in ("decode", "auto")):
            return
        kv_q = kv_fetch_queue_name(self.queue, self.worker_id)
        await self.broker.broker.declare_queue(
            kv_q, ttl_ms=30_000, max_redeliveries=1
        )
        await self.broker.broker.declare_queue(
            kv_q + ".r", ttl_ms=30_000, max_redeliveries=1
        )
        self._kv_consumer_tag = await self.broker.broker.consume(
            kv_q, self._serve_kv_fetch, prefetch=4
        )

    async def _serve_ctl(self, message) -> None:
        """One control message: ``{"cancel": job_id}`` → ask the engine
        to cancel that request. Best-effort and always acked — an
        unknown id (job finished, or landed on a peer after a requeue)
        ages out of the engine's pending-cancel map on its own."""
        try:
            req = json.loads(message.body)
            job_id = req.get("cancel")
            if (
                job_id
                and self.engine is not None
                and hasattr(self.engine, "cancel")
            ):
                self.engine.cancel(str(job_id))
                self.jobs_cancelled += 1
                emit_trace_event(
                    str(job_id), "cancel_requested", worker_id=self.worker_id
                )
        except Exception:  # noqa: BLE001 — control plane is best-effort
            self.logger.debug("Control message failed", exc_info=True)
        finally:
            try:
                await message.ack()
            except Exception:  # noqa: BLE001 — already settled
                pass

    async def _serve_kv_fetch(self, message) -> None:
        """One fetch request: ``{"want": [hex], "reply_to": q, "req": id,
        "from": worker_id}`` → export whatever of the want-list is resident
        (host tier or device cache) and publish the chunks back, each with
        an outer blake2b digest the requester verifies before ingest.

        Serving is bounded: more than ``Config.peer_serve_concurrency``
        in-flight exports for one requester — or a host-memory governor
        past its serve watermark — replies ``{"busy": true}`` immediately
        so the requester recomputes instead of waiting out its timeout.
        Always acks: a failed export just means the requester recomputes."""
        peer_key = None
        try:
            req = json.loads(message.body)
            if "adopt" in req:
                # KV adoption offer from a prefill peer — outside the
                # peer-serve accounting (it is a single durable publish,
                # not a page export). peer_key stays None.
                await self._serve_adopt_offer(req)
                return
            want = [str(d) for d in (req.get("want") or [])][:64]
            reply_to = req.get("reply_to")
            req_id = req.get("req")
            peer_key = str(req.get("from") or reply_to or "?")
            cap = self.config.peer_serve_concurrency
            busy = (
                cap > 0 and self._peer_serving.get(peer_key, 0) >= cap
            ) or not get_governor().admit_serve()
            if busy:
                self.kv_serve_busy_rejects += 1
                peer_key = None  # nothing in flight to decrement
                if reply_to:
                    await self.broker.broker.publish(
                        reply_to,
                        json.dumps({"req": req_id, "busy": True}).encode(
                            "utf-8"
                        ),
                    )
                return
            self._peer_serving[peer_key] = (
                self._peer_serving.get(peer_key, 0) + 1
            )
            chunks: List[str] = []
            if want and self.engine is not None:
                loop = asyncio.get_running_loop()
                chunks = await loop.run_in_executor(
                    None, lambda: self.engine.export_prefix_chunks(want)
                )
            if reply_to:
                await self.broker.broker.publish(
                    reply_to,
                    json.dumps(
                        {
                            "req": req_id,
                            "chunks": chunks,
                            "digests": [_chunk_digest(c) for c in chunks],
                        }
                    ).encode("utf-8"),
                )
            self.prefix_chunks_served += len(chunks)
        except Exception:  # noqa: BLE001 — serving is best-effort
            self.logger.debug("KV fetch request failed", exc_info=True)
        finally:
            if peer_key is not None:
                left = self._peer_serving.get(peer_key, 1) - 1
                if left > 0:
                    self._peer_serving[peer_key] = left
                else:
                    self._peer_serving.pop(peer_key, None)
            try:
                await message.ack()
            except Exception:  # noqa: BLE001 — already settled / transport gone
                pass

    async def _serve_adopt_offer(self, req: dict) -> None:
        """Decode side of the phase-boundary handshake: a prefill peer
        offers a prefill-complete job payload (prompt-KV snapshot riding
        inside). Accept iff this worker currently serves the decode role;
        on accept the payload is durably parked on this worker's private
        ``<q>.d.<id>`` adoption queue BEFORE the reply goes out — either
        side dying after that point leaves the payload recoverable (the
        consumer drains it, or the janitor reclaims it to ``<q>.decode``)."""
        reply_to = req.get("reply_to")
        req_id = req.get("req")
        payload = req.get("adopt")
        accept = (
            self.running
            and self.role_active == "decode"
            and isinstance(payload, str)
            and bool(payload)
        )
        if accept:
            aq = decode_adopt_queue_name(self.queue, self.worker_id)
            try:
                await self.broker.broker.declare_queue(
                    aq,
                    ttl_ms=self.config.job_ttl_ms,
                    max_redeliveries=self.config.max_redeliveries,
                )
                await self.broker.broker.publish(
                    aq, payload.encode("utf-8"), message_id=req_id
                )
            except Exception:  # noqa: BLE001 — can't park it: decline
                self.logger.debug("Adoption park failed", exc_info=True)
                accept = False
        if reply_to:
            reply = (
                {"req": req_id, "accepted": True}
                if accept
                else {"req": req_id, "busy": True}
            )
            try:
                await self.broker.broker.publish(
                    reply_to, json.dumps(reply).encode("utf-8")
                )
            except Exception:  # noqa: BLE001 — offerer times out → fallback
                self.logger.debug("Adoption reply failed", exc_info=True)

    async def _ship_to_decode_peer(self, job: Job, body: bytes) -> bool:
        """Pick a decode peer for this prefill-complete job — deepest
        prefix-affinity match among fresh decode-role heartbeats wins,
        rendezvous hash breaks ties (and covers the no-affinity case) —
        then run the offer handshake. False on any miss: no fresh decode
        peer, all negative-cached, peer declined, or reply timeout."""
        try:
            mapping = await self.broker.decode_targets(self.queue)
        except Exception:  # noqa: BLE001 — discovery failed: fallback path
            return False
        now = time.monotonic()
        peers = [
            w
            for w in mapping
            if w != self.worker_id and not self._peer_dead(w, now)
        ]
        if not peers:
            return False
        peer = None
        text = job_affinity_text(job)
        if text:
            for digest in reversed(text_prefix_chain(text)):
                candidates = [
                    w for w in peers if digest in (mapping.get(w) or [])
                ]
                if candidates:
                    peer = rendezvous_pick(digest, candidates)
                    break
        if peer is None:
            peer = rendezvous_pick(job.id, sorted(peers))
        return await self._offer_adoption(peer, job.id, body)

    async def _offer_adoption(
        self, peer: str, job_id: str, body: bytes
    ) -> bool:
        """Offer/ack half of the handshake: publish the payload to the
        peer's ``<q>.kv.<peer>`` RPC queue and poll the shared reply queue
        until ``handoff_timeout_s``. True only on an explicit accept —
        busy, timeout, or garbage all return False (snapshot fallback)."""
        async with self._fetch_lock:
            reply_q = kv_fetch_queue_name(self.queue, self.worker_id) + ".r"
            try:
                await self.broker.broker.declare_queue(
                    reply_q, ttl_ms=30_000, max_redeliveries=1
                )
                await self.broker.broker.publish(
                    kv_fetch_queue_name(self.queue, peer),
                    json.dumps(
                        {
                            "adopt": body.decode("utf-8"),
                            "reply_to": reply_q,
                            "req": job_id,
                            "from": self.worker_id,
                        }
                    ).encode("utf-8"),
                )
            except Exception:  # noqa: BLE001 — peer queue gone
                return False
            loop = asyncio.get_running_loop()
            deadline = loop.time() + self.config.handoff_timeout_s
            while loop.time() < deadline:
                try:
                    msg = await self.broker.broker.get(reply_q)
                except Exception:  # noqa: BLE001 — transport hiccup
                    break
                if msg is None:
                    await asyncio.sleep(0.05)
                    continue
                try:
                    payload = json.loads(msg.body)
                except Exception:  # noqa: BLE001
                    payload = None
                await msg.ack()
                if (
                    not isinstance(payload, dict)
                    or payload.get("req") != job_id
                ):
                    continue  # stale reply from an earlier timed-out offer
                return bool(payload.get("accepted"))
            # Timeout: negative-cache the peer like a failed page fetch —
            # its RPC queue may be an unreclaimed orphan.
            self._dead_peers[peer] = time.monotonic() + PEER_NEGATIVE_CACHE_S
            return False

    async def _maybe_fetch_prefix(self, job: Job, text: str) -> None:
        """Cache miss with a remote hit: ship the missing prefix pages
        from the affinity peer instead of recomputing them. Strictly
        best-effort — no peer, no reply within the timeout, or an
        incompatible chunk all fall back to a plain local prefill."""
        if self.engine is None or not text:
            return
        core = self.engine.core
        if core.prefix_store is None:
            return  # nowhere to land shipped pages
        if self._fetch_lock.locked():
            return  # one in-flight fetch at a time (shared reply queue)
        tchain = text_prefix_chain(text)
        if not tchain:
            return
        mapping = await self.broker.affinity_targets(self.queue)
        peer = None
        now = time.monotonic()
        for digest in reversed(tchain):
            candidates = [
                w
                for w in mapping.get(digest, [])
                if w != self.worker_id and not self._peer_dead(w, now)
            ]
            if candidates:
                peer = rendezvous_pick(digest, candidates)
                break
        if peer is None:
            return
        try:
            token_ids = core.tokenizer.encode(text)
        except Exception:  # noqa: BLE001 — tokenizer hiccup: just prefill
            return
        digests = [
            h.hex() for h in token_prefix_chain(token_ids, core.cfg.page_size)
        ]
        if not digests:
            return
        loop = asyncio.get_running_loop()
        want = await loop.run_in_executor(
            None, lambda: self.engine.missing_prefix_digests(digests)
        )
        if not want:
            return
        async with self._fetch_lock:
            await self._fetch_from_peer(peer, want, job.id)

    def _peer_dead(self, peer: str, now: float) -> bool:
        """Negative-cache check: a peer that timed out a fetch within the
        last ``PEER_NEGATIVE_CACHE_S`` is skipped (expired entries drop)."""
        expiry = self._dead_peers.get(peer)
        if expiry is None:
            return False
        if now >= expiry:
            self._dead_peers.pop(peer, None)
            return False
        return True

    def _note_kv_fetch_failed(
        self, req_id: str, peer: str, reason: str
    ) -> None:
        """Classify a failed cross-worker page fetch on the job's trace
        (reason ∈ timeout / busy / digest-mismatch) — the fetch itself is
        best-effort, but *why* it failed is what distinguishes a dead peer
        from an overloaded one from a corrupt ship in `monitor top`."""
        self.kv_fetch_failures += 1
        trace = self._job_traces.get(req_id)
        if trace is not None:
            trace_event(trace, "kv_fetch_failed", peer=peer, reason=reason)
        emit_trace_event(
            req_id,
            "kv_fetch_failed",
            worker_id=self.worker_id,
            peer=peer,
            reason=reason,
        )

    async def _fetch_from_peer(
        self, peer: str, want: List[str], req_id: str
    ) -> None:
        from llmq_tpu.engine.snapshot import SnapshotError

        reply_q = kv_fetch_queue_name(self.queue, self.worker_id) + ".r"
        try:
            # Idempotent (normally done at startup): the reply must have
            # a landing place before the request goes out.
            await self.broker.broker.declare_queue(
                reply_q, ttl_ms=30_000, max_redeliveries=1
            )
            await self.broker.broker.publish(
                kv_fetch_queue_name(self.queue, peer),
                json.dumps(
                    {
                        "want": want[:64],
                        "reply_to": reply_q,
                        "req": req_id,
                        "from": self.worker_id,
                    }
                ).encode("utf-8"),
            )
        except Exception:  # noqa: BLE001 — peer queue gone: recompute
            return
        loop = asyncio.get_running_loop()
        deadline = loop.time() + PREFIX_FETCH_TIMEOUT_S
        while loop.time() < deadline:
            try:
                msg = await self.broker.broker.get(reply_q)
            except Exception:  # noqa: BLE001 — transport hiccup
                break
            if msg is None:
                await asyncio.sleep(0.05)
                continue
            try:
                payload = json.loads(msg.body)
            except Exception:  # noqa: BLE001
                payload = None
            await msg.ack()
            if not isinstance(payload, dict) or payload.get("req") != req_id:
                continue  # stale reply from an earlier timed-out fetch
            if payload.get("busy"):
                # The peer is saturated (serve cap or host-memory
                # governor): recompute now, don't wait out the timeout.
                # No negative cache — busy is load, not death.
                self._note_kv_fetch_failed(req_id, peer, "busy")
                return
            chunks = payload.get("chunks") or []
            digests = payload.get("digests")
            if chunks and isinstance(digests, list):
                # Outer transport digests (older peers omit them — the
                # chunk codec's own payload check still applies there).
                if len(digests) != len(chunks) or any(
                    _chunk_digest(c) != d for c, d in zip(chunks, digests)
                ):
                    self.logger.warning(
                        "Peer %s shipped chunks failing digest check", peer
                    )
                    self._note_kv_fetch_failed(req_id, peer, "digest-mismatch")
                    return
            if chunks:
                try:
                    n = await loop.run_in_executor(
                        None,
                        lambda: self.engine.ingest_prefix_chunks(chunks),
                    )
                    self.prefix_chunks_fetched += n
                    self.logger.info(
                        "Fetched %d prefix page(s) from %s", n, peer
                    )
                except SnapshotError as exc:
                    # Payload-level integrity/compat failure — same class
                    # as a transport digest mismatch for the fleet view.
                    self.logger.warning(
                        "Peer %s shipped incompatible prefix chunks: %s",
                        peer,
                        exc,
                    )
                    self._note_kv_fetch_failed(req_id, peer, "digest-mismatch")
            return
        self.prefix_fetch_timeouts += 1
        self._dead_peers[peer] = time.monotonic() + PEER_NEGATIVE_CACHE_S
        self._note_kv_fetch_failed(req_id, peer, "timeout")

    # --- token-delta streaming -------------------------------------------
    def _stream_tokenizer(self):
        core = getattr(self.engine, "core", None)
        return getattr(core, "tokenizer", None)

    async def _stream_begin(self, job: Job) -> bool:
        """Set up per-token streaming for a job that asked for it
        (truthy ``stream`` extra): declare the per-request stream queue
        and register an engine token callback that marshals each token
        onto the event loop, where a flush task decodes the pending tail
        and publishes character-offset text frames. Returns False (job
        runs unstreamed) when the engine can't stream — stub engines
        without the callback surface, or no tokenizer to decode with."""
        if not job.extras().get("stream"):
            return False
        if (
            self.engine is None
            or not hasattr(self.engine, "set_token_callback")
            or self._stream_tokenizer() is None
        ):
            return False
        sq = stream_queue_name(self.queue, job.id)
        try:
            # Short-TTL: frames outliving their consumer by a minute are
            # garbage (the Result on <q>.results is the settlement).
            await self.broker.broker.declare_queue(
                sq, ttl_ms=60_000, max_redeliveries=1_000_000_000
            )
        except Exception:  # noqa: BLE001 — no stream queue: run unstreamed
            self.logger.debug("Stream queue declare failed", exc_info=True)
            return False
        loop = asyncio.get_running_loop()
        self._streams[job.id] = {
            "queue": sq,
            "tokens": [],  # by absolute emit index (replays overwrite)
            "sent": 0,  # characters already published
            "flushed_n": 0,
            "flushing": False,
        }
        job_id = job.id

        def on_token(token: int, n_out: int) -> None:
            # Engine thread — just marshal; the event loop owns the state.
            loop.call_soon_threadsafe(
                self._note_stream_token, job_id, token, n_out
            )

        self.engine.set_token_callback(job.id, on_token)
        return True

    def _note_stream_token(self, job_id: str, token: int, n_out: int) -> None:
        st = self._streams.get(job_id)
        if st is None:
            return
        idx = n_out - 1
        if idx < len(st["tokens"]):
            # Fault-recovery replay: greedy determinism re-emits the same
            # value, so the decoded text (and the sent offset) is stable.
            st["tokens"][idx] = token
        else:
            st["tokens"].append(token)
        if not st["flushing"]:
            st["flushing"] = True
            spawn(
                self._flush_stream(job_id),
                registry=self._stream_tasks,
                name=f"stream-{job_id}",
            )

    async def _flush_stream(self, job_id: str) -> None:
        """Publish the undelivered decoded tail of one stream as a frame
        ``{"text_offset": chars_already_sent, "text": delta}``. Offsets
        are absolute character positions in the full decoded output, so
        a consumer that sees overlapping frames (worker died and the job
        resumed elsewhere, re-streaming from token zero) dedups by
        skipping everything before its high-water mark."""
        st = self._streams.get(job_id)
        if st is None:
            return
        tokenizer = self._stream_tokenizer()
        try:
            while tokenizer is not None:
                n = len(st["tokens"])
                if n == st["flushed_n"]:
                    break
                text = tokenizer.decode(st["tokens"][:n])
                st["flushed_n"] = n
                # An output that ends inside a multi-byte character
                # decodes to trailing U+FFFDs which the next token may
                # still turn into one real character. A sent frame cannot
                # be taken back, so hold them: the done frame
                # (_stream_finish) flushes whatever is left as it stands.
                delta = text.rstrip("\ufffd")[st["sent"] :]
                if not delta:
                    continue
                frame = {
                    "id": job_id,
                    "text_offset": st["sent"],
                    "text": delta,
                    "worker_id": self.worker_id,
                }
                st["sent"] += len(delta)
                await self.broker.broker.publish(
                    st["queue"],
                    json.dumps(frame).encode("utf-8"),
                    message_id=f"{job_id}.{frame['text_offset']}",
                )
                self.stream_frames_published += 1
        except Exception:  # noqa: BLE001 — streaming is best-effort
            self.logger.debug("Stream flush failed", exc_info=True)
        finally:
            st["flushing"] = False

    async def _stream_finish(self, job: Job, out: Any) -> None:
        """Tear down a job's stream: unregister the callback, flush the
        tail, and publish a terminal ``done`` frame when the request
        actually finished here. A drain handoff (or a requeue-bound
        error) publishes NO done frame — the job resumes on a peer whose
        re-stream continues this one (offset dedup), and the final
        Result settles whatever raced."""
        st = self._streams.pop(job.id, None)
        try:
            if hasattr(self.engine, "clear_token_callback"):
                self.engine.clear_token_callback(job.id)
        except Exception:  # noqa: BLE001 — engine may be mid-teardown
            pass
        if st is None:
            return
        finish = getattr(out, "finish_reason", None) or (
            "stop" if getattr(out, "text", None) is not None else None
        )
        if finish in (None, "prefill_done"):
            return
        tokenizer = self._stream_tokenizer()
        delta = ""
        try:
            if tokenizer is not None and st["tokens"]:
                text = tokenizer.decode(st["tokens"])
                delta = text[st["sent"] :]
        except Exception:  # noqa: BLE001
            delta = ""
        frame = {
            "id": job.id,
            "text_offset": st["sent"],
            "text": delta,
            "done": True,
            "finish_reason": finish,
            "worker_id": self.worker_id,
        }
        try:
            await self.broker.broker.publish(
                st["queue"],
                json.dumps(frame).encode("utf-8"),
                message_id=f"{job.id}.done",
            )
            self.stream_frames_published += 1
        except Exception:  # noqa: BLE001 — Result still settles the job
            self.logger.debug("Stream done frame failed", exc_info=True)

    # --- per-job processing (reference vllm_worker.py:136-195) ------------
    def _sampling_for(self, job: Job):
        """Job → SamplingParams: structured ``job.sampling`` wins, loose
        extra fields (``{"temperature": 0.2, ...}`` in the JSONL) fall back,
        reference defaults otherwise (temp 0.7, vllm_worker.py:162)."""
        from llmq_tpu.engine.sampling import SamplingParams

        params = SamplingParams.from_job_extras(
            job.extras(), default_max_tokens=self.config.max_tokens
        )
        if job.stop:
            params.stop = tuple(job.stop)
        opts = job.sampling
        if opts is not None:
            params.temperature = opts.temperature
            params.top_p = opts.top_p
            params.top_k = opts.top_k
            params.seed = opts.seed
            params.min_tokens = opts.min_tokens
            if opts.max_tokens is not None:
                params.max_tokens = opts.max_tokens
            if opts.stop:
                params.stop = tuple(opts.stop)
        return params

    def _resume_snapshot(self, job: Job):
        """Deserialize the resume snapshot a handed-off job carries, or
        None to process from scratch — on any codec/compat problem the
        prompt is still in the payload, so re-running from token zero is
        always available and always correct."""
        from llmq_tpu.engine.snapshot import SnapshotError, snapshot_from_wire

        resume = job.extras().get(RESUME_FIELD)
        if not isinstance(resume, dict) or not resume.get("snapshot"):
            return None
        try:
            # Wire-format agnostic: accepts the default base64 string as
            # well as a length-prefixed binary frame (LLMQ_WIRE_FORMAT=
            # binary senders on bytes-capable transports).
            return snapshot_from_wire(resume["snapshot"])
        except SnapshotError as exc:
            self.logger.warning(
                "Job %s resume snapshot unusable (%s); re-running from "
                "scratch",
                job.id,
                exc,
                extra={"job_id": job.id},
            )
            return None

    async def _process_job(self, job: Job) -> str:
        from llmq_tpu.engine.engine import HandoffOutput
        from llmq_tpu.engine.snapshot import SnapshotError, snapshot_to_b64

        params = self._sampling_for(job)
        out = None
        # Engine passthrough: a stamped deadline rides into generate()/
        # resume() so the scheduler sweep can expire the request between
        # decode steps. Sent only when set — defaults change nothing.
        gen_kw = (
            {} if job.deadline_at is None else {"deadline_at": job.deadline_at}
        )
        # SLO class passthrough, superset-only: batch (the default) sends
        # nothing, so engine stubs with pre-priority generate() signatures
        # keep working and priority-free jobs take the identical path.
        if job.priority_class == "interactive":
            gen_kw["priority"] = "interactive"
        if job.deadline_at is not None and time.time() > job.deadline_at:
            # Claim-time check passed but the deadline has since lapsed
            # (e.g. slots were busy): fail before any engine work.
            raise DeadlineExceeded(job.id)
        snapshot = self._resume_snapshot(job)
        if self._prefix_enabled():
            text = job_affinity_text(job)
            if text:
                self._note_prefix_chain(text)
                if snapshot is None and (
                    job.deadline_at is None
                    or time.time() + PREFIX_FETCH_TIMEOUT_S < job.deadline_at
                ):
                    # The fetch may stall up to its full timeout: a job
                    # whose remaining budget can't cover that goes
                    # straight to a local prefill.
                    await self._maybe_fetch_prefix(job, text)
        streaming = await self._stream_begin(job)
        try:
            if snapshot is not None:
                trace = self._job_traces.get(job.id)
                if trace is not None:
                    trace_event(
                        trace, "resumed", offset=len(snapshot.output_ids)
                    )
                # Phase-boundary adoption: a handoff_at stamp marks this
                # resume as a prefill→decode handoff (drain handoffs don't
                # carry one). Count it and sample the handoff latency.
                resume = job.extras().get(RESUME_FIELD)
                ho_at = (
                    resume.get("handoff_at")
                    if isinstance(resume, dict)
                    else None
                )
                if ho_at is not None:
                    try:
                        latency_ms = max(
                            0.0, (clock.wall() - float(ho_at)) * 1000.0
                        )
                    except (TypeError, ValueError):
                        latency_ms = 0.0
                    self.jobs_adopted += 1
                    self._handoff_ms.append(latency_ms)
                    if trace is not None:
                        trace_event(
                            trace, "adopted", latency_ms=round(latency_ms, 3)
                        )
                    emit_trace_event(
                        job.id,
                        "adopted",
                        worker_id=self.worker_id,
                        latency_ms=round(latency_ms, 3),
                    )
                try:
                    out = await self.engine.resume(
                        rid=job.id, snapshot=snapshot, **gen_kw
                    )
                except SnapshotError as exc:
                    # Valid blob, wrong engine (model signature / KV dtype
                    # mismatch) — recompute from the prompt instead.
                    self.logger.warning(
                        "Job %s snapshot not insertable (%s); re-running "
                        "from scratch",
                        job.id,
                        exc,
                        extra={"job_id": job.id},
                    )
            if out is None:
                if self.role_active == "prefill":
                    # Prefill role: run the prompt phase only. The engine
                    # finishes the request at the boundary with a
                    # prompt-KV snapshot (finish_reason="prefill_done");
                    # the PrefillDone raise below routes it to the decode
                    # pool. Passed only for this role so unified call
                    # sites (and engine stubs) keep their existing
                    # signature.
                    gen_kw["prefill_only"] = True
                if job.messages is not None:
                    out = await self.engine.generate(
                        rid=job.id,
                        messages=job.messages,
                        params=params,
                        **gen_kw,
                    )
                elif job.chat_mode:
                    messages = [
                        {"role": "user", "content": job.get_formatted_prompt()}
                    ]
                    out = await self.engine.generate(
                        rid=job.id,
                        messages=messages,
                        params=params,
                        **gen_kw,
                    )
                else:
                    out = await self.engine.generate(
                        rid=job.id,
                        prompt=job.get_formatted_prompt(),
                        params=params,
                        **gen_kw,
                    )
            # Project any fault-recovery events the engine recorded for
            # this request (device_fault → engine_rebuilt) onto its trace,
            # whether it completed after a restore or comes back as a
            # handoff below.
            self._trace_fault_events(job.id)
            if getattr(out, "finish_reason", None) == "deadline_exceeded":
                # The engine's sweep expired the request between decode
                # blocks: terminal dead-letter, not a (truncated) result.
                raise DeadlineExceeded(job.id)
            if isinstance(out, HandoffOutput):
                # This worker is draining: surface the partial progress to
                # the base loop, which republishes the job as resumable.
                raise JobHandoff(
                    snapshot_to_b64(out.snapshot)
                    if out.snapshot is not None
                    else None,
                    out.emitted,
                )
            if getattr(out, "finish_reason", None) == "prefill_done":
                snap = getattr(out, "snapshot", None)
                if snap is None:
                    # Must never happen (the engine snapshots before it
                    # finishes the sequence); RuntimeError — not
                    # ValueError — so the base loop requeues instead of
                    # dropping the job.
                    raise RuntimeError(
                        f"prefill_done for job {job.id} carried no snapshot"
                    )
                raise PrefillDone(snapshot_to_b64(snap))
        finally:
            if streaming:
                await self._stream_finish(job, out)
        self._usage[job.id] = {
            "prompt_tokens": out.prompt_tokens,
            "completion_tokens": out.completion_tokens,
        }
        finish = getattr(out, "finish_reason", None)
        if finish is not None:
            self._finish_reasons[job.id] = finish
        if self.config.result_digest:
            self._result_tokens[job.id] = list(out.token_ids)
        self._trace_engine_timing(job.id, out)
        return out.text

    def _trace_fault_events(self, job_id: str) -> None:
        """Move the engine's per-request fault-recovery events onto the
        request trace at their original monotonic stamps."""
        if self.engine is None:
            return
        events = self.engine.pop_fault_events(job_id)
        if not events:
            return
        trace = self._job_traces.get(job_id)
        if trace is None:
            return
        for name, t_mono, fields in events:
            trace_event_at(trace, name, t_mono, **fields)

    def _trace_engine_timing(self, job_id: str, out) -> None:
        """Backfill the engine's monotonic lifecycle stamps into the
        request trace (claimed → tokenized → prefill_start → first_token
        → decode → finished). Host-side dict writes only."""
        trace = self._job_traces.get(job_id)
        timing = getattr(out, "timing", None)
        if trace is None or not timing:
            return
        # The claim is the worker's stamp; it joins the engine's here so
        # that one record holds claimed <= engine_submit <= enqueued ...
        for event in trace["events"]:
            if event.get("name") == "claimed":
                timing["claimed"] = event["t_mono"]
        if self.spans.on and "claimed" in timing:
            self.spans.note_request(job_id, claimed=timing["claimed"])
        trace_event_at(trace, "tokenized", timing.get("enqueued"))
        trace_event_at(trace, "admitted", timing.get("admitted"))
        trace_event_at(trace, "prefill_start", timing.get("prefill_start"))
        trace_event_at(trace, "first_token", timing.get("first_token"))
        preempts = int(timing.get("preempt_count", 0))
        trace_event_at(
            trace,
            "decode",
            timing.get("last_token"),
            tokens=out.completion_tokens,
            preempt_count=preempts,
        )
        if preempts:
            # No per-preemption stamp survives readmission; record the
            # fact (and count) at the time decoding completed.
            trace_event_at(
                trace, "preempted", timing.get("last_token"), count=preempts
            )

    def _build_result(
        self, job: Job, output: str, duration_ms: float, trace=None
    ):
        result = super()._build_result(job, output, duration_ms, trace=trace)
        usage = self._usage.pop(job.id, None)
        if usage is not None:
            result.usage = usage
        finish = self._finish_reasons.pop(job.id, None)
        if finish is not None:
            result.finish_reason = finish
        tokens = self._result_tokens.pop(job.id, None)
        if tokens is not None:
            result.token_ids = tokens
            result.token_digest = token_fold(tokens)
        return result

    def _dispatch_ok_age(self):
        if self.engine is None:
            return None
        watchdog = getattr(self.engine.core, "watchdog", None)
        if watchdog is None:
            return None
        return round(watchdog.last_ok_age_s(), 3)

    def _integrity_status(self):
        if self.engine is None:
            return None
        core = self.engine.core
        if (
            core.logit_guard != "on"
            and core.weight_audit_every <= 0
            and core.canary_every <= 0
        ):
            return None
        return core.integrity_status()

    def _engine_stats(self):
        if self.engine is None:
            return None
        stats = self.engine.stats()
        # Superset-only: rebuild accounting appears once a fault happened.
        if self.engine.engine_rebuilds:
            stats["engine_rebuilds"] = self.engine.engine_rebuilds
            if self.engine.last_fault_reason:
                stats["last_fault_reason"] = self.engine.last_fault_reason
        # Online-serving counters, superset-only (appear once they move).
        if self.stream_frames_published:
            stats["stream_frames_published"] = self.stream_frames_published
        if self.jobs_cancelled:
            stats["jobs_cancelled"] = self.jobs_cancelled
        if self.config.prefix_affinity:
            stats = {
                **stats,
                "prefix_chunks_served": self.prefix_chunks_served,
                "prefix_chunks_fetched": self.prefix_chunks_fetched,
                "prefix_fetch_timeouts": self.prefix_fetch_timeouts,
            }
            # Superset-only: the hardening counters appear once they move.
            if self.kv_fetch_failures:
                stats["kv_fetch_failures"] = self.kv_fetch_failures
            if self.kv_serve_busy_rejects:
                stats["kv_serve_busy_rejects"] = self.kv_serve_busy_rejects
        return stats
