"""Environment-driven configuration.

Counterpart of the reference's ``llmq/core/config.py:9-69`` — env vars (with
``.env`` autoload) materialised into a pydantic model, re-read on every
``get_config()`` call so tests can monkeypatch the environment.

Differences from the reference, on purpose:

- TPU-native knob names (``LLMQ_*`` / ``TPU_*``); the reference's ``VLLM_*``
  names are accepted as fallback aliases so existing llmq deployment scripts
  keep working unchanged (parity with ``utils/run_llmq_benchmark.slurm:32-33``).
- ``.env`` parsing is implemented here (python-dotenv is not a dependency).
- ``job_ttl_minutes`` is actually applied by the broker layer (the reference
  declared it but never used it — SURVEY.md §5 "dead config").
- ``max_redeliveries`` adds a real dead-letter policy (the reference requeued
  failed jobs forever — ``workers/base.py:245``).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

from pydantic import BaseModel, Field


def load_env_file(path: str | os.PathLike = ".env", *, override: bool = False) -> None:
    """Minimal ``.env`` loader: KEY=VALUE lines, ``#`` comments, optional quotes."""
    p = Path(path)
    if not p.is_file():
        return
    for raw in p.read_text().splitlines():
        line = raw.strip()
        if not line or line.startswith("#") or "=" not in line:
            continue
        if line.startswith("export "):
            line = line[len("export ") :]
        key, _, value = line.partition("=")
        key = key.strip()
        value = value.strip()
        if len(value) >= 2 and value[0] == value[-1] and value[0] in "\"'":
            value = value[1:-1]
        if key and (override or key not in os.environ):
            os.environ[key] = value


_ENV_LOADED = False


def _ensure_env_loaded() -> None:
    global _ENV_LOADED
    if not _ENV_LOADED:
        load_env_file()
        _ENV_LOADED = True


def _env(name: str, *aliases: str) -> Optional[str]:
    for key in (name, *aliases):
        value = os.getenv(key)
        if value is not None:
            return value
    return None


def _env_int(name: str, *aliases: str, default: Optional[int] = None) -> Optional[int]:
    value = _env(name, *aliases)
    return int(value) if value not in (None, "") else default


def _env_float(name: str, *aliases: str, default: Optional[float] = None) -> Optional[float]:
    value = _env(name, *aliases)
    return float(value) if value not in (None, "") else default


class Config(BaseModel):
    """Runtime configuration snapshot (one env read per instantiation)."""

    # --- broker -----------------------------------------------------------
    broker_url: str = Field(
        default_factory=lambda: _env("LLMQ_BROKER_URL", "BROKER_URL", "RABBITMQ_URL")
        or "tcp://127.0.0.1:5672/",
        description=(
            "Broker endpoint. Schemes: memory:// (in-process), file:///path "
            "(durable on-disk), tcp://host:port/ (llmq-tpu broker daemon), "
            "amqp://... (RabbitMQ, if aio-pika is installed)."
        ),
    )

    queue_prefetch: int = Field(
        default_factory=lambda: _env_int(
            "LLMQ_QUEUE_PREFETCH", "VLLM_QUEUE_PREFETCH", default=100
        ),
        description="Messages prefetched (in flight) per worker consumer.",
    )

    reconnect_base_delay_s: float = Field(
        default_factory=lambda: _env_float("LLMQ_RECONNECT_BASE_S", default=0.5),
        description="First re-dial backoff after a mid-run connection loss "
        "(doubles per attempt, with jitter).",
    )

    reconnect_max_delay_s: float = Field(
        default_factory=lambda: _env_float("LLMQ_RECONNECT_MAX_S", default=30.0),
        description="Backoff ceiling for broker reconnect attempts.",
    )

    outbox_limit: int = Field(
        default_factory=lambda: _env_int("LLMQ_OUTBOX_LIMIT", default=10_000),
        description="Publishes parked during a broker outage before "
        "publishers block (bounded so back-pressure still propagates).",
    )

    # --- engine -----------------------------------------------------------
    hbm_utilization: float = Field(
        default_factory=lambda: _env_float(
            "TPU_HBM_UTILIZATION", "VLLM_GPU_MEMORY_UTILIZATION", default=0.9
        ),
        description="Fraction of device HBM the engine may claim for the KV cache.",
    )

    max_num_seqs: Optional[int] = Field(
        default_factory=lambda: _env_int("LLMQ_MAX_NUM_SEQS", "VLLM_MAX_NUM_SEQS"),
        description="Max sequences resident in one continuous-batching step.",
    )

    max_model_len: Optional[int] = Field(
        default_factory=lambda: _env_int("LLMQ_MAX_MODEL_LEN", "VLLM_MAX_MODEL_LEN"),
        description="Context-window cap (prompt + generation).",
    )

    max_tokens: int = Field(
        default_factory=lambda: _env_int(
            "LLMQ_MAX_TOKENS", "VLLM_MAX_TOKENS", default=8192
        ),
        description="Default max new tokens per request (per-job override allowed).",
    )

    prefill_chunk_size: Optional[int] = Field(
        default_factory=lambda: _env_int("LLMQ_PREFILL_CHUNK"),
        description="Chunked prefill: positions per chunk (None = bucketed).",
    )

    kv_dtype: Optional[str] = Field(
        default_factory=lambda: _env("LLMQ_KV_DTYPE", "VLLM_KV_CACHE_DTYPE"),
        description="KV cache storage dtype (bf16 default; fp8 = "
        "float8_e5m2, half the KV bytes — vLLM kv-cache-dtype parity).",
    )

    enable_prefix_caching: bool = Field(
        default_factory=lambda: (_env("LLMQ_PREFIX_CACHING") or "").lower()
        in ("1", "true", "yes"),
        description="Reuse cached KV for shared prompt prefixes "
        "(requires prefill_chunk_size).",
    )

    prefix_affinity: bool = Field(
        default_factory=lambda: (_env("LLMQ_PREFIX_AFFINITY") or "").lower()
        in ("1", "true", "yes"),
        description="Prefix-affinity routing: workers advertise hot "
        "prefix-chain digests in heartbeats, and the submit path routes "
        "jobs sharing an advertised prompt prefix to the per-worker queue "
        "<queue>.w.<worker_id> of the worker already holding those KV "
        "pages (falling back to the shared queue on no fresh match). "
        "Workers also serve cross-worker page-fetch requests on "
        "<queue>.kv.<worker_id> when this is on.",
    )

    decode_block: int = Field(
        default_factory=lambda: _env_int("LLMQ_DECODE_BLOCK", default=1),
        description="Fused multi-step decode: device iterations per host "
        "dispatch (one lax.scan'd XLA computation returns a K-token "
        "block per sequence). 1 = per-token dispatch.",
    )

    spec_tokens: int = Field(
        default_factory=lambda: _env_int("LLMQ_SPEC_TOKENS", default=0),
        description="Lossless speculative decoding: n-gram prompt-lookup "
        "draft tokens verified per decode step (0 = off). Greedy output "
        "is bit-identical to non-speculative decoding; sampled requests "
        "keep the exact output distribution via rejection sampling.",
    )

    tp_overlap: str = Field(
        default_factory=lambda: (_env("LLMQ_TP_OVERLAP") or "off").lower(),
        description="Tensor-parallel collective overlap: 'on' replaces "
        "GSPMD's per-layer all-reduces with chunked ppermute rings "
        "(ops/collective_matmul.py), 'auto' A/Bs ring-vs-GSPMD on the "
        "deployment hardware, 'off' keeps the literal GSPMD programs.",
    )

    mixed_step: str = Field(
        default_factory=lambda: (_env("LLMQ_MIXED_STEP") or "off").lower(),
        description="Piggyback scheduling: 'on' fuses one pending "
        "request's prefill chunk into each decode dispatch (shared "
        "paged-KV writes, one executable) instead of alternating whole "
        "dispatches. Requires prefill_chunk_size.",
    )

    # --- disaggregated prefill/decode serving -----------------------------
    worker_role: str = Field(
        default_factory=lambda: (_env("LLMQ_WORKER_ROLE") or "unified").lower(),
        description="Disaggregated serving role. 'unified' (default) runs "
        "prefill and decode on one worker, exactly the pre-disaggregation "
        "behavior. 'prefill' consumes the shared job queue, runs prefill "
        "only, and hands the request off at the phase boundary (KV ship "
        "to a decode peer, snapshot republish to <q>.decode as fallback). "
        "'decode' consumes <q>.decode plus its private adoption queue "
        "<q>.d.<worker_id> and runs the decode hot path on adopted "
        "requests. 'auto' starts as prefill and switches roles on fleet "
        "queue-depth skew with hysteresis (role_dwell_s / role_switch_*).",
    )

    role_dwell_s: float = Field(
        default_factory=lambda: _env_float("LLMQ_ROLE_DWELL_S", default=60.0),
        description="Auto-role hysteresis: minimum seconds a worker stays "
        "in its current role before the depth-ratio controller may switch "
        "it again. Prevents role flapping when the prefill:decode demand "
        "mix sits near a switch band.",
    )

    role_switch_hi: float = Field(
        default_factory=lambda: _env_float("LLMQ_ROLE_SWITCH_HI", default=2.0),
        description="Auto-role band: a decode-role worker switches to "
        "prefill when (shared depth + 1) / (decode depth + 1) exceeds "
        "this ratio (prefill demand dominates).",
    )

    role_switch_lo: float = Field(
        default_factory=lambda: _env_float("LLMQ_ROLE_SWITCH_LO", default=0.5),
        description="Auto-role band: a prefill-role worker switches to "
        "decode when (shared depth + 1) / (decode depth + 1) falls below "
        "this ratio (decode backlog dominates).",
    )

    role_check_interval_s: float = Field(
        default_factory=lambda: _env_float(
            "LLMQ_ROLE_CHECK_INTERVAL_S", default=5.0
        ),
        description="Auto-role controller cadence: seconds between fleet "
        "queue-depth polls (two stats() reads per poll).",
    )

    handoff_timeout_s: float = Field(
        default_factory=lambda: _env_float("LLMQ_HANDOFF_TIMEOUT_S", default=2.0),
        description="Seconds a prefill-role worker waits for a decode "
        "peer to accept a KV adoption offer before falling back to the "
        "snapshot republish on <q>.decode.",
    )

    result_digest: bool = Field(
        default_factory=lambda: (_env("LLMQ_RESULT_DIGEST") or "").lower()
        in ("1", "true", "yes", "on"),
        description="Result-payload integrity: workers attach the emitted "
        "token_ids plus a blake2b-16 token_digest to every result, and "
        "the receive/collect paths recompute it — wire/storage corruption "
        "of a result becomes a counted, dead-letterable event. Off by "
        "default: result JSON stays byte-identical.",
    )

    # --- SLO priority classes / online serving ----------------------------
    priority_classes: bool = Field(
        default_factory=lambda: (_env("LLMQ_PRIORITY_CLASSES") or "1").lower()
        not in ("0", "false", "no", "off"),
        description="SLO priority classes: jobs carrying priority="
        "'interactive' route to the per-queue fast lane <q>.interactive "
        "and are admitted ahead of batch work at the engine. On by "
        "default; a fleet that never sets Job.priority is unaffected "
        "(the fast lane stays empty and admission order is FIFO). "
        "Set LLMQ_PRIORITY_CLASSES=0 to force pure FIFO everywhere "
        "(the detune the policy regression documents).",
    )

    priority_preempt: bool = Field(
        default_factory=lambda: (_env("LLMQ_PRIORITY_PREEMPT") or "1").lower()
        not in ("0", "false", "no", "off"),
        description="Allow the engine to preempt a running batch sequence "
        "(swap-preempt under preempt_mode=swap, else recompute) when an "
        "interactive sequence would otherwise queue for a slot. Greedy "
        "outputs stay token-identical either way — preemption changes "
        "only scheduling order, never a sequence's token stream.",
    )

    serve_port: int = Field(
        default_factory=lambda: _env_int("LLMQ_SERVE_PORT", default=8100),
        description="HTTP port for the OpenAI-compatible streaming "
        "gateway (llmq-tpu serve). 0 binds an ephemeral port.",
    )

    # --- queue/job policy -------------------------------------------------
    job_ttl_minutes: int = Field(
        default_factory=lambda: _env_int("LLMQ_JOB_TTL_MINUTES", default=30),
        description="Job time-to-live; expired jobs are dropped by the broker.",
    )

    max_redeliveries: int = Field(
        default_factory=lambda: _env_int("LLMQ_MAX_REDELIVERIES", default=3),
        description="Redeliveries before a job is dead-lettered to <q>.failed.",
    )

    redelivery_backoff_s: float = Field(
        default_factory=lambda: _env_float(
            "LLMQ_REDELIVERY_BACKOFF_S", default=0.0
        ),
        description="Base delay before a rejected job is redelivered; "
        "doubles per attempt (exponential backoff). 0 redelivers "
        "immediately (the pre-backoff behavior).",
    )

    redelivery_backoff_max_s: float = Field(
        default_factory=lambda: _env_float(
            "LLMQ_REDELIVERY_BACKOFF_MAX_S", default=30.0
        ),
        description="Ceiling on the exponential redelivery backoff.",
    )

    deadline_ms: int = Field(
        default_factory=lambda: _env_int("LLMQ_DEADLINE_MS", default=0),
        description="Default per-job completion deadline (ms from submit). "
        "Expired jobs dead-letter as deadline_exceeded instead of running; "
        "the submit path sheds early when queue depth x observed service "
        "rate cannot meet it. 0 disables (no deadline stamped).",
    )

    host_mem_gb: float = Field(
        default_factory=lambda: _env_float("LLMQ_HOST_MEM_GB", default=0.0),
        description="Shared host-RAM byte budget (GiB) governing the prefix "
        "cold tier, snapshot swap, and resume-republish blobs together "
        "(utils/host_mem.HostMemoryGovernor). Under pressure the governor "
        "degrades in order: evict cold prefixes, refuse swap-preempt "
        "(recompute-preemption fallback), refuse KV-ship serves. "
        "0 disables the shared budget (per-store budgets still apply).",
    )

    quarantine_attempts: int = Field(
        default_factory=lambda: _env_int("LLMQ_QUARANTINE_ATTEMPTS", default=0),
        description="Fleet-wide attempts before a job that keeps crashing "
        "the engine is quarantined to <queue>.quarantine instead of "
        "cycling through workers. 0 disables quarantine.",
    )

    peer_serve_concurrency: int = Field(
        default_factory=lambda: _env_int(
            "LLMQ_PEER_SERVE_CONCURRENCY", default=2
        ),
        description="Concurrent KV-ship fetch requests a worker serves "
        "before replying busy (the requester recomputes immediately "
        "instead of burning its fetch timeout).",
    )

    breaker_failures: int = Field(
        default_factory=lambda: _env_int("LLMQ_BREAKER_FAILURES", default=0),
        description="Consecutive engine failures before a worker trips its "
        "circuit breaker and self-drains via the handoff path (its jobs "
        "requeue/hand off to healthy peers). 0 disables.",
    )

    job_timeout_s: Optional[float] = Field(
        default_factory=lambda: _env_float("LLMQ_JOB_TIMEOUT_S"),
        description="Per-job processing timeout: a job running past it is "
        "cancelled and reject-requeued (dead-letters via max_redeliveries) "
        "instead of wedging a worker slot forever. None disables.",
    )

    drain_timeout_s: float = Field(
        default_factory=lambda: _env_float("LLMQ_DRAIN_TIMEOUT_S", default=30.0),
        description="Seconds a shutting-down worker waits for in-flight "
        "jobs to finish (TPU jobs with long decodes may need more).",
    )

    chunk_size: int = Field(
        default_factory=lambda: _env_int("LLMQ_CHUNK_SIZE", default=10000),
        description="Jobs submitted per publish chunk.",
    )

    log_level: str = Field(
        default_factory=lambda: _env("LLMQ_LOG_LEVEL") or "INFO",
        description="Logging level.",
    )

    @property
    def job_ttl_ms(self) -> int:
        return self.job_ttl_minutes * 60 * 1000


def get_config() -> Config:
    """Fresh config (env re-read each call, like the reference's config.py:67-69)."""
    _ensure_env_loaded()
    return Config()
