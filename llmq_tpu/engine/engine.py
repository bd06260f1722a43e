"""TPU inference engine: continuous batching over compiled XLA steps.

This module replaces what the reference delegated to vLLM's
``AsyncLLMEngine`` (``llmq/workers/vllm_worker.py:104-123,183-195``): an
engine that coalesces many in-flight requests into device batches. The
TPU-native design differs from vLLM's CUDA core on purpose:

- **Fixed-shape compiled programs.** A batched prefill (bucketed
  whole-prompt by default, or fixed-[B, C] chunked against the paged
  cache via ``prefill_chunk_size``) and a ``max_num_seqs``-slot decode
  step. Requests churn; the compiled programs never change, so there is
  no recompilation in steady state.
- **Device-resident decode state + run-ahead pipeline.** The decode
  state (current tokens, context lengths, block tables, sampling state)
  lives on the device and is *updated by the compiled step itself*; the
  host dispatches step ``k`` while asynchronously fetching the sampled
  tokens of step ``k - ahead``. Steady-state decode therefore ships
  **zero** host→device bytes, and the device never waits for the host.
  ``ahead`` is as many steps as cover the engine thread's own turn
  (:func:`runahead_target`: the longest turn, fetch return to next
  decode dispatch, that came twice in its last few hundred, over the
  device's step period, plus one; both read off the loop's own clock),
  at least 2 and never more than ``EngineConfig.runahead``: the device
  queue is first in, first out, so every dispatch kept in flight is one
  more step a new request's prefill waits behind. While every slot is
  taken or requests wait, an arrival waits for a slot and not for that
  queue, and ``ahead`` is the whole cap. Correctness pieces:
    * *Page lookahead*: KV pages are allocated at dispatch time for every
      position any in-flight step may write (`Scheduler.ensure_pages`),
      so the device block tables are never stale when a sequence crosses
      a page boundary.
    * *Device-side stopping*: per-slot limit/min/stop-token-id arrays let
      the compiled step deactivate finished slots itself, so EOS and
      max-token finishes need no host round-trip and no resync. Stop
      *strings* (host-only) mark the state dirty and force a resync.
    * *Deferred page frees*: pages of a finished sequence return to the
      allocator only after every dispatched step that might still write
      them has been processed (watermark on the dispatch counter).
- **Host scheduler, device compute.** `engine/scheduler.py` owns slots
  and KV pages in plain Python; resyncs rebuild the device state from it.
  Pages are refcounted: automatic prefix caching shares the leading full
  prompt pages of identical prefixes (blake2b chain match) and evicts
  lazily, and pool exhaustion triggers recompute preemption (re-queue,
  keep generated tokens, re-prefill later) rather than truncation.
- **SPMD via the mesh.** Weights/KV are sharded with ``NamedSharding``
  (`parallel/sharding.py`); GSPMD inserts the ICI collectives. The same
  engine runs single-chip or tensor-parallel across a slice unchanged.
- **Sampling on device.** Per-slot temperature/top-k/top-p/seed arrays;
  the model step and the sampler fuse into one executable.
- **Memory dtypes.** Weight-only int8 (``models/quant.py``) and an fp8
  (float8_e5m2) KV cache (``kv_dtype="fp8"``) are first-class: pools
  and params stay narrow in HBM and kernels convert on-chip.

An ``AsyncEngine`` wrapper runs the step loop on a dedicated thread and
bridges to asyncio futures, mirroring the AsyncLLMEngine surface the
reference consumed.
"""

from __future__ import annotations

import bisect
import dataclasses
import logging
import math
import os
import queue
import re
import threading
import time
from collections import deque
from concurrent.futures import Future
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llmq_tpu.core.faults import (
    FAULT_NUMERICAL,
    FAULT_OOM,
    DeviceFaultError,
    LogitGuardError,
    StepCompileError,
    classify_failure,
    is_compile_failure,
)
from llmq_tpu.engine import sampling as sampling_mod
from llmq_tpu.engine import snapshot as snapshot_mod
from llmq_tpu.engine.prefix_store import PrefixStore
from llmq_tpu.engine.watchdog import NO_GUARD, DispatchWatchdog
from llmq_tpu.engine.snapshot import (
    KVRestore,
    RequestSnapshot,
    SnapshotCompatError,
)
from llmq_tpu.engine.sampling import (
    SamplingParams,
    make_base_key,
    request_tag,
    sample_tokens,
)
from llmq_tpu.engine.scheduler import (
    OutOfPages,
    Scheduler,
    SchedulerConfig,
    Sequence,
    mixed_token_budget,
)
from llmq_tpu.engine.tokenizer import Tokenizer
from llmq_tpu.models.cache import cache_layout
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import (
    Params,
    Transformer,
    build_model,
)
from llmq_tpu.obs.metrics import (
    DEFAULT_BUCKETS,
    Gauge,
    Histogram,
    get_registry,
    to_ms,
)
from llmq_tpu.obs.spans import SpanRing
from llmq_tpu.obs.trace import emit_trace_event
from llmq_tpu.ops import dispatch as _dispatch
from llmq_tpu.utils.host_mem import get_governor
from llmq_tpu.utils.platform import on_tpu
from llmq_tpu.ops.attention import mixed_query_grid
from llmq_tpu.parallel import pipeline as pp_mod
from llmq_tpu.parallel.mesh import (
    DP_AXIS,
    SP_AXIS,
    TP_AXIS,
    make_mesh,
    mesh_pp,
)
from llmq_tpu.parallel.sharding import param_shardings

logger = logging.getLogger(__name__)

#: ITL needs a finer low end than the default latency buckets: tokens of
#: one fused decode block reach the host in a burst, so sub-ms gaps are
#: the common case there.
ITL_BUCKETS: Tuple[float, ...] = (0.0001, 0.00025, 0.0005) + DEFAULT_BUCKETS

#: Cancellation requests for rids the engine doesn't hold (result already
#: emitted, or a disconnect raced the publish) age out of the sweep map
#: after this many seconds so it can never grow unboundedly.
_CANCEL_TTL_S = 5.0


#: Whether a profile of this process is being taken: an atomic load, which
#: ``AsyncEngine._run`` looks at once a turn (its span ring follows it).
_profile_active = TraceAnnotation.is_enabled


class _StepProgram:
    """One jitted step program that can tell a failure to *compile* from
    a fault while a compiled program runs.

    The two must not share a handler: a device fault is retried on a
    rebuilt engine (``core/faults.py``), but a program the compiler
    refuses — it does not fit HBM, Mosaic rejects a kernel — fails the
    same way on every rebuild and for every job. ``jax.jit`` runs the
    Python body only on a cache miss, which marks the call cold; when a
    cold call raises, compiling the program once more on its own says
    which of the two it was. Zero cost on the warm path.
    """

    def __init__(self, fn, **jit_kwargs) -> None:
        inner = fn.func if isinstance(fn, partial) else fn
        self.name = getattr(inner, "__name__", "step")
        self._cold = False
        #: variant (``variant_of``) -> the abstract arguments it was
        #: compiled for, kept at each cold call: what ``scope_map`` needs
        #: to look the compiled program up again. One entry a compiled
        #: variant, like jit's own cache.
        self.variants: Dict[str, tuple] = {}  # llmq: ignore[unbounded-host-buffer]
        self._scope_maps: Dict[str, Dict[str, str]] = {}  # llmq: ignore[unbounded-host-buffer]
        # Metadata only: the compiled program is the same with or without.
        scope = "llmq." + self.name.replace("_block", "").replace(
            "mixedfill", "mixed"
        )

        def traced(*args, **kwargs):
            self._cold = True
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        # Keep the step's own name on the compiled module (profiles and
        # compile-cache entries are read by it).
        traced.__name__ = self.name
        traced.__qualname__ = getattr(inner, "__qualname__", self.name)
        self._jit = jax.jit(traced, **jit_kwargs)
        self.lower = self._jit.lower

    def __call__(self, *args):
        self._cold = False
        try:
            out = self._jit(*args)
        except Exception as exc:
            if self._cold:
                self._raise_if_uncompilable(args, exc)
            raise
        if self._cold:  # compiled just now: seconds ago, once a variant
            self.variants[self.variant_of(args)] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None)
                ),
                args,
            )
        return out

    @staticmethod
    def variant_of(args) -> str:
        """Which compiled variant of the program these arguments run:
        the shape of the first argument after (params, k pool, v pool),
        ``4x256`` for a prefill of 4 rows in the 256 bucket, and ``""``
        where that is no array (the decode step takes its state)."""
        shape = getattr(args[3], "shape", None) if len(args) > 3 else None
        return "x".join(str(n) for n in shape) if shape else ""

    def scope_map(self, variant: str) -> Optional[Dict[str, str]]:
        """HLO instruction name -> the innermost ``llmq.*`` scope it was
        traced under, for one compiled variant: read from the compiled
        program's text, which a device trace names its events by. None
        for a variant that never ran. Never on the hot path: a ring's
        ``dump`` asks. Compiling again is a lookup where the compile
        cache is on, and a compile where it is not. The cache's key
        leaves metadata out: an entry written before the scopes existed
        is found and carries none, until that cache is cleared."""
        if variant not in self._scope_maps:
            avals = self.variants.get(variant)
            if avals is None:
                return None
            found = scopes_from_hlo_text(
                self._jit.lower(*avals).compile().as_text()
            )
            self._scope_maps[variant] = found
        return self._scope_maps[variant]

    def _raise_if_uncompilable(self, args, exc: Exception) -> None:
        try:
            self._jit.lower(*args).compile()
        except Exception as compile_exc:  # noqa: BLE001 — whatever the
            # tracer, the lowering or the compiler raises
            raise StepCompileError(
                f"step program {self.name!r} does not compile: "
                f"{type(compile_exc).__name__}: {compile_exc}"
            ) from exc


_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"")
_SCOPE = re.compile(r"llmq\.[\w.]+")
_COLLECTIVE = re.compile(r"all-reduce|reduce-scatter|all-gather")


def scopes_from_hlo_text(text: str) -> Dict[str, str]:
    """``{instruction: scope}`` from a compiled module's text: each
    instruction's ``op_name`` metadata carries the ``jax.named_scope``
    path it was traced under; the innermost ``llmq.*`` name is its scope."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        m = _HLO_INSTR.match(line)
        if m:
            scopes = _SCOPE.findall(m.group(2))
            if not scopes:
                continue
            scope = scopes[-1]
            if _COLLECTIVE.match(m.group(1)):
                # The all-reduce GSPMD puts after a row-parallel matmul
                # carries that matmul's scope.
                if scope == "llmq.o_proj":
                    scope = "llmq.tp.allreduce.o_proj"
                elif scope == "llmq.mlp":
                    scope = "llmq.tp.allreduce.down_proj"
            out[m.group(1)] = scope
    return out


@dataclasses.dataclass
class RequestOutput:
    """Final result of one generation request."""

    rid: str
    text: str
    token_ids: List[int]
    prompt_tokens: int
    completion_tokens: int
    finish_reason: str  # "stop" | "length"
    # Host-side monotonic lifecycle stamps (engine_submit/enqueued/
    # admitted/prefill_start/first_token/last_token/finished +
    # preempt_count; the worker adds claimed), filled when the engine
    # recorded them; workers project these onto the request trace. None
    # for sequences that predate instrumentation.
    timing: Optional[Dict[str, float]] = None
    # Prefill-only requests (finish_reason="prefill_done") carry the
    # prompt-KV snapshot here for the decode-pool handoff; None always
    # for normal completions.
    snapshot: Optional[Any] = None


@dataclasses.dataclass
class EngineConfig:
    max_num_seqs: int = 64
    max_model_len: int = 4096
    page_size: int = 32
    num_pages: Optional[int] = None  # None → size from device HBM
    hbm_utilization: float = 0.9
    # KV cache storage dtype. "fp8" here means float8_e5m2, stored
    # scale-free (no per-tensor scaling factors). Note the vLLM mapping:
    # vLLM's bare ``kv-cache-dtype=fp8`` is an alias for fp8_e4m3 (with
    # calibrated scales); our choice matches vLLM's *explicit*
    # ``fp8_e5m2`` option — e5m2 keeps bf16's exponent range so it needs
    # no scales, trading mantissa instead. Either way the win is the
    # same: half the KV bytes, so double the page pool in the same HBM
    # and half the decode-attention bandwidth. Compute stays f32 inside
    # the kernels (pages are converted on-chip); accepts a jnp dtype or
    # the strings "bf16"/"bfloat16"/"fp8"/"float8_e5m2"/"f32"/"float32".
    kv_dtype: Any = jnp.bfloat16
    min_prefill_bucket: int = 32
    max_prefill_batch: int = 4  # admitted seqs prefetched per iteration
    # Chunked prefill: process prompts in fixed-size chunks of this many
    # positions against the paged cache instead of whole-prompt buckets.
    # One compiled executable for ANY prompt length (no per-bucket
    # variants, ≤ chunk−1 positions of padding), and decode steps for the
    # already-running batch interleave between chunks, so a long prompt
    # no longer stalls every running slot for its whole prefill.
    # None → bucketed whole-prompt prefill (the default).
    prefill_chunk_size: Optional[int] = None
    # Automatic prefix caching (requires chunked prefill): requests that
    # share leading full prompt pages reuse the cached KV via refcounted
    # pages and prefill only the tail — e.g. a shared --map template or
    # system prompt is computed once, not per job.
    enable_prefix_caching: bool = False
    # Admission deferral waits for a full prefill chunk's worth of free
    # slots (throughput), but never keeps *deferring admissible work* for
    # longer than this (latency floor for trickle arrivals; the clock
    # starts at the first deferred step, not at enqueue).
    admit_max_wait_s: float = 0.5
    # The most dispatches kept in flight ahead of result reads: a cap. With
    # slots free and nothing waiting the engine holds as many as cover its
    # own turn (runahead_target; 2 on a host whose turn is shorter than a
    # step) and deepens by itself up to here; with every slot taken, and
    # with pipeline stages, it runs at the cap. The OOM ladder halves it.
    runahead: int = 8
    # Fused multi-step decode: one compiled XLA computation runs this
    # many decode iterations (a lax.scan over the single decode step —
    # attention, KV write, LM head, on-device sampling with the key
    # chain advanced on device) and returns a [K, S] token block, so the
    # host dispatches, snapshots, and fetches once per K tokens instead
    # of once per token. 1 = today's per-token dispatch (the exact same
    # executable as before). The trade: a sequence that finishes at
    # iteration j of a block still rides the remaining K-1 iterations as
    # an inactive row (its tokens are discarded on the host), so large K
    # wastes device work on short completions while shrinking host
    # overhead on long ones; bench.py measures 1/2/4 and keeps the best.
    decode_block: int = 1
    # Lossless speculative decoding: an on-device n-gram prompt-lookup
    # drafter proposes up to spec_tokens candidates per running row
    # (matching the row's recent spec_ngram-token suffix against its own
    # prompt+output history), and one fused verify dispatch scores all
    # spec_tokens+1 positions through the paged-attention path (q-len >
    # 1, exactly like chunked prefill). The longest candidate prefix the
    # model itself would have emitted is accepted — greedy requests are
    # bit-identical to spec_tokens=0, sampled requests keep the exact
    # output distribution via rejection sampling — so one dispatch can
    # emit up to spec_tokens+1 tokens. Rejected candidates' KV writes
    # are simply overwritten by the next step (pages are append-only;
    # per-row lengths rewind on device). 0 = off: the decode executable
    # is literally the non-speculative one. Composes with decode_block
    # (K verify iterations per dispatch).
    spec_tokens: int = 0
    # Draft-match n-gram length for prompt lookup. Longer = fewer but
    # more reliable matches.
    spec_ngram: int = 2
    # Per-slot device-side stop-token-id capacity. Grows automatically
    # (drain + resync + jit retrace at the wider shape) when a request's
    # stop set exceeds it, so min_tokens suppression always covers the
    # full set — no silent truncation.
    stop_id_capacity: int = 8
    # Tensor-parallel collective overlap: "on" replaces GSPMD's two
    # blocking per-layer all-reduces (after o_proj and down_proj) with
    # the chunked bidirectional ppermute rings in
    # ops/collective_matmul.py, so each ICI hop hides behind the next
    # chunk's matmul; "off" (default) traces the literal pre-existing
    # programs — the decode_block=1 / spec_tokens=0 precedent; "auto"
    # lets kernel_autotune A/B ring-vs-GSPMD per deployment.
    # LLMQ_TP_OVERLAP pins over this. Greedy outputs are token-identical
    # either way (the ring reduces in a different order, so float
    # bitstreams may differ at bf16).
    tp_overlap: str = "off"
    # Piggyback scheduling: "on" fuses one head-of-line prefill chunk
    # into each decode dispatch (a single executable runs the decode
    # batch plus up to chunk_size - decode_rows prefill positions for
    # one pending request through the shared paged-attention path), so
    # the MXU bubble left by the bandwidth-bound decode rows does the
    # prefill for free instead of alternating whole dispatches. Greedy
    # outputs are token-identical to "off" (the decode rows' math is
    # unchanged; the chunk rides as an extra row). Requires
    # prefill_chunk_size. LLMQ_MIXED_STEP pins over this.
    mixed_step: str = "off"
    # Pool-exhaustion preemption policy. "recompute" (default) drops the
    # victim's KV and re-prefills prompt+output on re-admission — cheap
    # bookkeeping, expensive re-compute. "swap" gathers the victim's KV
    # pages to host RAM as the deferred-release watermark passes and
    # scatters them back on re-admission, paying two PCIe copies instead
    # of a re-prefill. Greedy outputs are bit-identical either way (the
    # restored pages are the exact bytes the uninterrupted run would have
    # read). LLMQ_PREEMPT_MODE pins over this.
    preempt_mode: str = "recompute"
    # SLO priority classes: interactive sequences are admitted before
    # batch waiters and (priority_preempt) may swap/recompute-preempt
    # the youngest prefilled batch victim when they would otherwise
    # queue for a slot. Scheduling-order only — no sequence's own token
    # stream ever changes, so greedy outputs are token-identical with
    # the knob off. The admission order itself changes only once the
    # first interactive request arrives (lazily enabled, like
    # deadlines), so priority-free deployments are byte-identical.
    # LLMQ_PRIORITY_CLASSES pins over this.
    priority_classes: bool = True
    # Allow interactive admission to preempt a running batch sequence
    # (rides preempt_mode: swap gathers the victim's KV to host, else
    # recompute). LLMQ_PRIORITY_PREEMPT pins over this.
    priority_preempt: bool = True
    # Host-RAM prefix cold tier (GiB of host blobs; 0 = off; requires
    # enable_prefix_caching): cache-registered pages evicted from the
    # device pool park in host RAM keyed by their chain digest, and a
    # later prompt walking the same chain gets them scattered back via
    # insert_kv_pages instead of re-prefilled. Blobs stay in the KV
    # pool's stored dtype, so a host-restored greedy continuation is
    # bit-identical to cold prefill. LLMQ_PREFIX_HOST_GB pins over this.
    prefix_host_gb: float = 0.0
    # Dispatch watchdog (0 = off): every device dispatch/fetch bracket
    # gets a monotonic deadline of max(watchdog_min_s, p99(kind) *
    # watchdog_mult) from the live per-kind dispatch histograms, and a
    # side thread detects (not interrupts — nothing can) a call that
    # overruns it. Off means no thread and no bracketing: the hot path
    # is byte-identical. LLMQ_WATCHDOG_MULT pins over this.
    watchdog_mult: float = 0.0
    # Deadline floor in seconds: protects against tripping on cold-start
    # compiles and empty histograms (a kind with no history uses the
    # floor alone). LLMQ_WATCHDOG_MIN_S pins over this.
    watchdog_min_s: float = 30.0
    # On-device logit guards: "on" folds cheap silent-corruption
    # reductions (any-NaN/Inf count, max |logit|, min row entropy) into
    # every decode/prefill/mixed/verify dispatch and ships the verdict
    # home alongside the sampled tokens — zero extra host syncs. A trip
    # raises the new ``numerical_fault`` class, and blame attribution
    # (re-run the suspects once on a rebuilt core) decides job-poison vs
    # device-fault. "off" (default) traces the literal pre-existing
    # programs. LLMQ_LOGIT_GUARD pins over this.
    logit_guard: str = "off"
    # Guard threshold: any finite logit magnitude above this trips the
    # "logit_max" check. 0 disables the magnitude check (the guard then
    # watches non-finites, plus entropy if enabled). Trace-time constant
    # — changing it retraces. LLMQ_GUARD_LOGIT_MAX pins over this.
    guard_logit_max: float = 0.0
    # Guard threshold: a masked row whose softmax entropy falls below
    # this many nats trips the "entropy_collapse" check (a corrupted
    # lm_head row or a stuck accumulator collapses the distribution to
    # near-determinism at positions where healthy models stay broad).
    # 0 disables. LLMQ_GUARD_ENTROPY_MIN pins over this.
    guard_entropy_min: float = 0.0
    # Background weight-audit cadence in seconds (0 = off): the engine
    # digests every parameter leaf on device at build, then re-digests
    # during idle steps at this cadence (and on demand after any guard
    # trip); a changed leaf means the HBM copy of the weights rotted,
    # distinguishing persistent corruption from a transient compute
    # error. LLMQ_WEIGHT_AUDIT_EVERY pins over this.
    weight_audit_every: float = 0.0
    # Canary self-test cadence in seconds (0 = off): a deterministic
    # golden prompt is generated greedily at engine build and replayed
    # during idle steps at this cadence (and after any suspicion);
    # anything but a bit-exact token match counts a canary failure,
    # which the worker advertises in its heartbeat so the janitor can
    # reclaim a chip that keeps failing. LLMQ_CANARY_EVERY pins over
    # this.
    canary_every: float = 0.0

    def __post_init__(self):
        self.decode_block = int(self.decode_block)
        if self.decode_block < 1:
            raise ValueError(
                f"decode_block={self.decode_block} (want >= 1)"
            )
        self.spec_tokens = int(self.spec_tokens)
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens={self.spec_tokens} (want >= 0)"
            )
        self.spec_ngram = int(self.spec_ngram)
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram={self.spec_ngram} (want >= 1)"
            )
        self.tp_overlap = str(self.tp_overlap).lower()
        if self.tp_overlap not in ("off", "on", "auto"):
            raise ValueError(
                f"tp_overlap={self.tp_overlap!r} (want off|on|auto)"
            )
        self.mixed_step = str(self.mixed_step).lower()
        if self.mixed_step not in ("off", "on"):
            raise ValueError(
                f"mixed_step={self.mixed_step!r} (want off|on)"
            )
        self.preempt_mode = str(self.preempt_mode).lower()
        if self.preempt_mode not in ("recompute", "swap"):
            raise ValueError(
                f"preempt_mode={self.preempt_mode!r} (want recompute|swap)"
            )
        self.prefix_host_gb = float(self.prefix_host_gb)
        if self.prefix_host_gb < 0:
            raise ValueError(
                f"prefix_host_gb={self.prefix_host_gb} (want >= 0)"
            )
        self.watchdog_mult = float(self.watchdog_mult)
        if self.watchdog_mult < 0:
            raise ValueError(
                f"watchdog_mult={self.watchdog_mult} (want >= 0)"
            )
        self.watchdog_min_s = float(self.watchdog_min_s)
        if self.watchdog_min_s <= 0:
            raise ValueError(
                f"watchdog_min_s={self.watchdog_min_s} (want > 0)"
            )
        self.logit_guard = str(self.logit_guard).lower()
        if self.logit_guard not in ("off", "on"):
            raise ValueError(
                f"logit_guard={self.logit_guard!r} (want off|on)"
            )
        self.guard_logit_max = float(self.guard_logit_max)
        if self.guard_logit_max < 0:
            raise ValueError(
                f"guard_logit_max={self.guard_logit_max} (want >= 0)"
            )
        self.guard_entropy_min = float(self.guard_entropy_min)
        if self.guard_entropy_min < 0:
            raise ValueError(
                f"guard_entropy_min={self.guard_entropy_min} (want >= 0)"
            )
        self.weight_audit_every = float(self.weight_audit_every)
        if self.weight_audit_every < 0:
            raise ValueError(
                f"weight_audit_every={self.weight_audit_every} (want >= 0)"
            )
        self.canary_every = float(self.canary_every)
        if self.canary_every < 0:
            raise ValueError(
                f"canary_every={self.canary_every} (want >= 0)"
            )
        if isinstance(self.kv_dtype, str):
            names = {
                "bf16": jnp.bfloat16,
                "bfloat16": jnp.bfloat16,
                "fp8": jnp.float8_e5m2,
                "fp8_e5m2": jnp.float8_e5m2,
                "float8_e5m2": jnp.float8_e5m2,
                "f32": jnp.float32,
                "float32": jnp.float32,
            }
            try:
                self.kv_dtype = names[self.kv_dtype.lower()]
            except KeyError:
                raise ValueError(
                    f"kv_dtype={self.kv_dtype!r} (want one of {sorted(names)})"
                ) from None


def _prefill_buckets(
    cfg: EngineConfig, sp: int = 1, quarter_steps: bool = True
) -> List[int]:
    """Prompt buckets up to max_model_len: powers of two, plus quarter
    steps between octaves above 128. Pure doubling pads badly right
    where real prompts live — a 200-token prompt padded to 256 wastes
    28% of its prefill matmul FLOPs (prefill is compute-bound; padding
    is real work) — while quarter steps cap the waste at ~1/8th
    (200 -> 224). Below 128 the absolute waste is noise and extra
    compiled variants aren't worth it. Each bucket's prefill graph
    compiles lazily on first use, so unused buckets cost nothing.

    Every bucket is rounded up to a multiple of the sequence-parallel
    degree so ring attention (which shards the T axis over sp) applies
    to all of them — notably the top bucket, which is max_model_len
    itself and need not be on the ladder.

    ``quarter_steps=False`` keeps the powers of two alone: a model with a
    layer pattern takes 7-17 s to compile a bucket's program for a v5e
    and runs 26 of them before decode-long's window with quarter steps
    (585 s of set-up, my chip run, PR 33), 8 without."""
    buckets = []
    b = cfg.min_prefill_bucket
    while b < cfg.max_model_len:
        buckets.append(b)
        if b >= 128 and quarter_steps:
            for quarter in (b + b // 4, b + b // 2, b + 3 * b // 4):
                if quarter < cfg.max_model_len:
                    buckets.append(quarter)
        b *= 2
    buckets.append(cfg.max_model_len)
    rounded = [-(-b // sp) * sp for b in buckets]
    return sorted(set(rounded))


# Turns a bucket of the record of long turns: the engine remembers the two
# longest of this bucket and of the one before (3-7 s of 13 ms steps).
_TURN_BUCKET = 256
# Device-paced gaps between fetch returns whose median is the step period.
_STEP_GAPS = 15


def runahead_target(
    turn_s: float, step_s: float, cap: int, *, pp: int = 1, full: bool = False
) -> int:
    """Dispatches to keep in flight before blocking on the oldest one's
    tokens. When a fetch returns, the next entry is starting on the device
    and ``n`` in flight give the host ``n`` step periods to issue its next
    decode dispatch: as many steps as the host's turn ``turn_s`` takes of
    device periods ``step_s``, rounded up, and one more as margin. So never
    fewer than 2 (one step executing, one queued), and never more than
    ``cap``, which also holds while no step period is known.

    ``full``: every slot was taken or requests waited, lately. A request
    that arrives then waits for a slot, not for the device queue, so a short
    queue buys its first token nothing, while every stall of the host that
    the record cannot foresee (this machine stands still for 100-170 ms one
    to three times a minute, my chip runs, PR 34) idles the device under
    all of the running requests: the cap. Pipeline stages read the depth as
    their microbatch count (``m = decode_block × runahead``,
    ``_build_pp_jits``): the cap as well."""
    if pp > 1 or full or step_s <= 0.0:
        return cap
    return min(cap, max(2, math.ceil(turn_s / step_s) + 1))


# Pipeline entry: (dispatch index, kind "prefill"|"decode", device
#                  out-token array — or a (candidates, accept-counts)
#                  pair under speculative decoding —,
#                  [(row-in-out, Sequence), ...] snapshot,
#                  guard (stats, bad-rows) device pair or None)
_Pending = Tuple[int, str, Any, List[Tuple[int, Sequence]], Any]


class EngineCore:
    """Synchronous engine: owns device state and the step loop body."""

    def __init__(
        self,
        model_config: ModelConfig,
        params: Params,
        tokenizer: Tokenizer,
        *,
        mesh: Optional[Mesh] = None,
        engine_config: Optional[EngineConfig] = None,
    ) -> None:
        self.model_config = model_config
        self.tokenizer = tokenizer
        self.cfg = engine_config or EngineConfig()
        self.mesh = mesh if mesh is not None else make_mesh(tensor_parallel=1)
        # Pipeline parallelism: a (pp, dp, sp, tp) mesh is carved into pp
        # independent 3-axis stage submeshes; NOTHING is ever sharded
        # over pp. `self.mesh` is rebound to the LAST (head) stage's
        # submesh so every existing slot sharding, decode-state leaf and
        # sampler binding stays exactly where pp=1 put it — the head
        # stage owns decode state, sampling and the logits matmul, and
        # earlier stages only ever see (tokens, positions, block tables,
        # hidden states).
        self.full_mesh = self.mesh
        self.pp = mesh_pp(self.mesh)
        # What a sequence keeps on the device (models/cache.py): the pools,
        # their cost, the row map, the span's fields, the refusals.
        self.cache = cache_layout(
            model_config,
            page_size=self.cfg.page_size,
            max_model_len=self.cfg.max_model_len,
            max_num_seqs=self.cfg.max_num_seqs,
            kv_dtype=self.cfg.kv_dtype,
        )
        # A declared layer pattern (models/hybrid.py): its step programs
        # return the expert layers' counters beside the tokens, and whole
        # engine paths are refused for it.
        self._hybrid = model_config.layer_pattern is not None
        # Where the decode step that writes a window's last position
        # compacts it (EVA): the window, and the windows compacted so far.
        self._closing_window = self.cache.closing_window
        self.eva_windows_closed = 0
        if self._hybrid:
            self._refuse_for_layer_pattern(params)
        if self.pp > 1:
            if self.cfg.spec_tokens > 0:
                raise ValueError(
                    "spec_tokens > 0 with pp > 1 is not supported: the "
                    "draft/verify loop needs the full layer stack in one "
                    "executable (stage-split verify would ship hidden "
                    "states per candidate token)"
                )
            self._stage_meshes = pp_mod.stage_submeshes(self.mesh)
            self._stage_ranges = pp_mod.stage_layer_ranges(
                model_config.num_layers, self.pp
            )
            self.mesh = self._stage_meshes[-1]
        else:
            self._stage_meshes = [self.mesh]
            self._stage_ranges = [(0, model_config.num_layers)]
        # Resolved once, before any trace: the mode is a static field on
        # the frozen Transformer, so every jit variant (prefill buckets,
        # decode, verify, chunked prefill) sees the same choice and the
        # donation/sharding contracts are untouched.
        self.tp_overlap = _dispatch.resolve_tp_overlap(
            self.cfg.tp_overlap,
            self.mesh,
            hidden_size=model_config.hidden_size,
            intermediate_size=model_config.intermediate_size,
            max_seqs=self.cfg.max_num_seqs,
            logger=logger,
        )
        if self.pp > 1:
            # One Transformer + param subtree + sharding tree per stage.
            # The stage field confines the lax.scan to [lo, hi) layers
            # (local KV indices, global sliding-window policy); the
            # param/sharding trees are generic pytrees under a "stages"
            # key so tree-wide consumers (digest_params, the weight
            # audit) walk them unchanged.
            tied = "lm_head" not in params
            stage_models = []
            stage_trees = []
            stage_shardings = []
            for s, (lo, hi) in enumerate(self._stage_ranges):
                sub = pp_mod.slice_stage_params(
                    params,
                    lo,
                    hi,
                    num_layers=model_config.num_layers,
                    tied_embeddings=tied,
                )
                stage_models.append(
                    Transformer(
                        model_config,
                        mesh=self._stage_meshes[s],
                        tp_overlap=self.tp_overlap,
                        stage=(lo, hi),
                    )
                )
                sh = param_shardings(
                    self._stage_meshes[s], model_config, params=sub
                )
                stage_trees.append(
                    jax.tree.map(jax.device_put, sub, sh)
                )
                stage_shardings.append(sh)
            self._stage_models = stage_models
            self.model = stage_models[-1]
            self.params = {"stages": stage_trees}
            self._param_shardings = {"stages": stage_shardings}
        else:
            self._stage_models = None
            self.model = build_model(
                model_config, mesh=self.mesh, tp_overlap=self.tp_overlap
            )
            self._param_shardings = param_shardings(
                self.mesh, model_config, params=params
            )
            self.params = jax.tree.map(
                jax.device_put, params, self._param_shardings
            )

        if self.cfg.enable_prefix_caching and not self.cfg.prefill_chunk_size:
            raise ValueError(
                "enable_prefix_caching requires prefill_chunk_size: only "
                "chunked prefill can start mid-prompt (the bucketed "
                "executables always compute positions 0..T)"
            )
        # Under pp each stage owns its own pool holding just that stage's
        # [hi-lo] layer slab; every pool shares one page-index space (the
        # scheduler's), so block tables replicate across stages verbatim.
        self._kv_formats = self.cache.placements(
            self._stage_meshes,
            pin=not (on_tpu() and self._decode_kernel_plan() == "xla"),
        )
        self._kv_format = self._kv_formats[-1]
        num_pages = self.cfg.num_pages or self._auto_num_pages()
        sched_cfg = SchedulerConfig(
            max_num_seqs=self.cfg.max_num_seqs,
            num_pages=num_pages,
            page_size=self.cfg.page_size,
            max_model_len=self.cfg.max_model_len,
            enable_prefix_caching=self.cfg.enable_prefix_caching,
        )
        self.scheduler = Scheduler(sched_cfg, self.cache)
        self.scheduler.on_preempt = self._on_scheduler_preempt
        self._pages_per_seq = sched_cfg.pages_per_seq

        if self.pp > 1:
            self.k_pages = []
            self.v_pages = []
            total_bytes = 0
            for s, (lo, hi) in enumerate(self._stage_ranges):
                k_s, v_s = self.cache.allocate(
                    num_pages, self._kv_formats[s], num_layers=hi - lo
                )
                self.k_pages.append(k_s)
                self.v_pages.append(v_s)
                total_bytes += 2 * k_s.size * k_s.dtype.itemsize
            self.kv_pool_bytes = total_bytes
            logger.info(
                "KV cache: %d pages x %d tokens (%.2f GiB total over %d "
                "pipeline stages), %d slots",
                num_pages,
                self.cfg.page_size,
                total_bytes / 2**30,
                self.pp,
                self.cfg.max_num_seqs,
            )
        else:
            self.k_pages, self.v_pages = self.cache.allocate(
                num_pages, self._kv_format
            )
            self.kv_pool_bytes = sum(
                x.size * x.dtype.itemsize
                for x in jax.tree.leaves((self.k_pages, self.v_pages))
            )
            logger.info(
                "KV cache: %d pages x %d tokens (%.2f GiB total), %d slots",
                num_pages,
                self.cfg.page_size,
                self.kv_pool_bytes / 2**30,
                self.cfg.max_num_seqs,
            )

        # Slot-axis sharding: decode shards the batch over dp when it
        # divides evenly; otherwise slots are replicated (tp still shards
        # the model math). Production DP is per-process (reference parity).
        dp = self.mesh.shape[DP_AXIS]
        S = self.cfg.max_num_seqs
        slot_axis = DP_AXIS if dp > 1 and S % dp == 0 else None
        self._repl = NamedSharding(self.mesh, P())
        self._slot1 = NamedSharding(self.mesh, P(slot_axis))
        self._slot2 = NamedSharding(self.mesh, P(slot_axis, None))
        # Fused decode blocks stack K per-step token vectors: [K, S] with
        # the slot axis second, so each device still owns its dp shard.
        self._block1 = NamedSharding(self.mesh, P(None, slot_axis))
        # Speculative verify emits [K, S, Q] candidate tokens per
        # dispatch (Q = spec_tokens + 1); slot axis stays in the middle.
        self._spec_out = NamedSharding(self.mesh, P(None, slot_axis, None))

        self._eos_ids = set(model_config.eos_token_ids) | set(
            tokenizer.eos_token_ids
        )
        if (
            self.cfg.prefill_chunk_size
            and int(self.mesh.shape.get(SP_AXIS, 1)) > 1
        ):
            logger.warning(
                "prefill_chunk_size with sp>1: chunked prefill does not "
                "context-parallelize over the sp axis (each chunk computes "
                "replicated); use bucketed prefill for ring attention"
            )
        tp_size = int(self.mesh.shape.get(TP_AXIS, 1))
        if (
            os.environ.get("LLMQ_INT8_MATMUL", "").lower() == "pallas"
            and tp_size > 1
        ):
            # tp==1 scope (ops/pallas_matmul.py): demote to the XLA int8
            # path before this engine traces. Process-wide by design —
            # workers and bench build exactly one engine per process.
            # With tp_overlap=on the restriction only bites the
            # column-parallel GSPMD sites: the overlap rings' chunk
            # matmuls are plain local calls and keep the Pallas kernel
            # (ops/collective_matmul.py checks the env var directly).
            logger.warning(
                "LLMQ_INT8_MATMUL=pallas is single-chip-only (tp=%d mesh); "
                "using the XLA int8 matmul path for the rest of this "
                "process%s",
                tp_size,
                " (tp_overlap ring chunks keep the Pallas path)"
                if self.tp_overlap == "on"
                else "",
            )
            from llmq_tpu.models import quant as _qm

            _qm.disable_pallas_matmul(f"tp={tp_size} mesh")
        if (
            os.environ.get("LLMQ_INT4_MATMUL", "").lower() == "pallas"
            and tp_size > 1
        ):
            # Same single-chip scope as the int8 kernel above: the int4
            # Pallas matmul has no sharded lowering, so GSPMD call sites
            # demote to the dequant-einsum XLA path on tp>1 meshes while
            # the overlap rings' local chunk calls keep the kernel
            # (ops/collective_matmul.py checks LLMQ_INT4_MATMUL itself).
            logger.warning(
                "LLMQ_INT4_MATMUL=pallas is single-chip-only (tp=%d mesh); "
                "using the XLA int4 matmul path for the rest of this "
                "process%s",
                tp_size,
                " (tp_overlap ring chunks keep the Pallas path)"
                if self.tp_overlap == "on"
                else "",
            )
            from llmq_tpu.models import quant as _qm

            _qm.disable_pallas_matmul(f"tp={tp_size} mesh")
        # Piggyback scheduling: resolved once, before any trace, like
        # tp_overlap above. The env var pins over the config so bench /
        # A-B runs can flip it without threading a flag through workers.
        mixed = os.environ.get("LLMQ_MIXED_STEP", "").lower()
        if mixed in ("on", "off"):
            self.mixed_step = mixed
        else:
            self.mixed_step = self.cfg.mixed_step
        preempt = os.environ.get("LLMQ_PREEMPT_MODE", "").lower()
        if preempt in ("recompute", "swap"):
            self.preempt_mode = preempt
        else:
            self.preempt_mode = self.cfg.preempt_mode
        # SLO priority classes: env pins over config like the knobs
        # above.
        pcls = os.environ.get("LLMQ_PRIORITY_CLASSES", "").lower()
        if pcls in ("0", "false", "no", "off"):
            self.priority_classes = False
        elif pcls in ("1", "true", "yes", "on"):
            self.priority_classes = True
        else:
            self.priority_classes = self.cfg.priority_classes
        ppre = os.environ.get("LLMQ_PRIORITY_PREEMPT", "").lower()
        if ppre in ("0", "false", "no", "off"):
            self.priority_preempt = False
        elif ppre in ("1", "true", "yes", "on"):
            self.priority_preempt = True
        else:
            self.priority_preempt = self.cfg.priority_preempt
        # Host-RAM prefix cold tier: env pins over config like the knobs
        # above. Resolved before hook attachment so the scheduler's
        # eviction path demotes from the very first request.
        host_gb = self.cfg.prefix_host_gb
        env_gb = os.environ.get("LLMQ_PREFIX_HOST_GB", "").strip()
        if env_gb:
            try:
                host_gb = float(env_gb)
            except ValueError:
                raise ValueError(
                    f"LLMQ_PREFIX_HOST_GB={env_gb!r} is not a number"
                ) from None
        self.prefix_host_gb = host_gb
        self.prefix_store = None
        if host_gb > 0:
            if self.pp > 1:
                raise ValueError(
                    "prefix_host_gb > 0 with pp > 1 is not supported: the "
                    "host cold tier demotes single-pool pages; per-stage "
                    "pools need a per-stage demote path (device-level "
                    "prefix caching itself works — stage pools share the "
                    "page-index space)"
                )
            if not self.cfg.enable_prefix_caching:
                raise ValueError(
                    "prefix_host_gb > 0 requires enable_prefix_caching: "
                    "the host tier extends the device prefix cache (there "
                    "is nothing to demote without it)"
                )
            self.prefix_store = PrefixStore(
                int(host_gb * 2**30),
                page_size=self.cfg.page_size,
                model_sig=self.cache.snapshot_sig(),
            )
            self.scheduler.on_demote = self._demote_page
            self.scheduler.host_lookup = self._host_prefix_lookup
            logger.info(
                "prefix host tier: %.2f GiB budget (%d-token pages)",
                host_gb,
                self.cfg.page_size,
            )
        # Dispatch watchdog: env pins over config like the knobs above.
        # Resolved here; the monitor itself starts at the end of __init__
        # once the per-kind dispatch histograms (its deadline source)
        # exist.
        wd_mult = self.cfg.watchdog_mult
        env_mult = os.environ.get("LLMQ_WATCHDOG_MULT", "").strip()
        if env_mult:
            try:
                wd_mult = float(env_mult)
            except ValueError:
                raise ValueError(
                    f"LLMQ_WATCHDOG_MULT={env_mult!r} is not a number"
                ) from None
        wd_min = self.cfg.watchdog_min_s
        env_min = os.environ.get("LLMQ_WATCHDOG_MIN_S", "").strip()
        if env_min:
            try:
                wd_min = float(env_min)
            except ValueError:
                raise ValueError(
                    f"LLMQ_WATCHDOG_MIN_S={env_min!r} is not a number"
                ) from None
        self.watchdog_mult = wd_mult
        self.watchdog_min_s = wd_min
        self.watchdog: Optional[DispatchWatchdog] = None
        # Numerics-integrity knobs: env pins over config like the knobs
        # above. The guard flag and its thresholds are resolved before
        # _build_steps because they are trace-time constants — "off"
        # traces the literal pre-existing programs.
        guard = os.environ.get("LLMQ_LOGIT_GUARD", "").lower()
        if guard in ("on", "off"):
            self.logit_guard = guard
        else:
            self.logit_guard = self.cfg.logit_guard
        if self.logit_guard == "on" and self.pp > 1:
            raise ValueError(
                "logit_guard=on with pp > 1 is not supported: the guard "
                "widens every jit's output tuple, and the pp drivers "
                "re-dispatch those tuples across stage boundaries"
            )
        self.guard_logit_max = self.cfg.guard_logit_max
        env_gmax = os.environ.get("LLMQ_GUARD_LOGIT_MAX", "").strip()
        if env_gmax:
            try:
                self.guard_logit_max = float(env_gmax)
            except ValueError:
                raise ValueError(
                    f"LLMQ_GUARD_LOGIT_MAX={env_gmax!r} is not a number"
                ) from None
        self.guard_entropy_min = self.cfg.guard_entropy_min
        env_gent = os.environ.get("LLMQ_GUARD_ENTROPY_MIN", "").strip()
        if env_gent:
            try:
                self.guard_entropy_min = float(env_gent)
            except ValueError:
                raise ValueError(
                    f"LLMQ_GUARD_ENTROPY_MIN={env_gent!r} is not a number"
                ) from None
        self.weight_audit_every = self.cfg.weight_audit_every
        env_audit = os.environ.get("LLMQ_WEIGHT_AUDIT_EVERY", "").strip()
        if env_audit:
            try:
                self.weight_audit_every = float(env_audit)
            except ValueError:
                raise ValueError(
                    f"LLMQ_WEIGHT_AUDIT_EVERY={env_audit!r} is not a number"
                ) from None
        self.canary_every = self.cfg.canary_every
        env_canary = os.environ.get("LLMQ_CANARY_EVERY", "").strip()
        if env_canary:
            try:
                self.canary_every = float(env_canary)
            except ValueError:
                raise ValueError(
                    f"LLMQ_CANARY_EVERY={env_canary!r} is not a number"
                ) from None
        if self._hybrid:
            # The options an environment variable can pin, once resolved.
            for option, value, off in (
                ("mixed_step", self.mixed_step, "off"),
                ("preempt_mode", self.preempt_mode, "recompute"),
                ("prefix_host_gb", self.prefix_host_gb, 0),
            ):
                if value != off:
                    raise ValueError(self.cache.refusal(f"{option}={value}"))
            # Counters of the expert layers, summed over layers and decode
            # steps; they ride the pending entry and the fetch of the tokens.
            self.moe_assignments_held = 0
            self.moe_experts_hit = 0
        if self.mixed_step == "on" and not self.cfg.prefill_chunk_size:
            raise ValueError(
                "mixed_step=on requires prefill_chunk_size: the fused "
                "dispatch piggybacks a prefill *chunk* onto the decode "
                "batch (bucketed whole-prompt prefill has no chunks)"
            )
        # LLMQ_PP_WIRE=1 routes every stage-boundary hidden-state handoff
        # through the snapshot wire codec (serialize → frame → decode →
        # device_put) instead of a direct device_put. Lossless — the
        # codec round-trips raw bytes — so greedy parity holds; it is the
        # single-process stand-in for the inter-host tcp:// hop and keeps
        # the wire format honest (the same frames ship over DCN when
        # stages live on different hosts).
        self.pp_wire = os.environ.get("LLMQ_PP_WIRE", "0") == "1"
        self._buckets = _prefill_buckets(
            self.cfg, sp=int(self.mesh.shape.get(SP_AXIS, 1)),
            quarter_steps=not self._hybrid,
        )
        self._mla_plan = self._mla_prefill_plan()  # as built: for stats()
        # What the rows of an admission wave share (Scheduler.next_wave):
        # the bucket, i.e. the program, of a whole-prompt prefill. Chunked,
        # mixed and prefix-cached prefill have no buckets.
        self._admit_bucket = (
            None if self.cfg.prefill_chunk_size else self._wave_bucket
        )
        self._build_steps()

        # Host-side mirrors of the device decode state, rebuilt wholesale
        # at every resync (resyncs are rare; steady-state decode ships
        # nothing host→device).
        self._stop_capacity = self.cfg.stop_id_capacity
        E = self._stop_capacity
        # Construction-time shape probe (no dispatch in flight yet).
        key_shape = np.asarray(make_base_key(0, 0)).shape  # llmq: ignore[unguarded-device-fetch]
        self._h_tokens = np.zeros((S,), np.int32)
        self._h_ctx = np.zeros((S,), np.int32)
        self._h_bt = np.zeros((S, self._pages_per_seq), np.int32)
        self._h_active = np.zeros((S,), bool)
        self._h_temp = np.zeros((S,), np.float32)
        self._h_topk = np.zeros((S,), np.int32)
        self._h_topp = np.ones((S,), np.float32)
        self._h_keys = np.zeros((S, *key_shape), np.uint32)
        self._h_steps = np.zeros((S,), np.int32)
        self._h_limits = np.zeros((S,), np.int32)
        self._h_mins = np.zeros((S,), np.int32)
        self._h_stopids = np.full((S, E), -1, np.int32)
        # Speculative decoding only: per-slot prompt+output token history
        # ([S, max_model_len], the drafter's lookup corpus). Appended as
        # the 13th decode-state leaf so drafting happens on device — the
        # run-ahead pipeline still ships zero bytes host→device in steady
        # state.
        self._h_history = (
            np.zeros((S, self.cfg.max_model_len), np.int32)
            if self.cfg.spec_tokens > 0
            else None
        )

        # Run-ahead pipeline state.
        self._pending: Deque[_Pending] = deque()
        self._pending_decodes = 0  # decode entries within _pending
        # How many of them to keep in flight (runahead_target), set anew at
        # the top of every step() from the loop's own clock readings:
        # ``_turn_from`` is when the last fetch returned with work still
        # queued behind it (0.0: nothing to be late for); ``_turn_worst``
        # the two longest fetch-return-to-decode-dispatch times of this
        # bucket of turns, then of the bucket before; ``_full`` whether a
        # turn of this bucket, of the one before, found every slot taken or
        # requests waiting; ``_step_s`` the device's step period, the
        # median of ``_step_gaps`` (the spacing of fetch returns that the
        # device paced).
        self._ahead = self.cfg.runahead
        self._turn_from = 0.0
        self._turn_worst = [0.0, 0.0, 0.0, 0.0]
        self._full = [False, False]
        self._turn_n = 0
        self._step_s = 0.0
        self._step_gaps: Deque[float] = deque(maxlen=_STEP_GAPS)
        self._fetched = (0, 0.0, False)  # dispatch index, at, device-paced
        self._defer_since: Optional[float] = None  # admission-deferral start
        # The engine thread's span ring (obs/spans.py): off by default,
        # and every site tests ``spans.on`` in place before it writes.
        self.spans = SpanRing("engine")
        self.spans.extra = self._dump_scopes
        self._deferred_pages: List[Tuple[int, List[int], int]] = []
        # Swap-to-host captures awaiting their deferred-release watermark:
        # (dispatch_idx, seq, pages, kv_valid, epoch-at-preemption). Each
        # rides the same watermark as its _deferred_pages entry and is
        # gathered to host BEFORE those pages return to the allocator.
        self._pending_swaps: List[Tuple[int, Sequence, List[int], int, int]] = []
        self._dispatch_idx = 0
        self._processed_idx = 0
        self._dirty = True
        self._mode = "greedy"
        self._dev_state: Optional[tuple] = None
        # Chaos/test hook: called with the dispatch kind ("prefill",
        # "mixed", "decode_block", "verify") after every device dispatch
        # is recorded. Runs on the engine thread; must be cheap.
        self.on_dispatch: Optional[Any] = None

        # Counters for stats/heartbeats.
        self.total_prompt_tokens = 0
        self.total_generated_tokens = 0
        self.decode_steps = 0  # device decode iterations (K per dispatch)
        self.decode_dispatches = 0  # host round trips for those iterations
        self.spec_proposed = 0  # draft tokens offered for verification
        self.spec_accepted = 0  # draft tokens the model confirmed
        self.prefills = 0
        self.mixed_steps = 0  # fused decode+prefill dispatches
        self.mixed_prefill_tokens = 0  # prompt positions piggybacked
        self.swap_preempts = 0  # preemptions whose KV was swapped to host
        self.kv_restores = 0  # admissions restored from host KV pages
        self.snapshots_extracted = 0
        self.snapshots_inserted = 0
        self.prefill_done = 0  # prefill-only requests finished at the boundary
        # rid → RequestSnapshot taken at the prefill boundary, popped by
        # _output_for when the finished RequestOutput is built. Transient:
        # entries live only between _append_and_check and the drain of the
        # same step's finished list.
        self._prefill_snapshots: Dict[str, RequestSnapshot] = {}
        self.prefill_tokens = 0  # prompt positions actually computed
        # Positions the prefill programs were shaped for (rows x width,
        # padding included): over prefill_tokens it is the padding factor.
        self.prefill_grid_tokens = 0
        self.prefix_demotes = 0  # pages parked in the host tier on evict
        self.prefix_promotes = 0  # pages restored from the host tier
        self.prefix_chunks_exported = 0  # pages serialized for peers
        self.prefix_chunks_ingested = 0  # shipped pages accepted
        self.deadline_expirations = 0  # sequences expired by the sweep
        # SLO priority plane. _priority_enabled flips at the first
        # interactive request (like _deadlines_enabled): a fleet that
        # never sets Job.priority keeps the exact pre-priority admission
        # order AND byte-identical stats payloads.
        self._priority_enabled = False
        self.priority_preemptions = 0  # batch victims evicted for interactive
        # Per-class finish accounting for goodput: requests that finished
        # cleanly ("stop"/"length"/EOS) vs shed/expired/cancelled ones.
        self.class_finished = {"interactive": 0, "batch": 0}
        self.class_tokens = {"interactive": 0, "batch": 0}
        # Client-disconnect cancellation: rid → monotonic enqueue time.
        # Swept between steps; unknown rids (result already out, or a
        # request this engine never saw) age out after _CANCEL_TTL_S.
        self._cancel_rids: Dict[str, float] = {}
        self.cancellations = 0  # sequences finished by the cancel sweep
        # Per-token host callback (streaming): called on the engine
        # thread as (seq, token) for every token that SURVIVES the stop
        # check (popped stop tokens never stream). Must be cheap.
        self.on_token: Optional[Any] = None
        self.swap_refused = 0  # captures the host-memory governor declined
        self.hbm_oom_events = 0  # allocation faults the ladder absorbed
        # Numerics-integrity counters (superset-only in stats: all stay
        # at zero — and their stats keys absent — with the knobs off).
        # Pipeline-parallel boundary accounting (pp > 1 only; superset-
        # only keys in stats). One "transfer" is one stage→stage hidden-
        # state handoff; bytes count the [rows, T, H] activation payload.
        self.pp_boundary_bytes = 0
        self.pp_boundary_transfers = 0
        self.guard_trips = 0  # dispatches whose on-device guard fired
        self.weight_audits = 0  # background/on-demand digest sweeps run
        self.weight_audit_mismatches = 0  # leaves whose HBM digest changed
        self.kv_spot_checks = 0  # KV page read-stability samples
        self.canary_runs = 0  # golden-prompt replays
        self.canary_failures = 0  # replays that were not bit-exact
        # Leaf paths from the most recent failed audit (bounded: replaced
        # wholesale per audit, never appended across audits).
        self._last_audit_mismatch: List[str] = []
        self._weight_baseline: Optional[Dict[str, Tuple[int, int]]] = None
        self._canary_golden: Optional[List[int]] = None
        self._next_weight_audit = 0.0
        self._next_canary = 0.0
        # HBM-OOM degradation ladder position (monotonic per engine: a
        # pool that OOMed once stays degraded) and the rungs taken, in
        # order, for stats/probes.
        self._oom_rung = 0
        # Bounded by construction: the rung counter is monotonic 0→3, so
        # at most three entries are ever appended per engine lifetime.
        self._oom_ladder_log: List[str] = []  # llmq: ignore[unbounded-host-buffer]
        # Flipped by the first deadline-carrying request so the per-step
        # sweep costs nothing on deadline-free deployments.
        self._deadlines_enabled = False
        self._started_at = time.monotonic()

        # Unified host-memory governor: the prefix cold tier and the
        # swap-restore blobs report into the shared budget (registration
        # only when a budget is configured — default engines touch
        # nothing). Names are per-instance so test processes running
        # several engines don't shadow each other's gauges.
        gov = get_governor()
        if gov.enabled:
            tag = f"engine-{id(self):x}"
            gov.register(f"swap:{tag}", self._swap_restore_bytes)
            if self.prefix_store is not None:
                gov.register(
                    f"prefix:{tag}",
                    lambda: self.prefix_store.occupancy_bytes,
                    evict_fn=self._evict_prefix_bytes,
                )

        # Observability: host-side only — a histogram record is a bucket
        # increment, never inside jitted code. Per-engine instances
        # (not registry get-or-create) so stats() percentiles never mix
        # across the many engines a test process builds; `register`
        # replaces same-named series, so the latest engine owns the
        # exported ones (one engine per worker process in production).
        self.ttft_hist = Histogram(
            "llmq_ttft_seconds", "Enqueue-to-first-token latency"
        )
        self.itl_hist = Histogram(
            "llmq_itl_seconds",
            "Inter-token latency at the host boundary",
            buckets=ITL_BUCKETS,
        )
        # Per-class SLO latency series: interactive requests observe into
        # BOTH the all-class hists above and these labeled ones, so the
        # unlabeled series keeps its pre-priority meaning. Batch gets no
        # extra series (it IS the unlabeled series minus interactive, and
        # a priority-free fleet's export stays identical).
        self.ttft_hist_interactive = Histogram(
            "llmq_ttft_seconds",
            "Enqueue-to-first-token latency (interactive class)",
            labels={"class": "interactive"},
        )
        self.itl_hist_interactive = Histogram(
            "llmq_itl_seconds",
            "Inter-token latency at the host boundary (interactive class)",
            buckets=ITL_BUCKETS,
            labels={"class": "interactive"},
        )
        # Keyed by dispatch kind ("prefill"/"decode"/"mixed"): a fixed set.
        self._dispatch_hists: Dict[str, Histogram] = {}  # llmq: ignore[unbounded-host-buffer]
        reg = get_registry()
        for metric in (
            self.ttft_hist,
            self.itl_hist,
            self.ttft_hist_interactive,
            self.itl_hist_interactive,
            self.scheduler.queue_wait_hist,
            self.scheduler.preempt_delay_hist,
            Gauge(
                "llmq_engine_tokens_per_sec",
                "Generated tokens per second since engine start",
                fn=lambda: self.total_generated_tokens
                / max(1e-9, time.monotonic() - self._started_at),
            ),
            Gauge(
                "llmq_engine_kv_page_utilization",
                "Fraction of the KV page pool in use",
                fn=lambda: (
                    (self.scheduler.config.num_pages - 1)
                    - self.scheduler.allocator.available
                )
                / max(1, self.scheduler.config.num_pages - 1),
            ),
            Gauge(
                "llmq_engine_batch_occupancy",
                "Fraction of decode slots holding a running sequence",
                fn=lambda: len(self.scheduler.running)
                / max(1, self.cfg.max_num_seqs),
            ),
            Gauge(
                "llmq_prefix_hit_pages",
                "KV pages reused via the prefix cache (device + host tier)",
                fn=lambda: self.scheduler.prefix_hits,
            ),
            Gauge(
                "llmq_prefix_miss_pages",
                "Full prompt pages that had to prefill (prefix cache miss)",
                fn=lambda: self.scheduler.prefix_misses,
            ),
            Gauge(
                "llmq_prefix_demote_pages",
                "Evicted device pages parked in the host prefix tier",
                fn=lambda: self.prefix_demotes,
            ),
            Gauge(
                "llmq_prefix_promote_pages",
                "Pages restored from the host prefix tier to device",
                fn=lambda: self.prefix_promotes,
            ),
            Gauge(
                "llmq_prefix_host_evictions",
                "Host prefix tier entries dropped by the byte-budget LRU",
                fn=lambda: (
                    self.prefix_store.evictions if self.prefix_store else 0
                ),
            ),
            Gauge(
                "llmq_prefix_host_bytes",
                "Host prefix tier occupancy in bytes",
                fn=lambda: (
                    self.prefix_store.occupancy_bytes
                    if self.prefix_store
                    else 0
                ),
            ),
            Gauge(
                "llmq_prefix_host_entries",
                "Host prefix tier resident page count",
                fn=lambda: (
                    len(self.prefix_store) if self.prefix_store else 0
                ),
            ),
            Gauge(
                "llmq_priority_preemptions",
                "Batch sequences preempted so interactive work could admit",
                fn=lambda: self.priority_preemptions,
            ),
            Gauge(
                "llmq_class_tokens",
                "Tokens generated for interactive-class requests",
                labels={"class": "interactive"},
                fn=lambda: self.class_tokens["interactive"],
            ),
            Gauge(
                "llmq_class_tokens",
                "Tokens generated for batch-class requests",
                labels={"class": "batch"},
                fn=lambda: self.class_tokens["batch"],
            ),
            Gauge(
                "llmq_class_finished",
                "Interactive-class requests finished cleanly (goodput)",
                labels={"class": "interactive"},
                fn=lambda: self.class_finished["interactive"],
            ),
            Gauge(
                "llmq_class_finished",
                "Batch-class requests finished cleanly (goodput)",
                labels={"class": "batch"},
                fn=lambda: self.class_finished["batch"],
            ),
            Gauge(
                "llmq_integrity_guard_trips",
                "Dispatches whose on-device logit guard fired",
                fn=lambda: self.guard_trips,
            ),
            Gauge(
                "llmq_integrity_weight_audit_mismatches",
                "Parameter leaves whose HBM digest diverged from the "
                "build-time baseline",
                fn=lambda: self.weight_audit_mismatches,
            ),
            Gauge(
                "llmq_integrity_canary_failures",
                "Golden-prompt canary replays that were not bit-exact",
                fn=lambda: self.canary_failures,
            ),
        ):
            reg.register(metric)

        self._resync()
        if os.environ.get("LLMQ_PARAM_AUTO_LAYOUT", "0") == "1":
            self._optimize_param_layouts()

        # Dispatch watchdog (default off): deadlines read the per-kind
        # histograms above, so it starts last. p99 comes from the live
        # distribution; kinds with no history (cold start, snapshot
        # gathers) fall back to the floor inside deadline_for.
        if self.watchdog_mult > 0:
            self.watchdog = DispatchWatchdog(
                mult=self.watchdog_mult,
                min_s=self.watchdog_min_s,
                percentile_fn=self._dispatch_p99,
            )
            logger.info(
                "dispatch watchdog: p99 x %.1f, floor %.1fs",
                self.watchdog_mult,
                self.watchdog_min_s,
            )

        # Integrity baselines, recorded last so they see the final
        # (possibly re-laid-out) parameters and a fully working engine.
        if self.weight_audit_every > 0:
            from llmq_tpu.engine import integrity as integrity_mod

            with self._wd("weight_audit"):
                self._weight_baseline = integrity_mod.digest_params(
                    self.params
                )
            self._next_weight_audit = (
                time.monotonic() + self.weight_audit_every
            )
            logger.info(
                "weight audit: %d leaves digested, sweeping every %.1fs",
                len(self._weight_baseline),
                self.weight_audit_every,
            )
        if self.canary_every > 0:
            self._canary_golden = self._generate_canary()
            self._next_canary = time.monotonic() + self.canary_every
            logger.info(
                "canary self-test: %d golden tokens, replaying every %.1fs",
                len(self._canary_golden),
                self.canary_every,
            )

    def _refuse_for_layer_pattern(self, params: Params) -> None:
        """What a layer pattern cannot do yet, refused at build by name: a
        per-sequence state cannot be shared by a prefix, cut at a chunk,
        rewound by a length or moved between pools; for a latent cache
        alone those paths are not built; either way the expert share is
        held whole on one device."""
        from llmq_tpu.models import quant as qm

        cfg = self.cfg
        tp = int(self.mesh.shape.get(TP_AXIS, 1))
        quantised = any(
            qm.is_quantized(leaf)
            for leaf in jax.tree.leaves(params, is_leaf=qm.is_quantized)
        )
        for refused, what in (
            (cfg.enable_prefix_caching, "enable_prefix_caching"),
            (cfg.prefix_host_gb > 0, "prefix_host_gb"),
            (cfg.spec_tokens > 0, f"spec_tokens={cfg.spec_tokens}"),
            (bool(cfg.prefill_chunk_size), "prefill_chunk_size"),
            (self.pp > 1, f"pp={self.pp}"),
            (tp > 1, f"tp={tp}"),
            (quantised, "quantised weights"),
            (jnp.dtype(cfg.kv_dtype).itemsize < 2, f"kv_dtype={cfg.kv_dtype}"),
        ):
            if refused:
                raise ValueError(self.cache.refusal(what))

    def _dispatch_p99(self, kind: str) -> Optional[float]:
        """Watchdog deadline source: live p99 of one dispatch kind, or
        None (→ floor) before any dispatch of that kind landed. Reads a
        histogram the engine thread appends to; bucket counts are ints,
        so a torn read costs at most one stale observation."""
        hist = self._dispatch_hists.get(kind)
        if hist is None:
            return None
        return hist.percentile(0.99)

    def _wd(self, kind: str):
        """Watchdog bracket for one device dispatch/fetch boundary; the
        shared no-op context when the watchdog is off (the default), so
        the hot path stays allocation-free and byte-identical."""
        wd = self.watchdog
        return NO_GUARD if wd is None else wd.guard(kind)

    def stop_watchdog(self) -> None:
        """Stop the monitor thread (engine teardown / fault rebuild)."""
        if self.watchdog is not None:
            self.watchdog.stop()

    # --- compilation ------------------------------------------------------
    def _build_steps(self) -> None:
        model = self.model
        S = self.cfg.max_num_seqs
        spec = self.cfg.spec_tokens > 0
        hybrid = self._hybrid  # trace-time: other models' programs are as they were
        # On-device logit guard (default off → every closure below traces
        # the literal pre-existing program). When on, each step also
        # returns (stats f32[3], bad bool[rows]) folded from its logits;
        # thresholds are trace-time constants.
        guard = self.logit_guard == "on"
        g_max, g_ent = self.guard_logit_max, self.guard_entropy_min

        def guard_stats(logits, mask):
            return _dispatch.logit_guard_stats(
                logits, mask, max_abs=g_max, min_entropy=g_ent
            )

        # Device decode-state layout (leaf order is load-bearing):
        # 0 tokens[S]  1 ctx[S]    2 bt[S,pps]  3 active[S]  4 keys[S,kd]
        # 5 steps[S]   6 temps[S]  7 topks[S]   8 topps[S]   9 limits[S]
        # 10 mins[S]   11 stop_ids[S,E]
        # Speculative decoding appends leaf 12: history[S, max_model_len]
        # (prompt+output tokens; history[ctx] is the current token) —
        # the on-device drafter's lookup corpus. spec_tokens=0 builds
        # the exact 12-leaf state and functions as before.
        def advance_state(st, out, active):
            (tokens, ctx, bt, _, keys, steps, temps, topks, topps,
             limits, mins, stop_ids) = st
            new_steps = steps + active.astype(steps.dtype)
            hit_stop = jnp.logical_and(
                (out[:, None] == stop_ids).any(axis=1), new_steps > mins
            )
            hit_limit = new_steps >= limits
            still = jnp.logical_and(
                active,
                jnp.logical_not(jnp.logical_or(hit_stop, hit_limit)),
            )
            return (
                jnp.where(active, out, tokens),
                ctx + active.astype(ctx.dtype),
                bt,
                still,
                keys,
                new_steps,
                temps,
                topks,
                topps,
                limits,
                mins,
                stop_ids,
            )

        def suppress_stops(logits, stop_ids, steps, mins):
            """Mask stop/EOS logits while a slot is under min_tokens, so
            the forbidden token can never be sampled (vLLM semantics)."""

            def apply(logits):
                V = logits.shape[1]
                ids = jnp.where(stop_ids < 0, V, stop_ids)  # pad → OOB → drop
                rows = jnp.broadcast_to(
                    jnp.arange(ids.shape[0])[:, None], ids.shape
                )
                masked = logits.at[rows, ids].set(
                    sampling_mod.NEG_INF, mode="drop"
                )
                return jnp.where((steps < mins)[:, None], masked, logits)

            # min_tokens is rare; the scatter + full-logits rewrite costs
            # ~0.7 ms/step on [192, 152k] (measured) — skip it on device
            # unless some slot is actually still under its minimum.
            return jax.lax.cond(
                jnp.any(steps < mins), apply, lambda l: l, logits
            )

        def decode_step(params, kp, vp, st, *, mode, h=None):
            (tokens, ctx, bt, active, keys, steps, temps, topks,
             topps, _limits, mins, stop_ids) = st
            if hybrid:
                # A slot's state row is its index + 1 (row 0 is scratch);
                # the expert layers' counters leave beside the tokens.
                logits, kp, vp, moe = model.decode(
                    params, tokens, ctx, kp, vp, bt, active, 1, counters=True,
                )
            else:
                logits, kp, vp = model.decode(
                    params, tokens, ctx, kp, vp, bt, active, h=h
                )
            # Guard reads the raw model logits: suppress_stops writes
            # NEG_INF sentinels that would false-trip the magnitude lane.
            g = guard_stats(logits, active) if guard else None
            logits = suppress_stops(logits, stop_ids, steps, mins)
            next_tokens = sample_tokens(
                logits, keys, steps, temps, topks, topps, mode=mode
            )
            out = jnp.where(active, next_tokens, 0)
            new_st = advance_state(st, out, active)
            if hybrid:
                out = (out, moe)
            if guard:
                return (out, g), kp, vp, new_st
            return out, kp, vp, new_st

        def decode_block_step(params, kp, vp, st, *, mode):
            """``decode_block`` fused decode iterations in ONE XLA
            computation: a ``lax.scan`` over ``decode_step`` carrying
            (kv pools, decode state) and stacking the per-iteration
            token vectors into a [K, S] block. Everything the host used
            to do between steps happens on device instead: the sampling
            key chain advances because ``advance_state`` increments the
            carried per-slot step counters that ``sample_tokens`` folds
            into the (fixed) base keys, and per-row stopping works
            because ``advance_state`` deactivates finished rows, whose
            remaining iterations then emit token 0 and write no KV
            (positions route to -1 / ctx_incl 0). Rows that finish at
            iteration j still ride out iterations j+1..K-1 inactive —
            the host discards those tokens when it processes the block.
            """

            def body(carry, _):
                kp, vp, st = carry
                out, kp, vp, st = decode_step(params, kp, vp, st, mode=mode)
                return (kp, vp, st), out

            (kp, vp, st), outs = jax.lax.scan(
                body,
                (kp, vp, st),
                None,
                length=self.cfg.decode_block,
            )
            return outs, kp, vp, st

        M = self.cfg.max_model_len
        n_draft = self.cfg.spec_tokens
        n_gram = self.cfg.spec_ngram
        max_kv_pos = self._pages_per_seq * self.cfg.page_size

        def draft_lookup(history, ctx):
            """On-device prompt-lookup drafter: find the most recent
            earlier occurrence of the n_gram-token suffix ending at
            history[ctx] and propose the n_draft tokens that followed
            it. Rows with no match (or fewer than n_gram tokens so far)
            draft -1, which never equals an emitted token — the verify
            step then degenerates to exactly one non-speculative decode
            for that row. Overlapping matches are fine (repetition runs
            draft themselves), and stale tokens past ctx can never leak:
            gathers are clipped into the row and every draft is verified
            before it is emitted."""
            sfx_pos = ctx[:, None] - (n_gram - 1) + jnp.arange(n_gram)
            sfx = jnp.take_along_axis(
                history, jnp.clip(sfx_pos, 0, M - 1), axis=1
            )  # [S, n_gram]
            match = jnp.ones((S, M), bool)
            for t in range(n_gram):
                eq = history == sfx[:, t][:, None]
                # Shift so position p asks "does the n-gram ENDING at p
                # match the suffix" for every element at once.
                match &= jnp.roll(eq, (n_gram - 1) - t, axis=1)
            p_idx = jnp.arange(M)[None, :]
            match &= (
                (p_idx >= n_gram - 1)
                & (p_idx < ctx[:, None])
                & (ctx[:, None] + 1 >= n_gram)
            )
            j = jnp.max(jnp.where(match, p_idx, -1), axis=1)  # [S]
            d_pos = j[:, None] + 1 + jnp.arange(n_draft)[None, :]
            drafts = jnp.take_along_axis(
                history, jnp.clip(d_pos, 0, M - 1), axis=1
            )
            return jnp.where((j >= 0)[:, None], drafts, -1)

        def verify_step(params, kp, vp, st, *, mode):
            """One speculative decode iteration: draft, score all
            Q = spec_tokens+1 candidate positions in one model call
            (multi-query decode through the chunked-prefill attention
            path), accept the longest prefix the model itself emits,
            and advance per-row state by the accepted count. Rejected
            positions' KV stays in place — their sequence length simply
            doesn't advance past them, and the next verify step rewrites
            the same append-only positions. Emits ``(emit [S, Q],
            count [S])``: count = accepted drafts + 1 corrected/bonus
            token (0 for inactive rows); the host appends
            ``emit[row, :count]``."""
            (tokens, ctx, bt, active, keys, steps, temps, topks,
             topps, limits, mins, stop_ids, history) = st
            Q = n_draft + 1
            drafts = draft_lookup(history, ctx)  # [S, n_draft]
            qtok = jnp.concatenate(
                [tokens[:, None], jnp.maximum(drafts, 0)], axis=1
            )  # [S, Q]
            pos_grid = ctx[:, None] + jnp.arange(Q)[None, :]
            # Inactive rows and positions past the per-row page map route
            # to -1 (scratch page, no attention): an unmapped position
            # would otherwise clamp into the row's LAST mapped page and
            # corrupt it. The grid keeps the leading-contiguous-run form
            # the chunked-prefill kernel contract requires.
            qpos = jnp.where(
                active[:, None] & (pos_grid < max_kv_pos), pos_grid, -1
            )
            logits, kp, vp = model.verify(params, qtok, qpos, kp, vp, bt)
            V = logits.shape[-1]
            if guard:
                # Raw logits (pre suppress_stops sentinels); per-row
                # verdict folds the Q candidate positions of each slot.
                g_stats, g_bad = guard_stats(
                    logits.reshape(S * Q, V), jnp.repeat(active, Q)
                )
                g = (g_stats, g_bad.reshape(S, Q).any(axis=1))
            else:
                g = None
            steps_grid = steps[:, None] + jnp.arange(Q)[None, :]
            flat = suppress_stops(
                logits.reshape(S * Q, V),
                jnp.repeat(stop_ids, Q, axis=0),
                steps_grid.reshape(-1),
                jnp.repeat(mins, Q),
            )
            emit = sampling_mod.spec_verify_tokens(
                flat.reshape(S, Q, V), drafts, keys, steps,
                temps, topks, topps, mode=mode,
            )  # [S, Q]
            # Position i is reached iff every earlier draft was accepted
            # (emit == draft); position 0 (the normal decode token) is
            # always reached on active rows.
            reached = jnp.concatenate(
                [
                    jnp.ones((S, 1), bool),
                    jnp.cumprod(
                        (emit[:, :-1] == drafts).astype(jnp.int32), axis=1
                    ).astype(bool),
                ],
                axis=1,
            )
            # Stopping mirrors advance_state per position: a stop/limit
            # hit at position i emits i's token and cuts everything after.
            new_steps_grid = steps_grid + 1
            hit_stop = (
                (emit[:, :, None] == stop_ids[:, None, :]).any(axis=2)
                & (new_steps_grid > mins[:, None])
            )
            stop_here = hit_stop | (new_steps_grid >= limits[:, None])
            stopped_before = (
                jnp.cumsum(stop_here.astype(jnp.int32), axis=1)
                - stop_here.astype(jnp.int32)
            ) > 0
            emitted = active[:, None] & reached & ~stopped_before  # [S, Q]
            count = emitted.sum(axis=1).astype(ctx.dtype)  # [S]
            new_tok = jnp.take_along_axis(
                emit, jnp.maximum(count - 1, 0)[:, None], axis=1
            )[:, 0]
            still = active & ~(emitted & stop_here).any(axis=1)
            rows = jnp.broadcast_to(jnp.arange(S)[:, None], (S, Q))
            hist_pos = jnp.where(emitted, pos_grid + 1, M)  # OOB → drop
            st = (
                jnp.where(count > 0, new_tok, tokens),
                ctx + count,
                bt,
                still,
                keys,
                steps + count,
                temps,
                topks,
                topps,
                limits,
                mins,
                stop_ids,
                history.at[rows, hist_pos].set(emit, mode="drop"),
            )
            ys = (jnp.where(emitted, emit, 0), count)
            if guard:
                return (ys, g), kp, vp, st
            return ys, kp, vp, st

        def verify_block_step(params, kp, vp, st, *, mode):
            """decode_block fused verify iterations in one XLA
            computation, mirroring decode_block_step. Always a lax.scan
            (even K=1) so the output block is uniformly ([K, S, Q]
            tokens, [K, S] accept counts)."""

            def body(carry, _):
                kp, vp, st = carry
                ys, kp, vp, st = verify_step(params, kp, vp, st, mode=mode)
                return (kp, vp, st), ys

            (kp, vp, st), outs = jax.lax.scan(
                body,
                (kp, vp, st),
                None,
                length=self.cfg.decode_block,
            )
            return outs, kp, vp, st

        def sample_and_scatter(logits, valid, p_lengths, p_bt, p_slots,
                               p_keys, p_steps, p_temps, p_topks, p_topps,
                               p_limits, p_mins, p_stopids, st, *, mode,
                               p_history=None):
            """Shared tail of the prefill variants: sample each valid
            row's first token and scatter the row into the decode state
            (invalid rows route out of range and are dropped)."""
            logits = suppress_stops(logits, p_stopids, p_steps, p_mins)
            nt = sample_tokens(
                logits, p_keys, p_steps, p_temps, p_topks, p_topps, mode=mode
            )
            out = jnp.where(valid, nt, 0)
            new_steps = p_steps + 1
            hit_stop = jnp.logical_and(
                (out[:, None] == p_stopids).any(axis=1), new_steps > p_mins
            )
            alive = jnp.logical_and(
                valid,
                jnp.logical_not(
                    jnp.logical_or(hit_stop, new_steps >= p_limits)
                ),
            )
            idx = jnp.where(valid, p_slots, S)
            (tokens, ctx, bt, active, keys, steps, temps, topks, topps,
             limits, mins, stop_ids, *hist) = st
            st = (
                tokens.at[idx].set(out, mode="drop"),
                ctx.at[idx].set(p_lengths, mode="drop"),
                bt.at[idx].set(p_bt, mode="drop"),
                active.at[idx].set(alive, mode="drop"),
                keys.at[idx].set(p_keys, mode="drop"),
                steps.at[idx].set(new_steps, mode="drop"),
                temps.at[idx].set(p_temps, mode="drop"),
                topks.at[idx].set(p_topks, mode="drop"),
                topps.at[idx].set(p_topps, mode="drop"),
                limits.at[idx].set(p_limits, mode="drop"),
                mins.at[idx].set(p_mins, mode="drop"),
                stop_ids.at[idx].set(p_stopids, mode="drop"),
            )
            if spec:
                # Keep the drafter's invariant history[ctx] == current
                # token: the row's prompt+output plus its fresh first
                # sample at position p_lengths (== the new ctx).
                B = p_history.shape[0]
                hrow = p_history.at[jnp.arange(B), p_lengths].set(
                    out, mode="drop"
                )
                st += (hist[0].at[idx].set(hrow, mode="drop"),)
            return out, st

        def prefill_step(params, kp, vp, p_tokens, p_lengths, p_bt, p_slots,
                         p_keys, p_steps, p_temps, p_topks, p_topps,
                         p_limits, p_mins, p_stopids, *rest, mode, h=None):
            # rest = (p_history, st) under speculation, (st,) otherwise.
            p_history, st = rest if spec else (None, rest[0])
            if hybrid:
                logits, kp, vp = model.prefill(
                    params, p_tokens, p_lengths, kp, vp, p_bt,
                    jnp.where(p_slots >= 0, p_slots + 1, 0),
                )
            else:
                logits, kp, vp = model.prefill(
                    params, p_tokens, p_lengths, kp, vp, p_bt, h=h
                )
            g = guard_stats(logits, p_slots >= 0) if guard else None
            out, st = sample_and_scatter(
                logits, p_slots >= 0, p_lengths, p_bt, p_slots, p_keys,
                p_steps, p_temps, p_topks, p_topps, p_limits, p_mins,
                p_stopids, st, mode=mode, p_history=p_history,
            )
            if guard:
                return (out, g), kp, vp, st
            return out, kp, vp, st

        def chunkfill_step(params, kp, vp, c_tokens, c_positions, c_bt,
                           c_final, c_last, c_lengths, c_slots, c_keys,
                           c_steps, c_temps, c_topks, c_topps, c_limits,
                           c_mins, c_stopids, *rest, mode, h=None):
            """One chunk of prompt positions for up to B rows. Rows whose
            prompt ENDS in this chunk (c_final) sample their first token
            and scatter into the decode state exactly like prefill_step;
            other rows only extend their cached K/V."""
            c_history, st = rest if spec else (None, rest[0])
            logits, kp, vp = model.prefill_chunk(
                params, c_tokens, c_positions, kp, vp, c_bt, c_last, h=h
            )
            # Guard watches every valid row's chunk logits (non-final
            # rows too: mid-prompt logits are real model outputs, so
            # corruption surfaces chunks before the first sample).
            g = guard_stats(logits, c_slots >= 0) if guard else None
            out, st = sample_and_scatter(
                logits, jnp.logical_and(c_slots >= 0, c_final), c_lengths,
                c_bt, c_slots, c_keys, c_steps, c_temps, c_topks, c_topps,
                c_limits, c_mins, c_stopids, st, mode=mode,
                p_history=c_history,
            )
            if guard:
                return (out, g), kp, vp, st
            return out, kp, vp, st

        def mixedfill_step(params, kp, vp, m_tokens, m_positions, m_final,
                           m_last, m_bt, m_lengths, m_slots, m_keys,
                           m_steps, m_temps, m_topks, m_topps, m_limits,
                           m_mins, m_stopids, *rest, mode):
            """Piggyback scheduling: ONE fused dispatch runs decode_block
            iterations that each decode the running batch AND prefill one
            token-budgeted segment of a single pending prompt through the
            shared paged-attention path (``model.mixed`` — the same
            write-then-attend chunk trunk verify uses). The decode rows'
            math is exactly ``decode_step``'s, so greedy outputs are
            token-identical to the unfused engine; the prefill rides in
            the MXU bubble the bandwidth-bound decode leaves behind.

            Per-iteration inputs (scanned, leading axis K): segment
            tokens/positions ``[K, C]`` (−1-padded, leading-contiguous),
            ``m_final [K]`` (does this segment reach the prompt's last
            position) and ``m_last [K]`` (its in-segment index). The
            per-row args describe the ONE piggy sequence (shape [1, ...],
            same pack as the chunked-prefill group invariants). When the
            final segment lands at iteration k < K−1, the scatter
            activates the piggy's slot and the REMAINING iterations of
            this very scan decode it alongside the batch — the host
            pre-allocated pages for those in-dispatch positions. An
            all-(−1) segment is a pure decode iteration (re-planned
            page-pressure dispatches use these as middles)."""
            m_history, st = rest if spec else (None, rest[0])
            slot = m_slots[0]

            def body(carry, xs):
                kp, vp, st = carry
                seg_tokens, seg_positions, seg_final, seg_last = xs
                (tokens, ctx, bt, active, keys, steps, temps, topks,
                 topps, limits, mins, stop_ids, *hist) = st
                qtok, qpos, is_chunk = mixed_query_grid(
                    tokens, ctx, active, seg_tokens, seg_positions,
                    slot, max_kv_pos,
                )
                gather = jnp.where(is_chunk, seg_last, 0)
                # The piggy's block table rides in via m_bt: its pages
                # join the decode state only at the final-segment
                # scatter, and shipping it per dispatch also delivers
                # mid-prefill growth without a block-table swap.
                bt_used = bt.at[slot].set(m_bt[0])
                logits, kp, vp = model.mixed(
                    params, qtok, qpos, kp, vp, bt_used, gather
                )
                if guard:
                    # Active decode rows, plus the piggy's slot row on
                    # the iteration whose segment samples its first
                    # token (earlier segments gather pad positions).
                    g_mask = jnp.logical_or(
                        active,
                        (jnp.arange(S) == slot)
                        & seg_final
                        & (m_slots[0] >= 0),
                    )
                    g = guard_stats(logits, g_mask)
                else:
                    g = None
                # Decode tail — identical math to decode_step for the
                # active rows (the chunk row is inactive, emits 0 here).
                d_logits = suppress_stops(logits, stop_ids, steps, mins)
                next_tokens = sample_tokens(
                    d_logits, keys, steps, temps, topks, topps, mode=mode
                )
                out = jnp.where(active, next_tokens, 0)
                st12 = advance_state(st[:12], out, active)
                if spec:
                    # Drafting pauses during mixed dispatches (plain
                    # decode — still lossless); keep the invariant
                    # history[ctx] == current token so the drafter
                    # resumes coherently on the next verify dispatch.
                    st = st12 + (
                        hist[0].at[
                            jnp.arange(S), jnp.where(active, ctx + 1, M)
                        ].set(out, mode="drop"),
                    )
                else:
                    st = st12
                # Piggy activation AFTER the decode advance: the final
                # segment's last position samples the first token and
                # scatters the row into the decode state, so the next
                # iteration of this scan decodes it.
                out1, st = sample_and_scatter(
                    logits[slot][None],
                    seg_final[None] & (m_slots >= 0),
                    m_lengths, m_bt, m_slots, m_keys, m_steps, m_temps,
                    m_topks, m_topps, m_limits, m_mins, m_stopids, st,
                    mode=mode, p_history=m_history,
                )
                emit = jnp.where(
                    (jnp.arange(S) == slot) & seg_final, out1[0], out
                )
                if guard:
                    return (kp, vp, st), (emit, g)
                return (kp, vp, st), emit

            (kp, vp, st), outs = jax.lax.scan(
                body, (kp, vp, st), (m_tokens, m_positions, m_final, m_last)
            )
            return outs, kp, vp, st

        def mixed_iter(params, kp, vp, h, seg_tokens, seg_positions,
                       seg_final, seg_last, m_bt, m_lengths, m_slots,
                       m_keys, m_steps, m_temps, m_topks, m_topps,
                       m_limits, m_mins, m_stopids, st, *, mode):
            """ONE iteration of the mixed scan body, h-threaded — the pp
            head-stage executable (the host drives the K loop because
            every iteration's hidden states cross stage boundaries).
            Math is line-for-line the scan body above minus the guard and
            speculation branches, both of which are gated off under pp."""
            slot = m_slots[0]
            (tokens, ctx, bt, active, keys, steps, temps, topks,
             topps, limits, mins, stop_ids) = st
            qtok, qpos, is_chunk = mixed_query_grid(
                tokens, ctx, active, seg_tokens, seg_positions,
                slot, max_kv_pos,
            )
            gather = jnp.where(is_chunk, seg_last, 0)
            bt_used = bt.at[slot].set(m_bt[0])
            logits, kp, vp = model.mixed(
                params, qtok, qpos, kp, vp, bt_used, gather, h=h
            )
            d_logits = suppress_stops(logits, stop_ids, steps, mins)
            next_tokens = sample_tokens(
                d_logits, keys, steps, temps, topks, topps, mode=mode
            )
            out = jnp.where(active, next_tokens, 0)
            st = advance_state(st, out, active)
            out1, st = sample_and_scatter(
                logits[slot][None],
                seg_final[None] & (m_slots >= 0),
                m_lengths, m_bt, m_slots, m_keys, m_steps, m_temps,
                m_topks, m_topps, m_limits, m_mins, m_stopids, st,
                mode=mode,
            )
            emit = jnp.where(
                (jnp.arange(S) == slot) & seg_final, out1[0], out
            )
            return emit, kp, vp, st

        repl, slot1, slot2 = self._repl, self._slot1, self._slot2
        kv = self._kv_format
        st_sh = (slot1, slot1, slot2, slot1, slot2, slot1, slot1, slot1,
                 slot1, slot1, slot1, slot2)
        if spec:
            st_sh += (slot2,)  # history[S, M]
        self._st_shardings = st_sh
        self._prefill_arg_shardings = (repl,) * (13 if spec else 12)
        self._decode_fn = decode_step
        self._decode_block_fn = decode_block_step
        self._verify_block_fn = verify_block_step
        self._prefill_fn = prefill_step
        self._chunkfill_fn = chunkfill_step
        self._mixedfill_fn = mixedfill_step
        self._mixed_iter_fn = mixed_iter
        if self.pp > 1:
            self._build_pp_jits(
                decode_step=decode_step,
                prefill_step=prefill_step,
                chunkfill_step=chunkfill_step,
                mixed_iter=mixed_iter,
            )
            return
        self._make_jits(self._param_shardings)

    def _make_jits(self, param_spec) -> None:
        """(Re)build the per-mode compiled steps with ``param_spec`` as the
        parameter in_sharding (NamedShardings, or pinned Formats after
        ``_optimize_param_layouts``). One executable per sampler variant
        actually used: a greedy batch must not pay the [S, V] vocab sort
        (sampling.required_mode); jit compiles lazily, so unused variants
        cost nothing. Prefill gets the same per-mode treatment (~19 ms per
        8x256 chunk of filter machinery at a 152k vocab, measured round 3).
        """
        repl, slot1 = self._repl, self._slot1
        kv = self._kv_format
        st_sh = self._st_shardings
        # decode_block > 1 swaps in the fused K-iteration scan: same
        # signature and donation, token output [K, S] instead of [S]
        # (the host normalises both to 2-D when processing). K == 1
        # keeps literally the pre-block executable. Speculation swaps in
        # the fused verify scan, whose token output is the tuple
        # ([K, S, Q] candidates, [K, S] accept counts); with
        # spec_tokens == 0 none of this branch exists and the decode
        # executable is bit-for-bit the non-speculative one.
        if self.cfg.spec_tokens > 0:
            fn, out0 = self._verify_block_fn, (self._spec_out, self._block1)
        elif self.cfg.decode_block > 1:
            fn, out0 = self._decode_block_fn, self._block1
        else:
            fn, out0 = self._decode_fn, slot1
        # Logit guard on: every step's token output pairs with the tiny
        # (stats, bad-rows) guard fold — replicated, it rides the same
        # async fetch as the tokens. Off: the out specs (and programs)
        # are untouched.
        g_on = self.logit_guard == "on"
        guard_sh = (repl, repl)
        if self._hybrid:
            out0 = (out0, repl)  # tokens, and the expert layers' counters
        if g_on:
            out0 = (out0, guard_sh)
        p_out = (repl, guard_sh) if g_on else repl
        self._decode_jits = {
            mode: _StepProgram(
                partial(fn, mode=mode),
                in_shardings=(param_spec, kv, kv, st_sh),
                out_shardings=(out0, kv, kv, st_sh),
                donate_argnums=(1, 2, 3),
            )
            for mode in ("greedy", "stochastic", "filtered")
        }
        # Prefill data args grow by one (the per-row history) under
        # speculation; the trailing decode-state arg shifts with them.
        nP = len(self._prefill_arg_shardings)  # 13 if spec else 12
        self._prefill_jits = {
            mode: _StepProgram(
                partial(self._prefill_fn, mode=mode),
                in_shardings=(param_spec, kv, kv) + (repl,) * nP + (st_sh,),
                out_shardings=(p_out, kv, kv, st_sh),
                donate_argnums=(1, 2, 3 + nP),
            )
            for mode in ("greedy", "stochastic", "filtered")
        }
        nC = nP + 3  # chunk args: 5 per-chunk + (10|11) group-invariant
        self._chunkfill_jits = {
            mode: _StepProgram(
                partial(self._chunkfill_fn, mode=mode),
                in_shardings=(param_spec, kv, kv) + (repl,) * nC + (st_sh,),
                out_shardings=(p_out, kv, kv, st_sh),
                donate_argnums=(1, 2, 3 + nC),
            )
            for mode in ("greedy", "stochastic", "filtered")
        }
        # Snapshot plane: whole-page KV scatter for insert_request /
        # swap-to-host restore. Same donation-and-format discipline as the
        # decode steps — the pool buffer is reused in place and the
        # result keeps the pool's pinned layout+sharding, so restores
        # compose with run-ahead dispatch. Retraces per distinct page
        # count; restores are rare (preemption under pressure, handoff),
        # so the retrace cost is noise.
        self._kv_insert_jit = _StepProgram(
            _dispatch.insert_kv_pages,
            in_shardings=(kv, repl, repl),
            out_shardings=kv,
            donate_argnums=(0,),
        )
        # Piggyback scheduling: built only when resolved on — an "off"
        # engine carries literally the pre-existing executables. Token
        # output is a [K, S] block like fused decode.
        if self.mixed_step == "on":
            nM = nP + 3  # 4 per-iteration [K, ...] + (11|12) piggy-row args
            self._mixedfill_jits = {
                mode: _StepProgram(
                    partial(self._mixedfill_fn, mode=mode),
                    in_shardings=(param_spec, kv, kv)
                    + (repl,) * nM
                    + (st_sh,),
                    out_shardings=(
                        (self._block1, guard_sh) if g_on else self._block1,
                        kv,
                        kv,
                        st_sh,
                    ),
                    donate_argnums=(1, 2, 3 + nM),
                )
                for mode in ("greedy", "stochastic", "filtered")
            }

    def _build_pp_jits(
        self, *, decode_step, prefill_step, chunkfill_step, mixed_iter
    ) -> None:
        """Stage-partitioned executables + the host drivers that chain
        them (pp > 1). Each NON-HEAD stage compiles one executable per
        dispatch kind over its own 3-axis submesh — (stage params, stage
        KV pools, data args[, upstream hidden]) → (hidden grid, pools) —
        and the HEAD stage compiles the existing per-mode step closures
        with the upstream hidden threaded in, so sampling, decode-state
        advance and donation are bit-for-bit the pp=1 programs. The
        drivers installed into ``_decode_jits``/``_prefill_jits``/
        ``_chunkfill_jits``/``_mixedfill_jits`` keep the pp=1 call
        signatures (kp/vp become per-stage lists), which leaves every
        dispatch site untouched.

        GPipe microbatching falls out of the call structure: prefill
        chunks are the microbatches (the chunk loop keeps stage s busy on
        chunk i+1 while stage s+1 runs chunk i, because every jit call
        here is an async dispatch), and decode amortizes fill/drain over
        ``decode_block`` iterations per dispatch × ``runahead`` dispatches
        in flight."""
        pp = self.pp
        repl = self._repl
        st_sh = self._st_shardings
        stage_params = self._param_shardings["stages"]
        self._stage_repl = [
            NamedSharding(m, P()) for m in self._stage_meshes
        ]
        max_kv_pos = self._pages_per_seq * self.cfg.page_size

        # --- per-stage (non-head) executables --------------------------
        def stage_jit(fn, s, n_data, with_h):
            kv_s = self._kv_formats[s]
            repl_s = self._stage_repl[s]
            n = n_data + (1 if with_h else 0)
            return _StepProgram(
                fn,
                in_shardings=(stage_params[s], kv_s, kv_s)
                + (repl_s,) * n,
                out_shardings=(repl_s, kv_s, kv_s),
                donate_argnums=(1, 2),
            )

        def make_stage_fns(s):
            model_s = self._stage_models[s]
            first = s == 0

            if first:
                def dec(params, kp, vp, tokens, ctx, bt, active):
                    return model_s.decode(
                        params, tokens, ctx, kp, vp, bt, active,
                        return_hidden=True,
                    )

                def pre(params, kp, vp, tokens, lengths, bt):
                    return model_s.prefill(
                        params, tokens, lengths, kp, vp, bt,
                        return_hidden=True,
                    )

                def chk(params, kp, vp, tokens, positions, bt):
                    return model_s._paged_chunk_trunk(
                        params, tokens, positions, kp, vp, bt
                    )

                def mix(params, kp, vp, tokens, ctx, active, bt,
                        seg_tokens, seg_positions, seg_last, m_bt, m_slots):
                    slot = m_slots[0]
                    qtok, qpos, is_chunk = mixed_query_grid(
                        tokens, ctx, active, seg_tokens, seg_positions,
                        slot, max_kv_pos,
                    )
                    gather = jnp.where(is_chunk, seg_last, 0)
                    bt_used = bt.at[slot].set(m_bt[0])
                    return model_s.mixed(
                        params, qtok, qpos, kp, vp, bt_used, gather,
                        return_hidden=True,
                    )
            else:
                def dec(params, kp, vp, tokens, ctx, bt, active, h):
                    return model_s.decode(
                        params, tokens, ctx, kp, vp, bt, active, h=h,
                        return_hidden=True,
                    )

                def pre(params, kp, vp, tokens, lengths, bt, h):
                    return model_s.prefill(
                        params, tokens, lengths, kp, vp, bt, h=h,
                        return_hidden=True,
                    )

                def chk(params, kp, vp, tokens, positions, bt, h):
                    return model_s._paged_chunk_trunk(
                        params, tokens, positions, kp, vp, bt, h=h
                    )

                def mix(params, kp, vp, tokens, ctx, active, bt,
                        seg_tokens, seg_positions, seg_last, m_bt,
                        m_slots, h):
                    slot = m_slots[0]
                    qtok, qpos, is_chunk = mixed_query_grid(
                        tokens, ctx, active, seg_tokens, seg_positions,
                        slot, max_kv_pos,
                    )
                    gather = jnp.where(is_chunk, seg_last, 0)
                    bt_used = bt.at[slot].set(m_bt[0])
                    return model_s.mixed(
                        params, qtok, qpos, kp, vp, bt_used, gather, h=h,
                        return_hidden=True,
                    )
            return dec, pre, chk, mix

        self._pp_decode_stage = []
        self._pp_prefill_stage = []
        self._pp_chunk_stage = []
        self._pp_mixed_stage = []
        for s in range(pp - 1):
            dec, pre, chk, mix = make_stage_fns(s)
            with_h = s > 0
            self._pp_decode_stage.append(stage_jit(dec, s, 4, with_h))
            self._pp_prefill_stage.append(stage_jit(pre, s, 3, with_h))
            self._pp_chunk_stage.append(stage_jit(chk, s, 3, with_h))
            self._pp_mixed_stage.append(stage_jit(mix, s, 9, with_h))

        # --- head-stage executables (per sampler mode) -----------------
        head_sh = stage_params[-1]
        kv = self._kv_format  # head stage pool format

        def head_decode(params, kp, vp, h, st, *, mode):
            return decode_step(params, kp, vp, st, mode=mode, h=h)

        def head_prefill(params, kp, vp, h, *rest, mode):
            *data, st = rest
            return prefill_step(params, kp, vp, *data, st, mode=mode, h=h)

        def head_chunkfill(params, kp, vp, h, *rest, mode):
            *data, st = rest
            return chunkfill_step(
                params, kp, vp, *data, st, mode=mode, h=h
            )

        modes = ("greedy", "stochastic", "filtered")
        self._pp_decode_head = {
            mode: _StepProgram(
                partial(head_decode, mode=mode),
                in_shardings=(head_sh, kv, kv, repl, st_sh),
                out_shardings=(self._slot1, kv, kv, st_sh),
                donate_argnums=(1, 2, 4),
            )
            for mode in modes
        }
        nP = len(self._prefill_arg_shardings)  # 12 (spec gated off)
        self._pp_prefill_head = {
            mode: _StepProgram(
                partial(head_prefill, mode=mode),
                in_shardings=(head_sh, kv, kv, repl)
                + (repl,) * nP
                + (st_sh,),
                out_shardings=(repl, kv, kv, st_sh),
                donate_argnums=(1, 2, 4 + nP),
            )
            for mode in modes
        }
        nC = nP + 3
        self._pp_chunkfill_head = {
            mode: _StepProgram(
                partial(head_chunkfill, mode=mode),
                in_shardings=(head_sh, kv, kv, repl)
                + (repl,) * nC
                + (st_sh,),
                out_shardings=(repl, kv, kv, st_sh),
                donate_argnums=(1, 2, 4 + nC),
            )
            for mode in modes
        }
        nM = nP + 3  # 4 per-iteration seg args + m_bt + 10 piggy-row args
        self._pp_mixed_head = {
            mode: _StepProgram(
                partial(mixed_iter, mode=mode),
                in_shardings=(head_sh, kv, kv, repl)
                + (repl,) * nM
                + (st_sh,),
                out_shardings=(self._slot1, kv, kv, st_sh),
                donate_argnums=(1, 2, 4 + nM),
            )
            for mode in modes
        }
        # Per-stage KV whole-page scatter (restore/prefix-ingest path).
        self._kv_insert_jits = [
            _StepProgram(
                _dispatch.insert_kv_pages,
                in_shardings=(
                    self._kv_formats[s],
                    self._stage_repl[s],
                    self._stage_repl[s],
                ),
                out_shardings=self._kv_formats[s],
                donate_argnums=(0,),
            )
            for s in range(pp)
        ]

        # --- host drivers (installed under the pp=1 jit-dict names) ----
        K = self.cfg.decode_block

        def decode_driver(params, kps, vps, st, *, mode):
            outs = []
            for _ in range(K):
                h = None
                for s in range(pp - 1):
                    t_s, c_s, b_s, a_s = self._ship(
                        (st[0], st[1], st[2], st[3]), s
                    )
                    if s == 0:
                        h, kps[0], vps[0] = self._pp_decode_stage[0](
                            params["stages"][0], kps[0], vps[0],
                            t_s, c_s, b_s, a_s,
                        )
                    else:
                        h, kps[s], vps[s] = self._pp_decode_stage[s](
                            params["stages"][s], kps[s], vps[s],
                            t_s, c_s, b_s, a_s, self._ship_h(h, s),
                        )
                out, kps[-1], vps[-1], st = self._pp_decode_head[mode](
                    params["stages"][-1], kps[-1], vps[-1],
                    self._ship_h(h, pp - 1), st,
                )
                outs.append(out)
            block = outs[0] if K == 1 else jnp.stack(outs)
            return block, kps, vps, st

        def prefill_driver(params, kps, vps, *rest, mode):
            *data, st = rest
            p_tokens, p_lengths, p_bt = data[0], data[1], data[2]
            h = None
            for s in range(pp - 1):
                t_s, l_s, b_s = self._ship((p_tokens, p_lengths, p_bt), s)
                if s == 0:
                    h, kps[0], vps[0] = self._pp_prefill_stage[0](
                        params["stages"][0], kps[0], vps[0], t_s, l_s, b_s
                    )
                else:
                    h, kps[s], vps[s] = self._pp_prefill_stage[s](
                        params["stages"][s], kps[s], vps[s],
                        t_s, l_s, b_s, self._ship_h(h, s),
                    )
            out, kps[-1], vps[-1], st = self._pp_prefill_head[mode](
                params["stages"][-1], kps[-1], vps[-1],
                self._ship_h(h, pp - 1), *data, st,
            )
            return out, kps, vps, st

        def chunkfill_driver(params, kps, vps, *rest, mode):
            *data, st = rest
            c_tokens, c_positions, c_bt = data[0], data[1], data[2]
            h = None
            for s in range(pp - 1):
                t_s, p_s, b_s = self._ship((c_tokens, c_positions, c_bt), s)
                if s == 0:
                    h, kps[0], vps[0] = self._pp_chunk_stage[0](
                        params["stages"][0], kps[0], vps[0], t_s, p_s, b_s
                    )
                else:
                    h, kps[s], vps[s] = self._pp_chunk_stage[s](
                        params["stages"][s], kps[s], vps[s],
                        t_s, p_s, b_s, self._ship_h(h, s),
                    )
            out, kps[-1], vps[-1], st = self._pp_chunkfill_head[mode](
                params["stages"][-1], kps[-1], vps[-1],
                self._ship_h(h, pp - 1), *data, st,
            )
            return out, kps, vps, st

        def mixedfill_driver(params, kps, vps, m_tokens, m_positions,
                             m_final, m_last, m_bt, *rest, mode):
            *inv, st = rest  # m_lengths, m_slots, ... m_stopids (10)
            m_slots = inv[1]
            outs = []
            for k in range(m_tokens.shape[0]):
                seg_t = m_tokens[k]
                seg_p = m_positions[k]
                seg_f = m_final[k]
                seg_l = m_last[k]
                h = None
                for s in range(pp - 1):
                    args_s = self._ship(
                        (st[0], st[1], st[3], st[2],
                         seg_t, seg_p, seg_l, m_bt, m_slots),
                        s,
                    )
                    tok_s, ctx_s, act_s, bt_s = args_s[:4]
                    sT, sP, sL, mb_s, ms_s = args_s[4:]
                    if s == 0:
                        h, kps[0], vps[0] = self._pp_mixed_stage[0](
                            params["stages"][0], kps[0], vps[0],
                            tok_s, ctx_s, act_s, bt_s,
                            sT, sP, sL, mb_s, ms_s,
                        )
                    else:
                        h, kps[s], vps[s] = self._pp_mixed_stage[s](
                            params["stages"][s], kps[s], vps[s],
                            tok_s, ctx_s, act_s, bt_s,
                            sT, sP, sL, mb_s, ms_s, self._ship_h(h, s),
                        )
                out, kps[-1], vps[-1], st = self._pp_mixed_head[mode](
                    params["stages"][-1], kps[-1], vps[-1],
                    self._ship_h(h, pp - 1),
                    seg_t, seg_p, seg_f, seg_l, m_bt, *inv, st,
                )
                outs.append(out)
            return jnp.stack(outs), kps, vps, st

        self._decode_jits = {
            mode: partial(decode_driver, mode=mode) for mode in modes
        }
        self._prefill_jits = {
            mode: partial(prefill_driver, mode=mode) for mode in modes
        }
        self._chunkfill_jits = {
            mode: partial(chunkfill_driver, mode=mode) for mode in modes
        }
        if self.mixed_step == "on":
            self._mixedfill_jits = {
                mode: partial(mixedfill_driver, mode=mode)
                for mode in modes
            }

    def _ship(self, arrays: tuple, s: int) -> tuple:
        """Copy per-dispatch data args onto stage ``s``'s submesh
        (replicated). Small control tensors — tokens, positions, block
        tables — not the activation payload; those go via _ship_h."""
        repl_s = self._stage_repl[s]
        return tuple(jax.device_put(a, repl_s) for a in arrays)

    def _ship_h(self, h, s: int):
        """Move a hidden-state grid across the stage boundary onto stage
        ``s``'s submesh. This is THE pipeline wire: device-to-device
        inside one process; with LLMQ_PP_WIRE=1 the grid round-trips
        through the snapshot wire codec first (serialize → frame →
        digest-check → decode), the in-process stand-in for the tcp://
        hop between stage hosts. Boundary accounting feeds the bench pp
        rung's bytes/token metric."""
        self.pp_boundary_transfers += 1
        self.pp_boundary_bytes += int(h.size) * int(h.dtype.itemsize)
        if self.pp_wire:
            # Runs inside the caller's dispatch watchdog bracket
            # (_wd("prefill"/"decode_block"/"mixed")), which times the
            # whole stage loop including this fetch.
            h = snapshot_mod.tensor_from_wire(  # llmq: ignore[unguarded-device-fetch]
                snapshot_mod.tensor_to_wire(np.asarray(h))
            )
        return jax.device_put(h, self._stage_repl[s])

    def _kv_gather_np(self, pages) -> Tuple[np.ndarray, np.ndarray]:
        """Gather pool pages to host as FULL-layer-stack (k, v) blobs.
        ``pages`` stays a host/numpy index so each eager gather follows
        its own pool's devices; under pp the per-stage layer slabs
        concatenate back to [L, n, page, H, D], so snapshots, swap blobs
        and prefix chunks are byte-identical to pp=1 (the wire format is
        pipeline-degree-agnostic). np.asarray blocks until each gather
        lands, so the host buffers are safe against later donation."""
        idx = np.asarray(pages, np.int32)  # llmq: ignore[unguarded-device-fetch]
        # Every call site holds _wd("snapshot_gather"), so these blocking
        # fetches are already inside a watchdog bracket.
        if self.pp == 1:
            k = np.asarray(_dispatch.gather_kv_pages(self.k_pages, idx))  # llmq: ignore[unguarded-device-fetch]
            v = np.asarray(_dispatch.gather_kv_pages(self.v_pages, idx))  # llmq: ignore[unguarded-device-fetch]
            return k, v
        ks = [
            np.asarray(_dispatch.gather_kv_pages(kp, idx))  # llmq: ignore[unguarded-device-fetch]
            for kp in self.k_pages
        ]
        vs = [
            np.asarray(_dispatch.gather_kv_pages(vp, idx))  # llmq: ignore[unguarded-device-fetch]
            for vp in self.v_pages
        ]
        return np.concatenate(ks, axis=0), np.concatenate(vs, axis=0)

    def _kv_insert_np(self, pages, k: np.ndarray, v: np.ndarray) -> None:
        """Scatter full-layer-stack host KV back into the pool(s),
        rebinding ``self.k_pages``/``self.v_pages`` to the donated
        results. Under pp the [L, ...] blob splits into per-stage slabs
        along the layer axis (the inverse of ``_kv_gather_np``)."""
        idx = np.asarray(pages, np.int32)  # llmq: ignore[unguarded-device-fetch]
        if self.pp == 1:
            self.k_pages = self._kv_insert_jit(
                self.k_pages, idx, np.ascontiguousarray(k)
            )
            self.v_pages = self._kv_insert_jit(
                self.v_pages, idx, np.ascontiguousarray(v)
            )
            return
        for s, (lo, hi) in enumerate(self._stage_ranges):
            self.k_pages[s] = self._kv_insert_jits[s](
                self.k_pages[s], idx, np.ascontiguousarray(k[lo:hi])
            )
            self.v_pages[s] = self._kv_insert_jits[s](
                self.v_pages[s], idx, np.ascontiguousarray(v[lo:hi])
            )

    def _optimize_param_layouts(self) -> None:
        """Pin parameters to the decode executable's PREFERRED layouts
        (LLMQ_PARAM_AUTO_LAYOUT=1). With default row-major inputs XLA
        re-layouts some stacked weights around every layer-scan slice
        (o/k/v_proj transpose copies, ~1.1 ms/step at 3B/192 slots —
        measured round 4); compiling once with AUTO input layouts and
        re-putting the params in whatever XLA chose removes those copies
        for every subsequent step. Costs one extra compile at startup."""
        if self.pp > 1:
            # The probe lowers the single-executable decode step; under
            # pp there is no such executable (per-stage programs + host
            # driver), so keep the default layouts.
            logger.info("param auto-layout skipped: pp > 1 engine")
            return
        auto_ps = jax.tree.map(
            lambda sh: Format(Layout.AUTO, sh), self._param_shardings
        )
        kv = self._kv_format
        # Probe the executable production actually dispatches: with
        # decode blocks (or speculative verify) the scan body's preferred
        # layouts are what the params should be pinned to.
        if self.cfg.spec_tokens > 0:
            fn, out0 = self._verify_block_fn, (self._spec_out, self._block1)
        elif self.cfg.decode_block > 1:
            fn, out0 = self._decode_block_fn, self._block1
        else:
            fn, out0 = self._decode_fn, self._slot1
        if self.logit_guard == "on":
            out0 = (out0, (self._repl, self._repl))
        probe = jax.jit(
            partial(fn, mode="greedy"),
            in_shardings=(auto_ps, kv, kv, self._st_shardings),
            out_shardings=(out0, kv, kv, self._st_shardings),
            donate_argnums=(1, 2, 3),
        )
        # Runs after _resync, so the state spec comes straight from the
        # live device state — no hand-maintained shape list to drift.
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
        try:
            compiled = probe.lower(
                jax.tree.map(sds, self.params),
                sds(self.k_pages),
                sds(self.v_pages),
                jax.tree.map(sds, self._dev_state),
            ).compile()
            formats = compiled.input_formats[0][0]
        except Exception:  # noqa: BLE001 — backend without layout support
            logger.exception("param auto-layout probe failed; keeping defaults")
            return

        def reput(leaf, fmt):
            # Leaf-by-leaf with immediate delete: a whole-tree device_put
            # would briefly hold TWO full parameter copies in HBM, which
            # the auto-sized KV pool has not left room for. The in-flight
            # copy holds its own buffer reference, so delete() is safe —
            # but device_put returns the SAME array when the layout
            # already matches, and that one must survive.
            new = jax.device_put(leaf, fmt)
            if new is not leaf:
                leaf.delete()
            return new

        self.params = jax.tree.map(reput, self.params, formats)
        self._make_jits(formats)

    def _auto_num_pages(self) -> int:
        """Size the KV pool from device HBM (vLLM gpu_memory_utilization
        parity, ``vllm_worker.py:107``). A CPU run (tests) has no HBM to
        read and gets a fixed small pool; a TPU that reports no
        ``bytes_limit`` is an error — a guessed pool there would hide
        the device."""
        max_useful = self.cache.max_useful_pages
        if not on_tpu():
            return min(max_useful, 4096)
        device = self.mesh.devices.flat[0]
        stats = device.memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            raise RuntimeError(
                f"{device} reports no bytes_limit (memory_stats={stats!r}): "
                "cannot size the KV pool; pass num_pages explicitly"
            )
        budget = int(limit * self.cfg.hbm_utilization) - stats.get(
            "bytes_in_use", 0
        )
        # A state pool comes out of the same budget, before pages.
        budget -= self.cache.fixed_bytes
        page_bytes = self.cache.page_bytes(self._kv_format)
        return int(min(max(2, budget // page_bytes), max_useful))

    # --- request intake ---------------------------------------------------
    def add_request(
        self,
        rid: str,
        *,
        prompt: Optional[str] = None,
        messages: Optional[List[Dict[str, str]]] = None,
        prompt_ids: Optional[List[int]] = None,
        params: Optional[SamplingParams] = None,
        deadline_at: Optional[float] = None,
        prefill_only: bool = False,
        priority: str = "batch",
    ) -> Sequence:
        if prompt_ids is None:
            if messages is not None:
                prompt_ids = self.tokenizer.apply_chat_template(messages)
            elif prompt is not None:
                prompt_ids = self.tokenizer.encode(prompt)
            else:
                raise ValueError("request needs prompt, messages, or prompt_ids")
        if not prompt_ids:
            prompt_ids = [0]
        # Own copy: the scheduler caps max_tokens in place and a caller may
        # share one SamplingParams across requests.
        params = dataclasses.replace(params) if params else SamplingParams()
        need = len(
            set(params.stop_token_ids)
            | (set() if params.ignore_eos else self._eos_ids)
        )
        if need > self._stop_capacity:
            self._grow_stop_capacity(need)
        if priority not in ("interactive", "batch"):
            raise ValueError(
                f"priority={priority!r} (want interactive|batch)"
            )
        if prefill_only and self._hybrid:
            raise NotImplementedError(
                self.cache.refusal("prefill_only (the prefill role)")
            )
        if not self.priority_classes:
            priority = "batch"  # classes disabled: everything is FIFO batch
        seq = Sequence(
            rid=rid,
            prompt_ids=list(prompt_ids),
            params=params,
            deadline_at=deadline_at,
            prefill_only=prefill_only,
            priority=priority,
        )
        if deadline_at is not None:
            self._deadlines_enabled = True
        if priority == "interactive" and not self._priority_enabled:
            # Lazily turn on priority-aware admission (like deadlines):
            # a fleet that never submits interactive work keeps the
            # exact pre-priority FIFO order and stats surface.
            self._priority_enabled = True
            self.scheduler.config.priority_aware = True
        self.total_prompt_tokens += len(seq.prompt_ids)
        self.scheduler.add(seq)
        return seq

    @property
    def has_work(self) -> bool:
        return (
            bool(self.scheduler.running)
            or self.scheduler.has_waiting
            or bool(self._pending)
        )

    # --- one engine iteration --------------------------------------------
    def step(self) -> List[RequestOutput]:
        """Admit + prefill new sequences, dispatch one decode step for the
        batch, process lagged results. Returns requests whose finish was
        *observed* this iteration (results lag dispatch by ≤ runahead).

        Admission drains the whole admissible backlog BEFORE the decode
        dispatch: a decode step costs the same at any occupancy (fixed
        shapes), so interleaving chunk/decode/chunk/decode through a
        refill wave runs full-cost steps at partial occupancy — admitting
        24 chunks back-to-back instead of staggered saves ~one step per
        chunk of the wave (~1.7 s over the 3B bench run, measured round 4
        analysis). Trickle arrivals still refill in one chunk, so serving
        latency is unchanged.
        """
        finished: List[RequestOutput] = []
        # The host's turn is the longest that came twice in the record: a
        # stall that comes once (a profile starting, a compile, the machine
        # standing still) is paid when it comes, whatever is queued after.
        self._ahead = runahead_target(
            sorted(self._turn_worst)[2], self._step_s, self.cfg.runahead,
            pp=self.pp, full=any(self._full),
        )
        if self._deadlines_enabled:
            self._expire_deadlines(finished)
        if self._cancel_rids:
            self._sweep_cancels(finished)
        # Sequences decodable BEFORE this wave: only they justify
        # interleaving decode between admission chunks — a cold-start
        # wave decoding its own fresh rows would pay full-cost steps at
        # tiny occupancy, the exact waste batching the wave avoids.
        pre_wave = [s.rid for s in self._decodable_seqs()]
        while self._try_admit(finished):
            if any(rid in self.scheduler.running for rid in pre_wave):
                # Partial refill (e.g. 2 chunks admitted while 176 slots
                # decode): the decoders pay short stalls between chunks
                # instead of one long one.
                self._dispatch_decode(finished)
        if self.scheduler.running:
            self._dispatch_decode(finished)
        elif self._pending:
            self._process_oldest(finished)
        self._flush_deferred()
        return finished

    def _decodable_seqs(self) -> List[Sequence]:
        """Running sequences the decode step actually advances (prefilled;
        mid-prefill rows are in ``running`` but have no decode state)."""
        return [s for s in self.scheduler.running.values() if s.prefilled]

    def _decode_kernel_plan(self) -> str:
        """The decode-attention schedule of this engine's pool, as
        ``ops/dispatch`` names it."""
        plan, shape = self.cache.decode_plan
        return getattr(_dispatch, plan)(
            *shape, mesh=self.mesh, backend=self.model.attn_backend
        )

    def _kda_decode_plan(self) -> Optional[str]:
        """How a decode step updates the KDA state rows, as
        ``ops/dispatch.kda_decode_plan`` names it; None for a model with
        no KDA layer."""
        shape = self.cache.kda_plan_args
        if shape is None:
            return None
        return _dispatch.kda_decode_plan(
            *shape, mesh=self.mesh, backend=self.model.attn_backend
        )

    def _mla_prefill_plan(self) -> Optional[Dict[str, List[int]]]:
        """For every prefill bucket, the form a 1-row program of it takes
        for expanded latent attention, as ``ops/dispatch.mla_prefill_plan``
        names it (the rows are of the embedding's dtype): ``{"flash":
        [buckets], "xla": [buckets]}``; None for a model with no ``mla``
        layer. Read once at build: ``stats()`` is called from heartbeats
        while the benchmark swaps the weights (``params`` is then None)."""
        pattern = self.model_config.layer_pattern or ()
        if not any(attn == "mla" for attn, _ in pattern):
            return None
        from llmq_tpu.models import quant

        plans: Dict[str, List[int]] = {"flash": [], "xla": []}
        embed = self.params["embed"]
        dtype = (embed["scale"] if quant.is_quantized(embed) else embed).dtype
        for bucket in self._buckets:
            plans[self.model.mla_prefill_plan(bucket, dtype)].append(bucket)
        return plans

    def _expire_deadlines(self, finished: List[RequestOutput]) -> None:
        """Between-steps deadline sweep: waiting or running sequences
        whose wall-clock deadline has passed finish with
        ``deadline_exceeded`` — their slots and pages go to requests that
        can still meet theirs. Running mid-prefill rows are skipped (an
        in-flight chunk loop may still write their pages); they expire on
        the next sweep once prefilled."""
        now = time.time()
        for seq in [
            s
            for s in self.scheduler.waiting
            if s.deadline_at is not None and now > s.deadline_at
        ]:
            self.scheduler.waiting.remove(seq)
            self.scheduler.finish(seq, "deadline_exceeded")
            finished.append(self._output_for(seq))
            self.deadline_expirations += 1
        for seq in [
            s
            for s in self.scheduler.running.values()
            if s.prefilled and s.deadline_at is not None and now > s.deadline_at
        ]:
            self._finish_seq(
                seq, "deadline_exceeded", device_detected=False,
                finished=finished,
            )
            self.deadline_expirations += 1

    def cancel_request(self, rid: str) -> None:
        """Request cancellation of a waiting/running request (client
        disconnected mid-stream). Takes effect at the next step's sweep:
        the sequence finishes with ``finish_reason="cancelled"``, its
        slot and KV pages free through the normal deferred-release path,
        and the caller gets a RequestOutput like any other finish (so
        the job settles instead of redelivering). Safe to call with a
        rid this engine doesn't hold — the entry ages out."""
        self._cancel_rids[rid] = time.monotonic()

    def _sweep_cancels(self, finished: List[RequestOutput]) -> None:
        """Between-steps cancellation sweep, mirroring the deadline
        sweep: waiting sequences unqueue immediately; running prefilled
        sequences finish through ``_finish_seq`` (pages deferred, slot
        deactivated by the dirty resync). Mid-prefill rows are skipped —
        their in-flight chunk loop may still write their pages — and
        cancel on a later sweep once prefilled. Unknown rids age out
        after ``_CANCEL_TTL_S``."""
        now = time.monotonic()
        for seq in [
            s for s in self.scheduler.waiting if s.rid in self._cancel_rids
        ]:
            self.scheduler.waiting.remove(seq)
            self.scheduler.finish(seq, "cancelled")
            finished.append(self._output_for(seq))
            del self._cancel_rids[seq.rid]
            self.cancellations += 1
        for seq in [
            s
            for s in self.scheduler.running.values()
            if s.prefilled and s.rid in self._cancel_rids
        ]:
            self._finish_seq(
                seq, "cancelled", device_detected=False, finished=finished
            )
            del self._cancel_rids[seq.rid]
            self.cancellations += 1
        for rid, t in list(self._cancel_rids.items()):
            if now - t > _CANCEL_TTL_S:
                del self._cancel_rids[rid]

    def _interactive_victim(self) -> Optional[Sequence]:
        """Youngest running prefilled BATCH sequence — the preemption
        victim when interactive work would otherwise queue for a slot.
        Mid-prefill rows are never victims (their in-flight chunk loop
        would keep writing freed pages); interactive rows never evict
        each other (FIFO within the class)."""
        candidates = [
            s
            for s in self.scheduler.running.values()
            if s.prefilled and s.priority != "interactive"
        ]
        if not candidates:
            return None
        return max(candidates, key=lambda s: s.admitted_at)

    def _try_admit(self, finished: List[RequestOutput]) -> bool:
        """Admit + prefill up to one chunk; True if anything was admitted
        (the caller loops until the admissible backlog is drained)."""
        # Keep the pipeline's page-recycling cadence inside the wave:
        # processing entries past the runahead window advances
        # _processed_idx so deferred pages (from sequences that finished
        # just before the wave) return to the allocator BETWEEN chunks —
        # otherwise a tight pool cuts the wave short on OutOfPages that
        # next step's releases would have covered.
        while len(self._pending) > self._ahead:
            self._process_oldest(finished)
        self._flush_deferred()
        free = sum(s is None for s in self.scheduler.slots)
        # SLO preemption: an interactive waiter facing a full slot table
        # evicts the youngest prefilled batch victim (swap-preempt under
        # preempt_mode=swap — its KV gathers to host and scatters back on
        # re-admission) instead of queueing behind it. One victim per
        # admission round; the dirty resync the preemption forces is
        # paid by the prefill that follows anyway.
        int_waiting = self._priority_enabled and any(
            s.priority == "interactive" for s in self.scheduler.waiting
        )
        if int_waiting and free == 0 and self.priority_preempt:
            victim = self._interactive_victim()
            if victim is not None:
                self._self_preempt_deferred(victim)
                self.priority_preemptions += 1
                free = 1
        # The wave that would go: the head and, where more wait than a
        # wave admits, the waiters that share its prefill program
        # (Scheduler.next_wave). A chunk can't exceed the slots; with no
        # slot free nothing goes, and a full worker's every turn is spared
        # the look through its queue.
        wave: List[int] = []
        if free:
            wave = self.scheduler.next_wave(
                self.cfg.max_prefill_batch, self._admit_bucket
            )[: len(self.scheduler.slots)]
        want = len(wave)
        # Batch admission: wait for enough free slots to fill that wave
        # rather than prefilling singletons as slots trickle free —
        # a B=1 chunk costs nearly a full weight pass for 1/B the tokens.
        # Never defer when nothing is running (no progress to wait for),
        # and never keep deferring past admit_max_wait_s. The clock starts
        # when work first *could* be admitted (waiting + a free slot) but
        # was deferred — NOT at enqueue: under a sustained backlog every
        # request is already "old" at head-of-line, which would turn every
        # freed slot into a B=1 prefill and defeat the deferral entirely.
        can_admit = bool(want) and free > 0
        full = free >= (want if self.scheduler.running else 1)
        if not can_admit or full:
            if self.spans.on and self._defer_since is not None:
                self._span_admit_hold(free, expired=False)
            self._defer_since = None
        elif self._defer_since is None:
            self._defer_since = time.monotonic()
        overdue = (
            self._defer_since is not None
            and time.monotonic() - self._defer_since
            > self.cfg.admit_max_wait_s
        )
        # Interactive waiters never sit out the batch-admission deferral:
        # the latency that deferral trades away is exactly their SLO.
        if not (can_admit and (full or overdue or int_waiting)):
            return False
        if self.spans.on:
            if self._defer_since is not None:
                self._span_admit_hold(free, expired=overdue)
            self.spans.begin("admit", free=free)
        self._defer_since = None
        admitted = self.scheduler.admit(
            self.cfg.max_prefill_batch, self._admit_bucket
        )
        # Host-tier promotion runs BEFORE anything else touches the wave:
        # admit() already registered the promoted pages' hashes (so later
        # admits may share them), which is only sound if their KV lands
        # on device before any dispatch could read the pages.
        self._promote_host_pages(admitted)
        todo = []
        restored = []
        for seq in admitted:
            if seq.params.max_tokens <= 0:
                self.scheduler.finish(seq, "length")
                finished.append(self._output_for(seq))
                continue
            if seq.restore is not None:
                restored.append(seq)
            else:
                todo.append(seq)
        # Restores first: they mark the device state dirty, and the
        # prefill below (or the next decode dispatch) resyncs once for
        # the whole admission wave.
        if restored:
            self._restore_batch(restored)
        if todo:
            self._prefill_batch(todo, finished)
        if self.spans.on:
            self.spans.end(
                rows=len(admitted), waiting=len(self.scheduler.waiting),
                reach=max(wave[: len(admitted)], default=-1),
            )
        return bool(admitted)

    def _span_admit_hold(self, free: int, *, expired: bool) -> None:
        """An admission hold ends (the ring is on): it becomes a span from
        the instant work first could have been admitted and was deferred."""
        self.spans.add(
            "admit_hold", int(self._defer_since * 1e9), time.monotonic_ns(),
            free=free, waiting=len(self.scheduler.waiting),
            expired=int(expired),
        )

    # --- run-ahead pipeline ----------------------------------------------
    def _drain(self, finished: List[RequestOutput]) -> None:
        while self._pending:
            self._process_oldest(finished)
        self._flush_deferred()

    def _process_oldest(self, finished: List[RequestOutput]) -> None:
        idx, kind, out, snapshot, g = self._pending.popleft()
        if kind in ("decode", "mixed"):
            self._pending_decodes -= 1
        t_wait = time.monotonic()
        if self.spans.on:
            # ``fetch``: the wait for this dispatch's tokens, caused by
            # the dispatch; once they are on the host it becomes ``emit``:
            # what the host then does with them, caused by the fetch.
            self.spans.begin(
                "fetch", self.spans.cause_of(idx), kind=kind, seq=idx
            )
        if g is not None:
            # Evaluate the guard verdict BEFORE appending any of this
            # dispatch's tokens: a tripped dispatch's outputs are suspect
            # and must not reach user-visible sequences. The raise routes
            # into the numerical-fault recovery (blame attribution).
            self._eval_guard(kind, g, snapshot)
        if kind == "mixed":
            # Mixed dispatch: ([K, S] token block, per-row first-valid
            # iteration). Decode rows start at 0; the piggy row's tokens
            # before its final-segment iteration are padding zeros from
            # its inactive phase and must be skipped, not appended.
            block, starts = out
            with self._wd("mixed"):
                tokens = np.asarray(block)
            self._note_fetch(idx, kind, t_wait)
            if self.spans.on:
                self.spans.then("emit")
            for k in range(tokens.shape[0]):
                for row, seq, epoch in snapshot:
                    if k < starts[row]:
                        continue
                    if (
                        seq.finish_reason is not None
                        or seq.rid not in self.scheduler.running
                        or seq.epoch != epoch
                    ):
                        continue
                    self._append_and_check(seq, int(tokens[k, row]), finished)
            self._processed_idx = idx
            if self.spans.on:
                self.spans.end()
            return
        moe = None
        if self._hybrid and kind == "decode":
            out, moe = out
        if isinstance(out, tuple):
            # Speculative verify block: ([K, S, Q] candidates, [K, S]
            # accept counts). Per row and iteration, the first count
            # tokens are real (count-1 accepted drafts + 1 corrected or
            # bonus token); the rest were rejected on device. K-major so
            # page pressure is handled in device order, and each token
            # re-checks the row guards — a host-detected stop string at
            # candidate i must discard candidates i+1.. of the SAME row.
            with self._wd("verify"):
                emit = np.asarray(out[0])
                counts = np.asarray(out[1])
            self._note_fetch(idx, kind, t_wait)
            if self.spans.on:
                self.spans.then("emit")
            for k in range(emit.shape[0]):
                for row, seq, epoch in snapshot:
                    n = int(counts[k, row])
                    if n <= 0:
                        continue
                    if (
                        seq.finish_reason is not None
                        or seq.rid not in self.scheduler.running
                        or seq.epoch != epoch
                    ):
                        continue
                    self.spec_proposed += self.cfg.spec_tokens
                    self.spec_accepted += n - 1
                    for i in range(n):
                        if (
                            seq.finish_reason is not None
                            or seq.rid not in self.scheduler.running
                            or seq.epoch != epoch
                        ):
                            break
                        self._append_and_check(
                            seq, int(emit[k, row, i]), finished
                        )
            self._processed_idx = idx
            if self.spans.on:
                self.spans.end()
            return
        with self._wd("decode_block" if kind == "decode" else "prefill"):
            tokens = np.asarray(out)  # transfer started at dispatch; ~ready
        if moe is not None:
            # Same dispatch, on its way to the host with the tokens.
            held, hit = np.asarray(moe).reshape(-1, 2).sum(axis=0)  # llmq: ignore[unguarded-device-fetch]
            self.moe_assignments_held += int(held)
            self.moe_experts_hit += int(hit)
        self._note_fetch(idx, kind, t_wait)
        if self.spans.on:
            self.spans.then("emit")
        # Normalise to a [K, rows] block: prefill outputs and K=1 decode
        # steps are 1-D [rows]; fused decode blocks are already [K, S].
        # Iterating k-major reproduces exactly the per-step processing
        # order K=1 had (all rows' token k before any row's token k+1).
        if tokens.ndim == 1:
            tokens = tokens[None]
        for k_tokens in tokens:
            for row, seq, epoch in snapshot:
                if (
                    seq.finish_reason is not None
                    or seq.rid not in self.scheduler.running
                    or seq.epoch != epoch
                ):
                    # Finished, preempted, or preempted-and-readmitted
                    # (epoch mismatch) while this step was in flight —
                    # including rows that finished or self-preempted at
                    # an earlier iteration of this very block: their
                    # remaining in-block tokens are lagged garbage (the
                    # device rode them out inactive) and are discarded.
                    continue
                if (
                    self._closing_window is not None
                    and kind == "decode"
                    and seq.num_tokens % self._closing_window == 0
                ):
                    # The step wrote position num_tokens - 1, its window's
                    # last, and replaced the window's rows by its summaries.
                    self.eva_windows_closed += 1
                self._append_and_check(seq, int(k_tokens[row]), finished)
        self._processed_idx = idx
        if self.spans.on:
            self.spans.end()

    def _note_fetch(self, idx: int, kind: str, t_wait: float) -> None:
        """A dispatch's tokens are on the host: the two clock readings
        behind ``runahead_target``. The host's turn starts here if work is
        still queued on the device (otherwise the device is idle whatever
        the host does next, and the turn is not read), or as much earlier
        as this return came later than one step period after the last: the
        thread waits for the interpreter lock inside the fetch, and while
        another thread holds it (a collection, a long handler of the event
        loop) the device runs on with nobody to feed it. The device's step
        period is the median spacing of two successive decode dispatches'
        fetch returns where the device set the pace of both (the thread
        spent most of each interval blocked in the fetch, not in its own
        work): a median, because a return that the interpreter lock held
        up reads long and the one after it short."""
        now = time.monotonic()
        last_idx, last_at, last_paced = self._fetched
        paced = kind != "prefill" and 2.0 * (now - t_wait) > now - last_at
        late = 0.0
        if paced and last_paced and idx == last_idx + 1:
            gaps = self._step_gaps
            gaps.append(now - last_at)
            self._step_s = sorted(gaps)[len(gaps) // 2]
            late = max(0.0, now - last_at - self._step_s)
        self._fetched = (idx, now, paced)
        self._turn_from = now - late if self._pending else 0.0

    def _eval_guard(
        self,
        kind: str,
        guard: tuple,
        snapshot: List[Tuple[int, Sequence, int]],
    ) -> None:
        """Fetch one dispatch's on-device guard fold and raise a
        classifiable :class:`LogitGuardError` if any check tripped.

        The fetch rides the same async copy as the tokens (started at
        dispatch), so by drain time it is host-resident. Fused blocks
        ship per-iteration folds [K, ...]; they are combined here —
        trivial host arithmetic on a [K, 3] + [K, S] pair."""
        with self._wd("guard"):
            stats = np.asarray(guard[0])
            bad = np.asarray(guard[1])
        if stats.ndim == 2:  # stacked per-scan-iteration folds
            # Host-side combine of the already-fetched [K, 3] fold (the
            # bracket above did the device fetch) — no device value here.
            stats = np.array(  # llmq: ignore[unguarded-device-fetch]
                [stats[:, 0].sum(), stats[:, 1].max(), stats[:, 2].min()]
            )
        if bad.ndim == 2:
            bad = bad.any(axis=0)
        if not bad.any():
            return
        checks = []
        if stats[0] > 0:
            checks.append("nonfinite")
        if self.guard_logit_max > 0 and stats[1] > self.guard_logit_max:
            checks.append("logit_max")
        if (
            self.guard_entropy_min > 0
            and np.isfinite(stats[2])
            and stats[2] < self.guard_entropy_min
        ):
            checks.append("entropy_collapse")
        suspects = tuple(
            seq.rid
            for row, seq, _epoch in snapshot
            if row < bad.shape[0] and bad[row]
        )
        self.guard_trips += 1
        raise LogitGuardError(
            check="+".join(checks) or "guard",
            detail=(
                f"nonfinite={stats[0]:.0f} max|logit|={stats[1]:.4g} "
                f"min_entropy={stats[2]:.4g} rows={int(bad.sum())}"
            ),
            suspects=suspects,
            kind=kind,
        )

    def _flush_deferred(self) -> None:
        # Swap-to-host captures first: a swap entry shares its watermark
        # with the _deferred_pages entry appended by the same preemption,
        # and its pages must be gathered to host BEFORE they return to
        # the allocator (a reallocated page gets overwritten by the next
        # prefill). At the watermark every in-flight write to these pages
        # has executed — _process_oldest blocked on that step's outputs.
        while (
            self._pending_swaps
            and self._pending_swaps[0][0] <= self._processed_idx
        ):
            _, seq, pages, valid, epoch = self._pending_swaps.pop(0)
            self._capture_swap(seq, pages, valid, epoch)
        while (
            self._deferred_pages
            and self._deferred_pages[0][0] <= self._processed_idx
        ):
            _, pages, cacheable = self._deferred_pages.pop(0)
            self.scheduler.release_pages(pages, cacheable)

    def _capture_swap(
        self, seq: Sequence, pages: List[int], valid: int, epoch: int
    ) -> None:
        """Gather a swap-preempted sequence's KV pages to host RAM, so
        re-admission scatters them back instead of re-prefilling. Skipped
        (falling back to recompute, which is always correct) when the
        sequence moved on while the capture waited for its watermark:
        re-admitted, finished/aborted, preempted again, or already
        carrying a restore."""
        if (
            seq.epoch != epoch
            or seq.finish_reason is not None
            or seq.rid in self.scheduler.running
            or seq.restore is not None
        ):
            return
        n = snapshot_mod.pages_for(valid, self.cfg.page_size)
        if n == 0 or n > len(pages):
            return
        if not self._admit_swap_capture(n):
            return  # recompute fallback: re-admission re-prefills
        # The gather helper blocks until the copies land, so the fresh
        # host buffers are safe against the pools' later donation.
        with self._wd("snapshot_gather"):
            k, v = self._kv_gather_np(pages[:n])
        seq.restore = snapshot_mod.KVRestore(k=k, v=v, valid=valid)
        self.swap_preempts += 1

    def _page_host_bytes(self) -> int:
        """Host bytes one swapped KV page costs (K + V)."""
        if self.pp > 1:
            k_bytes = sum(
                int(kp.size) * int(jnp.dtype(kp.dtype).itemsize)
                for kp in self.k_pages
            )
        else:
            k_bytes = int(self.k_pages.size) * int(
                jnp.dtype(self.k_pages.dtype).itemsize
            )
        return 2 * (k_bytes // max(1, self.scheduler.config.num_pages))

    def _admit_swap_capture(self, n_pages: int) -> bool:
        """Ask the host-memory governor before buffering ``n_pages`` of
        swapped KV. A refusal downgrades the preemption to recompute
        (the pre-swap behavior — always correct, slower to resume)."""
        if get_governor().admit_swap(n_pages * self._page_host_bytes()):
            return True
        self.swap_refused += 1
        return False

    def _swap_restore_bytes(self) -> int:
        """Governor gauge: host bytes currently held by swap/restore KV
        blobs awaiting re-admission."""
        total = 0
        for seq in list(self.scheduler.waiting):
            r = seq.restore
            if r is not None:
                total += int(r.k.nbytes) + int(r.v.nbytes)
        return total

    def _evict_prefix_bytes(self, nbytes: int) -> int:
        """Governor evictor: drop cold prefix entries (oldest first)
        until ``nbytes`` are freed or the store is empty."""
        store = self.prefix_store
        if store is None:
            return 0
        freed = 0
        while freed < nbytes and len(store):
            before = store.occupancy_bytes
            store._evict_one()
            freed += before - store.occupancy_bytes
        return freed

    def _on_scheduler_preempt(self, seq: Sequence, deferred: bool) -> None:
        """Scheduler ``on_preempt`` hook. Deferred self-preemptions queue
        their own watermark capture in ``_self_preempt_deferred``; the
        immediate path (scheduler-picked victim under pool exhaustion,
        only reachable with the pipeline drained) gathers the victim's KV
        here, while it still owns its pages."""
        if (
            deferred
            or self.preempt_mode != "swap"
            or not seq.prefilled
            or not seq.pages
            or seq.restore is not None
        ):
            return
        assert not self._pending, "immediate preempt with in-flight steps"
        valid = seq.num_tokens - 1
        n = snapshot_mod.pages_for(valid, self.cfg.page_size)
        if n == 0 or n > len(seq.pages):
            return
        if not self._admit_swap_capture(n):
            return  # recompute fallback: re-admission re-prefills
        with self._wd("snapshot_gather"):
            k, v = self._kv_gather_np(seq.pages[:n])
        seq.restore = snapshot_mod.KVRestore(k=k, v=v, valid=valid)
        self.swap_preempts += 1

    # --- host prefix tier -------------------------------------------------
    def _demote_page(self, page: int, hashes: List[bytes]) -> None:
        """Scheduler ``on_demote`` hook: park an evicted cache page's KV
        in the host tier, keyed by every chain hash that pointed at it.
        Safe to gather here: a cached page is refcount-0 whose deferred
        release passed the watermark, so every in-flight write to it has
        executed, and the gather reads the newest pool reference (the
        donation chain's live buffer). np.asarray blocks until the copy
        lands, before the page can be reallocated and overwritten."""
        if self.prefix_store is None:
            return
        idx = jnp.asarray([page], jnp.int32)
        with self._wd("snapshot_gather"):
            k = np.asarray(_dispatch.gather_kv_pages(self.k_pages, idx))
            v = np.asarray(_dispatch.gather_kv_pages(self.v_pages, idx))
        for h in hashes:
            self.prefix_store.put(h, k, v)
        self.prefix_demotes += 1

    def _host_prefix_lookup(self, hashes: List[bytes]):
        """Scheduler ``host_lookup`` hook: the longest contiguous run of
        host-tier pages extending a device-cache match."""
        return self.prefix_store.match_chain(hashes)

    def _promote_host_pages(self, admitted: List[Sequence]) -> None:
        """Insert host-tier KV into the pages admit() reserved for it,
        before the wave's first dispatch. No ``_dirty`` resync needed:
        the sequences are still unprefilled (prefill's scatter brings
        their decode rows up), and ``_kv_insert_jit`` donates the pool
        like every other KV write. Also emits the per-request
        ``prefix_hit`` trace event covering device + host reuse."""
        for seq in admitted:
            if seq.prefix_len > 0:
                emit_trace_event(seq.rid, "prefix_hit", tokens=seq.prefix_len)
            hr = seq.host_restore
            if not hr:
                continue
            seq.host_restore = None
            # Host list → numpy; no device value involved.
            idx = np.asarray([page for page, _, _ in hr], np.int32)  # llmq: ignore[unguarded-device-fetch]
            k = np.concatenate([e.k for _, _, e in hr], axis=1)
            v = np.concatenate([e.v for _, _, e in hr], axis=1)
            self._kv_insert_np(idx, k, v)
            self.prefix_promotes += len(hr)

    def flush_prefix_to_host(self) -> int:
        """Demote every evictable (refcount-0) cached page to the host
        tier now, instead of waiting for pool pressure. Used before a
        planned teardown — and by the probes to exercise the
        demote→promote path deterministically. Returns the number of
        pages dropped from the device cache."""
        pages = list(self.scheduler.allocator._cached)
        for page in pages:
            self.scheduler.allocator.drop_cached(page)  # fires on_evict
        return len(pages)

    def export_prefix_chunks(self, digests_hex: List[str]) -> List[str]:
        """Serialize requested prefix pages for a peer (base64 chunk wire
        form). Each digest resolves against the host tier first, then the
        device cache (gathering on demand) — misses are skipped, not
        errors: shipping is best-effort and the requester re-prefills
        whatever doesn't arrive."""
        from llmq_tpu.engine import prefix_store as prefix_mod

        out: List[str] = []
        sig = self.cache.snapshot_sig()
        for hx in digests_hex:
            try:
                key = bytes.fromhex(hx)
            except ValueError:
                continue
            k = v = None
            if self.prefix_store is not None and key in self.prefix_store:
                entry = self.prefix_store.get(key)
                k, v = entry.k, entry.v
            else:
                page = self.scheduler._prefix_cache.get(key)
                if page is not None:
                    with self._wd("snapshot_gather"):
                        k, v = self._kv_gather_np([page])
            if k is None:
                continue
            blob = prefix_mod.chunk_to_bytes(
                key, k, v, model_sig=sig, page_size=self.cfg.page_size
            )
            out.append(prefix_mod.chunk_to_b64(blob))
            self.prefix_chunks_exported += 1
        return out

    def ingest_prefix_chunks(self, chunks_b64: List[str]) -> int:
        """Accept shipped prefix pages into the host tier (they promote
        to device on the next matching admission). Returns the number
        accepted; 0 when the host tier is disabled. Malformed or
        incompatible chunks raise — a fleet where shapes disagree should
        fail loudly, not silently recompute forever."""
        if self.prefix_store is None:
            return 0
        from llmq_tpu.engine import prefix_store as prefix_mod

        n = 0
        sig = self.cache.snapshot_sig()
        for c in chunks_b64:
            key, k, v, chunk_sig, page_size = prefix_mod.chunk_from_bytes(
                prefix_mod.chunk_from_b64(c)
            )
            prefix_mod.check_chunk_compat(
                chunk_sig,
                page_size,
                want_sig=sig,
                want_page_size=self.cfg.page_size,
            )
            if self.prefix_store.put(key, k, v):
                n += 1
                self.prefix_chunks_ingested += 1
        return n

    def missing_prefix_digests(self, digests_hex: List[str]) -> List[str]:
        """Subset of the given chain digests resident in NEITHER the
        device prefix cache nor the host tier — the want-list a worker
        sends to an affinity peer before recomputing a prefix. Pure
        dict/host lookups (no device work, no counter churn)."""
        missing: List[str] = []
        for hx in digests_hex:
            try:
                key = bytes.fromhex(hx)
            except ValueError:
                continue
            if key in self.scheduler._prefix_cache:
                continue
            if self.prefix_store is not None and key in self.prefix_store:
                continue
            missing.append(hx)
        return missing

    def hot_prefix_chains(self, n: int = 8) -> List[str]:
        """Hex digests of this engine's hottest prefix chains — host-tier
        entries by hit count, padded with device-cache chain heads. The
        heartbeat advertises these for affinity routing and shipping."""
        out: List[str] = []
        if self.prefix_store is not None:
            out.extend(self.prefix_store.hot_chains(n))
        if len(out) < n:
            for h in self.scheduler._prefix_cache:
                hx = h.hex()
                if hx not in out:
                    out.append(hx)
                if len(out) >= n:
                    break
        return out

    def _split_guard(self, out):
        """Split a jitted step's token output from its guard fold.

        With the guard on every step returns ``(tokens, (stats, bad))``;
        off, the output is the pre-existing structure and the guard slot
        is ``None`` — callers stay shape-agnostic either way."""
        if self.logit_guard == "on":
            return out
        return out, None

    def _push_pending(
        self,
        kind: str,
        out: jax.Array,
        snapshot: List[Tuple[int, Sequence]],
        guard: Optional[tuple] = None,
    ) -> None:
        arrs = list(out) if isinstance(out, tuple) else [out]
        if guard is not None:
            arrs.extend(guard)
        for arr in arrs:
            try:
                arr.copy_to_host_async()
            except Exception:  # noqa: BLE001 — numpy leaves / no support
                pass
        self._dispatch_idx += 1
        if kind in ("decode", "mixed"):
            self._pending_decodes += 1
        # Stamp each row with its sequence's preemption epoch: a row
        # snapshotted before a self-preemption must not be appended after
        # the sequence is re-admitted (its token came from abandoned
        # device state).
        stamped = [(row, seq, seq.epoch) for row, seq in snapshot]
        self._pending.append((self._dispatch_idx, kind, out, stamped, guard))

    def _resync(self) -> None:
        """Rebuild the device decode state from scheduler truth. Only valid
        after a full drain (host state must have caught up)."""
        assert not self._pending, "resync with in-flight steps"
        fills = [
            (self._h_tokens, 0), (self._h_ctx, 0), (self._h_active, False),
            (self._h_bt, 0), (self._h_temp, 0.0), (self._h_topk, 0),
            (self._h_topp, 1.0), (self._h_keys, 0), (self._h_steps, 0),
            (self._h_limits, 0), (self._h_mins, 0), (self._h_stopids, -1),
        ]
        if self._h_history is not None:
            fills.append((self._h_history, 0))
        for arr, fill in fills:
            arr[...] = fill
        modes = []
        for i, seq in enumerate(self.scheduler.slots):
            if seq is None or not seq.prefilled:
                continue  # unprefilled slots join via the prefill scatter
            p = seq.params
            self._h_tokens[i] = seq.last_token
            self._h_ctx[i] = seq.num_tokens - 1
            self._h_bt[i, : len(seq.pages)] = seq.pages
            self._h_active[i] = True
            self._h_temp[i] = p.temperature
            self._h_topk[i] = p.top_k
            self._h_topp[i] = p.top_p
            # µs-scale PRNG-key fetch at admission, not a dispatch wait.
            self._h_keys[i] = np.asarray(make_base_key(p.seed, request_tag(seq.rid)))  # llmq: ignore[unguarded-device-fetch]
            self._h_steps[i] = len(seq.output_ids)
            self._h_limits[i] = p.max_tokens
            self._h_mins[i] = p.min_tokens
            self._h_stopids[i] = self._stop_ids_for(seq)
            if self._h_history is not None:
                ids = seq.prompt_ids + seq.output_ids
                self._h_history[i, : len(ids)] = ids
            modes.append(sampling_mod.required_mode(p))
        self._mode = sampling_mod.join_modes(modes) if modes else "greedy"
        # One batched transfer with the final shardings — no per-array
        # convert programs, no resharding on first dispatch.
        state = (
            self._h_tokens, self._h_ctx, self._h_bt, self._h_active,
            self._h_keys, self._h_steps, self._h_temp, self._h_topk,
            self._h_topp, self._h_limits, self._h_mins, self._h_stopids,
        )
        if self._h_history is not None:
            state += (self._h_history,)
        self._dev_state = jax.device_put(state, self._st_shardings)
        self._dirty = False

    def _grow_stop_capacity(self, need: int) -> None:
        """Widen the per-slot stop-id arrays to the next power of two
        >= ``need``. The device decode-state shape changes, so the state
        is marked dirty (next dispatch drains in-flight steps and resyncs
        at the new shape; jit retraces once). Grow-only — a rare wide
        request costs one recompile, never a truncated stop set. The live
        capacity is engine state (``_stop_capacity``), not a mutation of
        the caller's EngineConfig (which may be shared across cores)."""
        E = 1 << max(need - 1, 1).bit_length()
        self._stop_capacity = E
        S = self.cfg.max_num_seqs
        self._h_stopids = np.full((S, E), -1, np.int32)
        self._dirty = True

    def _stop_ids_for(self, seq: Sequence) -> np.ndarray:
        """Per-slot device stop-token ids ([-1]-padded). Capacity has
        already been grown by ``add_request``, so the set always fits."""
        E = self._stop_capacity
        ids = list(dict.fromkeys(seq.params.stop_token_ids))
        if not seq.params.ignore_eos:
            ids.extend(i for i in self._eos_ids if i not in ids)
        assert len(ids) <= E, f"stop set {len(ids)} > capacity {E}"
        row = np.full((E,), -1, np.int32)
        row[: len(ids)] = ids
        return row

    # --- prefill ----------------------------------------------------------
    def _prefill_batch(
        self, seqs: List[Sequence], finished: List[RequestOutput]
    ) -> None:
        """Prefill admitted sequences in bucket-grouped batches; the
        compiled step scatters each row straight into the device decode
        state, so admission costs no pipeline drain."""
        if self._dirty:
            self._drain(finished)
            self._resync()
        if self.cfg.prefill_chunk_size:
            if self.mixed_step == "on":
                self._prefill_mixed(seqs, finished)
            else:
                self._prefill_chunked(seqs, finished)
            return
        by_bucket: Dict[int, List[Sequence]] = {}
        for seq in seqs:
            by_bucket.setdefault(self._bucket_of(seq), []).append(seq)
        # Decode interleaving across a multi-chunk wave happens at the
        # step() level (one decode per _try_admit round); per-chunk
        # interleaving inside one call only matters for the chunked path,
        # where a single long prompt spans many dispatches.
        for bucket, group in by_bucket.items():
            for i in range(0, len(group), self.cfg.max_prefill_batch):
                self._prefill_chunk(group[i : i + self.cfg.max_prefill_batch],
                                    bucket)

    def _bucket_of(self, seq: Sequence) -> int:
        """The prefill bucket that holds ``seq``'s prompt and output."""
        return self._buckets[bisect.bisect_left(self._buckets, seq.num_tokens)]

    def _wave_bucket(self, seq: Sequence) -> Optional[int]:
        """``_bucket_of``, or None for a sequence that brings its KV with
        it and is not prefilled."""
        return None if seq.restore is not None else self._bucket_of(seq)

    def _prefill_chunked(
        self, seqs: List[Sequence], finished: List[RequestOutput]
    ) -> None:
        """Chunked prefill: run each admitted group's prompts through the
        single fixed-[B, C] chunk executable, C positions at a time, and
        interleave one decode step for the already-running batch between
        chunks — a long prompt costs the decoders ceil(len/C) short
        stalls instead of one long one."""
        C = self.cfg.prefill_chunk_size
        B = self.cfg.max_prefill_batch
        repl = self._repl
        # Interleave decode only for sequences decodable BEFORE this
        # wave: a cold-start wave interleaving its own fresh rows would
        # pay full-cost decode steps at tiny occupancy — the waste wave
        # admission exists to avoid.
        pre_wave = [s.rid for s in self._decodable_seqs()]
        for i in range(0, len(seqs), B):
            rows = seqs[i : i + B]
            # Snapshot every chunk-invariant per-row value ONCE, and ship
            # the invariant arrays to the device ONCE per group. The live
            # seq.num_tokens/output_ids MUST NOT be re-read inside the lo
            # loop: interleaved decode steps append tokens to rows that
            # went final in an earlier chunk, and a re-read length would
            # mark such a row "final" again — double-scattering it and
            # rewinding its device RNG/step state. (Block tables are the
            # one exception below: pages only grow, and the final-chunk
            # scatter should carry the freshest map.)
            lens = [seq.num_tokens for seq in rows]
            ids0 = [seq.prompt_ids + seq.output_ids for seq in rows]
            # Prefix-cached positions are already in the (shared) leading
            # pages — each row prefills from its own prefix_len on.
            prefix0 = [seq.prefix_len for seq in rows]
            lengths0 = np.zeros((B,), np.int32)
            lengths0[: len(rows)] = lens
            inv_arrays = (lengths0, *self._pack_sampling_rows(rows, B))
            if self.cfg.spec_tokens > 0:
                inv_arrays += (self._pack_history_rows(rows, B),)
            inv = jax.device_put(inv_arrays, (repl,) * len(inv_arrays))
            chunk_mode = sampling_mod.join_modes(
                sampling_mod.required_mode(s.params) for s in rows
            )
            maxlen = max(lens)
            for lo in range(0, maxlen, C):
                tokens = np.zeros((B, C), np.int32)
                positions = np.full((B, C), -1, np.int32)
                bt = np.zeros((B, self._pages_per_seq), np.int32)
                final = np.zeros((B,), bool)
                last = np.zeros((B,), np.int32)
                snapshot: List[Tuple[int, Sequence]] = []
                any_rows = False
                for r, seq in enumerate(rows):
                    n = lens[r]
                    hi = min(n, lo + C)
                    row_start = max(lo, prefix0[r])
                    if (
                        lo >= n
                        or hi <= prefix0[r]  # still inside the cached prefix
                        or seq.rid not in self.scheduler.running
                    ):
                        continue  # nothing to compute — padding row
                    any_rows = True
                    self.prefill_tokens += hi - row_start
                    tokens[r, : hi - row_start] = ids0[r][row_start:hi]
                    positions[r, : hi - row_start] = np.arange(row_start, hi)
                    bt[r, : len(seq.pages)] = seq.pages  # live: grow-only
                    if row_start <= n - 1 < hi:
                        final[r] = True
                        last[r] = n - 1 - row_start
                        snapshot.append((r, seq))
                if not any_rows:
                    continue  # whole chunk inside every row's prefix
                chunk_args = jax.device_put(
                    (tokens, positions, bt, final, last), (repl,) * 5
                )
                if self.spans.on:
                    self.spans.begin(
                        "prefill_dispatch",
                        program=getattr(self._chunkfill_jits[chunk_mode], "name", ""),
                        mode=chunk_mode, variant=f"{B}x{C}", rows=len(rows),
                        rids=[seq.rid for seq in rows],
                        pending=len(self._pending),
                        tokens=int((positions >= 0).sum()), grid=B * C,
                    )
                t0 = time.monotonic()
                for seq in rows:
                    if seq.t_prefill_start == 0.0:
                        seq.t_prefill_start = t0
                with self._wd("prefill"):
                    out, self.k_pages, self.v_pages, self._dev_state = (
                        self._chunkfill_jits[chunk_mode](
                            self.params, self.k_pages, self.v_pages,
                            *chunk_args, *inv, self._dev_state,
                        )
                    )
                    self._record_dispatch("prefill", time.monotonic() - t0)
                self.prefill_grid_tokens += B * C
                out, g = self._split_guard(out)
                if snapshot:  # rows whose prompt finished in this chunk
                    for _, seq in snapshot:
                        seq.prefilled = True
                        self.scheduler.register_prefix(seq)
                    self.prefills += len(snapshot)
                    self._push_pending("prefill", out, snapshot, g)
                    self._mode = sampling_mod.join_modes(
                        (self._mode, chunk_mode)
                    )
                elif g is not None:
                    # No row finished in this chunk, but the guard fold
                    # still needs its drain-time verdict: ride the
                    # pipeline with an empty row snapshot.
                    self._push_pending("prefill", out, [], g)
                if self.spans.on:
                    # A chunk that ends no row and carries no guard pushes
                    # nothing: no fetch will name it.
                    self.spans.end_dispatch(
                        self._dispatch_idx if snapshot or g is not None else 0
                    )
                # Interleave: let pre-wave sequences advance while the
                # next chunk queues behind this one on the device stream
                # (an idle engine's long first prompt must not pay an
                # empty decode step per chunk, and a cold-start wave must
                # not decode its own fresh rows at tiny occupancy).
                if lo + C < maxlen and any(
                    rid in self.scheduler.running for rid in pre_wave
                ):
                    self._dispatch_decode(finished)

    def _prefill_mixed(
        self, seqs: List[Sequence], finished: List[RequestOutput]
    ) -> None:
        """Piggyback scheduling driver: prefill each admitted sequence by
        fusing its chunk segments INTO the decode dispatches instead of
        alternating whole dispatches. Every mixed dispatch advances the
        running batch by ``decode_block`` tokens (exactly like
        ``_dispatch_decode``) while the piggy's prompt trickles in under
        the per-iteration token budget (``mixed_token_budget``): the
        decode batch never stalls for a prefill, and the prefill rides
        compute the decode step was leaving idle. One sequence
        piggybacks at a time; when its final segment lands before the
        last iteration of a dispatch, the remaining iterations decode it
        in-dispatch (pages for those positions are ensured up front —
        under pool pressure the plan falls back to finishing at the last
        iteration, which needs none)."""
        C = self.cfg.prefill_chunk_size
        K = self.cfg.decode_block
        repl = self._repl
        for seq in seqs:
            # The fusion only pays when a decode batch is riding along:
            # with nothing decodable a mixed dispatch is chunked prefill
            # with S-1 wasted rows — use the plain chunk loop.
            if not self._decodable_seqs():
                self._prefill_chunked([seq], finished)
                continue
            epoch0 = seq.epoch
            # Snapshot chunk-invariant values ONCE (the same discipline
            # as _prefill_chunked): mixed dispatches append tokens to
            # OTHER rows, never to the mid-prefill piggy.
            n = seq.num_tokens
            ids0 = seq.prompt_ids + seq.output_ids
            cur = seq.prefix_len  # cached prefix pages already hold KV
            seq_mode = sampling_mod.required_mode(seq.params)
            inv_arrays = (
                # Host int → numpy; no device value involved.
                np.asarray([n], np.int32),  # llmq: ignore[unguarded-device-fetch]
                *self._pack_sampling_rows([seq], 1),
            )
            if self.cfg.spec_tokens > 0:
                inv_arrays += (self._pack_history_rows([seq], 1),)
            inv = jax.device_put(inv_arrays, (repl,) * len(inv_arrays))
            while cur < n:
                if (
                    seq.rid not in self.scheduler.running
                    or seq.epoch != epoch0
                ):
                    break  # preempted mid-prefill; re-admission restarts
                # Plan this dispatch's K segments under the token budget
                # (decode rows first, remainder to the piggy's prompt).
                decode_rows = len(self._decodable_seqs())
                segs: List[Tuple[int, int]] = []
                pos, final_k = cur, None
                for k in range(K):
                    take = mixed_token_budget(C, decode_rows, n - pos)
                    segs.append((pos, take))
                    pos += take
                    if take and pos >= n:
                        final_k = k
                if final_k is not None and final_k < K - 1:
                    # The iterations after activation decode the piggy
                    # in-dispatch, writing positions n..n+K-2-final_k —
                    # their pages must exist BEFORE the dispatch.
                    extra = K - 1 - final_k
                    try:
                        self.scheduler.ensure_pages(
                            seq,
                            self._page_target(seq, extra),
                            allow_preempt=False,
                        )
                    except OutOfPages:
                        self._drain(finished)
                        self._flush_deferred()
                        try:
                            self.scheduler.ensure_pages(
                                seq,
                                self._page_target(seq, extra),
                                allow_preempt=False,
                            )
                        except OutOfPages:
                            # Re-plan: the final segment moves to the
                            # LAST iteration (empty middles become pure
                            # decode iterations) — no in-dispatch piggy
                            # decode, no extra pages.
                            start, take = segs[final_k]
                            for k in range(final_k, K - 1):
                                segs[k] = (start, 0)
                            segs[K - 1] = (start, take)
                            final_k = K - 1
                # Decode rows' own page lookahead + dirty resync — the
                # mixed dispatch IS their decode dispatch.
                if not self._ensure_decode_pages(finished):
                    break  # piggy itself left running (preempt/abort)
                if (
                    seq.rid not in self.scheduler.running
                    or seq.epoch != epoch0
                ):
                    break
                m_tokens = np.zeros((K, C), np.int32)
                m_positions = np.full((K, C), -1, np.int32)
                m_final = np.zeros((K,), bool)
                m_last = np.zeros((K,), np.int32)
                for k, (start, take) in enumerate(segs):
                    if take:
                        m_tokens[k, :take] = ids0[start : start + take]
                        m_positions[k, :take] = np.arange(start, start + take)
                if final_k is not None:
                    m_final[final_k] = True
                    m_last[final_k] = n - 1 - segs[final_k][0]
                m_bt = np.zeros((1, self._pages_per_seq), np.int32)
                m_bt[0, : len(seq.pages)] = seq.pages  # live: grow-only
                seg_args = jax.device_put(
                    (m_tokens, m_positions, m_final, m_last, m_bt),
                    (repl,) * 5,
                )
                # The executable must cover the piggy's sampler needs as
                # well as the batch's (its first token samples here).
                mode = sampling_mod.join_modes((self._mode, seq_mode))
                if self.spans.on:
                    self.spans.begin(
                        "prefill_dispatch",
                        program=getattr(self._mixedfill_jits[mode], "name", ""),
                        mode=mode, variant=f"{K}x{C}", rows=1,
                        rids=[seq.rid], pending=len(self._pending),
                        tokens=sum(t for _, t in segs), grid=K * C,
                    )
                t0 = time.monotonic()
                if seq.t_prefill_start == 0.0:
                    seq.t_prefill_start = t0
                with self._wd("mixed"):
                    out, self.k_pages, self.v_pages, self._dev_state = (
                        self._mixedfill_jits[mode](
                            self.params, self.k_pages, self.v_pages,
                            *seg_args, *inv, self._dev_state,
                        )
                    )
                    self._record_dispatch("mixed", time.monotonic() - t0)
                self.mixed_steps += 1
                self.mixed_prefill_tokens += sum(t for _, t in segs)
                self.prefill_tokens += sum(t for _, t in segs)
                self.prefill_grid_tokens += K * C
                self.decode_steps += K
                self.decode_dispatches += 1
                if final_k is not None:
                    seq.prefilled = True
                    self.scheduler.register_prefix(seq)
                    self.prefills += 1
                    self._mode = mode
                # Snapshot AFTER marking prefilled so the piggy's row is
                # included; its tokens before final_k are skipped via
                # the per-row start index.
                starts = np.zeros((self.cfg.max_num_seqs,), np.int32)
                if final_k is not None:
                    starts[seq.slot] = final_k
                out, g = self._split_guard(out)
                self._push_pending(
                    "mixed",
                    (out, starts),
                    [
                        (i, s)
                        for i, s in enumerate(self.scheduler.slots)
                        if s is not None and s.prefilled
                    ],
                    g,
                )
                if self.spans.on:
                    self.spans.end_dispatch(self._dispatch_idx)
                while len(self._pending) > self._ahead:
                    self._process_oldest(finished)
                cur = pos

    def _pack_sampling_rows(self, rows: List[Sequence], B: int) -> tuple:
        """Per-row device-state arrays shared by both prefill paths
        (bucketed + chunked): slots, RNG keys, step counts, sampling
        params, stop-id rows. Padding rows keep slot −1 / limit 1."""
        E = self._stop_capacity
        key_shape = self._h_keys.shape[1:]
        slots = np.full((B,), -1, np.int32)
        keys = np.zeros((B, *key_shape), np.uint32)
        steps = np.zeros((B,), np.int32)
        temps = np.zeros((B,), np.float32)
        topks = np.zeros((B,), np.int32)
        topps = np.ones((B,), np.float32)
        limits = np.full((B,), 1, np.int32)
        mins = np.zeros((B,), np.int32)
        stopids = np.full((B, E), -1, np.int32)
        for r, seq in enumerate(rows):
            p = seq.params
            slots[r] = seq.slot
            # µs-scale PRNG-key fetch while packing, not a dispatch wait.
            keys[r] = np.asarray(make_base_key(p.seed, request_tag(seq.rid)))  # llmq: ignore[unguarded-device-fetch]
            steps[r] = len(seq.output_ids)
            temps[r] = p.temperature
            topks[r] = p.top_k
            topps[r] = p.top_p
            limits[r] = p.max_tokens
            mins[r] = p.min_tokens
            stopids[r] = self._stop_ids_for(seq)
        return slots, keys, steps, temps, topks, topps, limits, mins, stopids

    def _pack_history_rows(self, rows: List[Sequence], B: int) -> np.ndarray:
        """Per-row prompt+output token history for the speculative
        drafter ([B, max_model_len], zero-padded): the prefill scatter
        installs it as the row's device-side lookup corpus."""
        hist = np.zeros((B, self.cfg.max_model_len), np.int32)
        for r, seq in enumerate(rows):
            ids = seq.prompt_ids + seq.output_ids
            hist[r, : len(ids)] = ids
        return hist

    def _prefill_chunk(self, chunk: List[Sequence], bucket: int) -> None:
        # Pad to {1, max_prefill_batch} rows so at most two executables
        # exist per bucket.
        B = 1 if len(chunk) == 1 else self.cfg.max_prefill_batch
        tokens = np.zeros((B, bucket), np.int32)
        lengths = np.zeros((B,), np.int32)
        bt = np.zeros((B, self._pages_per_seq), np.int32)
        for row, seq in enumerate(chunk):
            ids = seq.prompt_ids + seq.output_ids
            tokens[row, : len(ids)] = ids
            lengths[row] = len(ids)
            bt[row, : len(seq.pages)] = seq.pages
        arg_arrays = (tokens, lengths, bt, *self._pack_sampling_rows(chunk, B))
        if self.cfg.spec_tokens > 0:
            arg_arrays += (self._pack_history_rows(chunk, B),)
        args = jax.device_put(arg_arrays, self._prefill_arg_shardings)
        chunk_mode = sampling_mod.join_modes(
            sampling_mod.required_mode(s.params) for s in chunk
        )
        if self.spans.on:
            self.spans.begin(
                "prefill_dispatch",
                program=getattr(self._prefill_jits[chunk_mode], "name", ""),
                mode=chunk_mode, variant=f"{B}x{bucket}", rows=len(chunk),
                rids=[seq.rid for seq in chunk], pending=len(self._pending),
                tokens=int(lengths.sum()), grid=B * bucket,
            )
        t0 = time.monotonic()
        for seq in chunk:
            if seq.t_prefill_start == 0.0:
                seq.t_prefill_start = t0
        with self._wd("prefill"):
            out, self.k_pages, self.v_pages, self._dev_state = (
                self._prefill_jits[chunk_mode](
                    self.params, self.k_pages, self.v_pages, *args,
                    self._dev_state,
                )
            )
            self._record_dispatch("prefill", time.monotonic() - t0)
        for seq in chunk:
            seq.prefilled = True
            self.prefill_tokens += seq.num_tokens
        self.prefill_grid_tokens += B * bucket
        self.prefills += len(chunk)
        out, g = self._split_guard(out)
        self._push_pending("prefill", out, list(enumerate(chunk)), g)
        if self.spans.on:
            self.spans.end_dispatch(self._dispatch_idx)
        # The new rows' sampler mode must be honored from the next decode.
        self._mode = sampling_mod.join_modes((self._mode, chunk_mode))

    # --- decode -----------------------------------------------------------
    def _ensure_decode_pages(self, finished: List[RequestOutput]) -> bool:
        """Pre-dispatch preamble shared by plain decode and mixed
        (decode + piggybacked prefill) dispatches: page lookahead for
        every decodable row, then the dirty drain + resync. Returns
        False when nothing is left running (caller skips the dispatch).
        """
        # Page lookahead: every position an in-flight or about-to-dispatch
        # step may write must be covered *now* — pages only ever get
        # *added* to a block table, so the grown table can be swapped into
        # the device state without draining the pipeline (in-flight steps
        # only touch already-mapped positions). Demand is capped by each
        # sequence's own remaining generation budget. Only allocator
        # exhaustion (preemption needed) forces a drain + resync.
        # Count only in-flight DECODE entries: a pending prefill writes
        # solely its own new rows, so a wave of refill chunks must not
        # inflate every running sequence's page demand. Mid-prefill
        # sequences are excluded outright: their prompt pages were fully
        # allocated at admission, decode steps never write their rows,
        # and demanding lookahead pages for them here could cascade into
        # preempting/length-finishing a row whose chunk loop is still in
        # flight (zombie-slot corruption).
        # Each in-flight decode entry covers decode_block positions —
        # times spec_tokens+1 when speculating, since every verify
        # iteration writes KV for ALL candidate positions (accepted or
        # not) — and the dispatch below adds another block; +1 slack.
        # (K=1, spec off recovers the historical `pending + 2`.)
        K = self.cfg.decode_block
        lookahead = (
            (self._pending_decodes + 1) * K * (self.cfg.spec_tokens + 1) + 1
        )
        decodable = self._decodable_seqs()
        needs_pages = any(
            self.scheduler.pages_for(seq, self._page_target(seq, lookahead))
            > len(seq.pages)
            for seq in decodable
        )
        if needs_pages:
            grown = False
            for seq in decodable:
                if seq.rid not in self.scheduler.running:
                    continue  # preempted by an earlier iteration's ensure
                try:
                    # Amortize: top up a full page beyond the need — but
                    # never at someone else's expense.
                    before = len(seq.pages)
                    self.scheduler.ensure_pages(
                        seq,
                        self._page_target(
                            seq, lookahead + self.cfg.page_size
                        ),
                        allow_preempt=False,
                    )
                    grown = grown or len(seq.pages) > before
                except OutOfPages:
                    # Pool exhausted: catch the host up so deferred pages
                    # return and preemption can free a victim safely.
                    self._drain(finished)
                    if seq.rid not in self.scheduler.running:
                        continue
                    try:  # minimal demand; preemption allowed (drained) —
                        # but never of a mid-prefill sequence, whose
                        # in-flight chunk loop would keep writing its old
                        # (freed) pages.
                        self.scheduler.ensure_pages(
                            seq,
                            self._page_target(seq, lookahead),
                            preemptible=lambda s: s.prefilled,
                        )
                    except OutOfPages:
                        if len(self.scheduler.running) == 1:
                            # Truly alone and still short: the pool
                            # itself is the cap. Must go through
                            # _finish_seq: pages stay deferred while
                            # in-flight steps may write them, and the
                            # dirty resync deactivates the device slot (a
                            # zombie slot would keep scattering KV through
                            # its stale block table into reallocated
                            # pages).
                            self._finish_seq(seq, "length",
                                             device_detected=False,
                                             finished=finished)
                        else:
                            # Others hold the pool (e.g. only mid-prefill
                            # rows, which are never preemption victims):
                            # self-preempt instead of truncating — the
                            # request retries once pages free (vLLM
                            # recompute-preemption parity), keeping its
                            # generated tokens. Pages defer like a finish
                            # (in-flight steps may still write them) and
                            # the dirty resync deactivates the slot.
                            self._self_preempt_deferred(seq)
                        continue
                    self._dirty = True
            if grown and not self._dirty:
                self._swap_block_tables()
        if self._dirty:
            self._drain(finished)
            if not self.scheduler.running:
                return False
            self._resync()
        return True

    def _dump_scopes(self, dump: Dict[str, Any]) -> None:
        """Add ``scopes`` to the ring's dump: ``{program: {"<mode>/
        <variant>": {instruction: scope}}}``, every compiled variant of
        each step program that a dispatch span still in the ring names:
        what names the events of a device trace, whose runs may have been
        launched before the ring was on (``_StepProgram.scope_map``)."""
        named = {s.get("program") for s in dump["spans"]}
        scopes: Dict[str, Dict[str, Dict[str, str]]] = {}
        for jits in (
            self._decode_jits, self._prefill_jits,
            self._chunkfill_jits, getattr(self, "_mixedfill_jits", None),
        ):
            for mode, prog in (jits or {}).items():
                if not isinstance(prog, _StepProgram) or prog.name not in named:
                    continue
                for variant in list(prog.variants):
                    try:
                        found = prog.scope_map(variant)
                    except Exception:  # noqa: BLE001 — a dump never fails for this
                        logger.exception("scope map of %s %s", prog.name, variant)
                        continue
                    scopes.setdefault(prog.name, {})[f"{mode}/{variant}"] = found
        dump["scopes"] = scopes

    def _record_dispatch(self, kind: str, seconds: float) -> None:
        """Record the host wall-time of one device dispatch call into the
        per-kind histogram. Dispatch is asynchronous, so this measures
        the host-side launch cost, not device execution — spikes mean
        the host blocked on the device (pipeline stalls)."""
        hist = self._dispatch_hists.get(kind)
        if hist is None:
            hist = self._dispatch_hists[kind] = Histogram(
                "llmq_dispatch_seconds",
                "Host wall-time of one device dispatch call",
                labels={"kind": kind},
            )
            get_registry().register(hist)
        hist.observe(seconds)
        if self.on_dispatch is not None:
            self.on_dispatch(kind)

    def _dispatch_decode(self, finished: List[RequestOutput]) -> None:
        if not self._ensure_decode_pages(finished):
            return
        t0 = time.monotonic()
        kind = "verify" if self.cfg.spec_tokens > 0 else "decode_block"
        jit = self._decode_jits[self._mode]
        if self.spans.on:
            lengths = [s.num_tokens for s in self._decodable_seqs()]
            self.spans.begin(
                "decode_dispatch", program=getattr(jit, "name", ""),
                mode=self._mode, variant="",
                rows=len(lengths), k_steps=self.cfg.decode_block,
                pending=len(self._pending), ahead=self._ahead,
                **self.cache.decode_span(lengths, self._decode_kernel_plan),
            )
        with self._wd(kind):
            out, self.k_pages, self.v_pages, self._dev_state = jit(
                self.params, self.k_pages, self.v_pages, self._dev_state
            )
            now = time.monotonic()
            self._record_dispatch(kind, now - t0)
        if self._turn_from:
            # The host's turn ends with the first decode dispatch after a
            # fetch. The record keeps long turns and no mean: a starved
            # device is paid by every running request, a queued prefill by
            # one.
            turn, worst = now - self._turn_from, self._turn_worst
            self._turn_from = 0.0
            if turn > worst[1]:
                worst[:2] = (turn, worst[0]) if turn > worst[0] else (worst[0], turn)
            sched = self.scheduler
            if sched.has_waiting or len(sched.running) >= len(sched.slots):
                self._full[0] = True
            self._turn_n += 1
            if self._turn_n >= _TURN_BUCKET:
                self._turn_n = 0
                worst[:] = 0.0, 0.0, worst[0], worst[1]
                self._full[:] = False, self._full[0]
        self.decode_steps += self.cfg.decode_block
        self.decode_dispatches += 1
        out, g = self._split_guard(out)
        # A layer pattern's ``out`` is (tokens, the expert layers' counters):
        # both ride the pending entry and start for the host together.
        self._push_pending(
            "decode",
            out,
            [
                (i, seq)
                for i, seq in enumerate(self.scheduler.slots)
                if seq is not None and seq.prefilled
            ],
            g,
        )
        if self.spans.on:
            self.spans.end_dispatch(self._dispatch_idx)
        while len(self._pending) > self._ahead:
            self._process_oldest(finished)

    def _self_preempt_deferred(self, seq: Sequence) -> None:
        """Preempt ``seq`` itself with finish-style page deferral: its
        pages return to the allocator only after every in-flight step
        that may write them has been processed. Generated tokens are
        kept; re-admission re-prefills prompt+output. The epoch bump in
        ``Scheduler.preempt`` keeps stale in-flight results (snapshotted
        before the preemption) from being appended after re-admission.

        In swap mode (``LLMQ_PREEMPT_MODE=swap`` / ``preempt_mode``) the
        victim's KV pages are queued for a host gather at the same
        deferred-release watermark, and re-admission scatters them back
        (bit-identical) instead of re-prefilling."""
        swap = (
            self.preempt_mode == "swap" and seq.prefilled and bool(seq.pages)
        )
        pages_copy = list(seq.pages) if swap else None
        kv_valid = seq.num_tokens - 1
        pages, cacheable = self.scheduler.preempt(seq, defer_pages=True)
        if swap:
            # Epoch AFTER the bump: the capture must only fire for this
            # exact preemption, not a later one of the same sequence.
            self._pending_swaps.append(
                (self._dispatch_idx, seq, pages_copy, kv_valid, seq.epoch)
            )
        if pages:
            self._deferred_pages.append(
                (self._dispatch_idx, pages, cacheable)
            )
        self._dirty = True

    def _swap_block_tables(self) -> None:
        """Ship grown block tables into the device state without draining:
        one small h2d transfer, no dispatch, no resync."""
        self._h_bt[...] = 0
        for i, seq in enumerate(self.scheduler.slots):
            if seq is not None:
                self._h_bt[i, : len(seq.pages)] = seq.pages
        bt_dev = jax.device_put(self._h_bt, self._st_shardings[2])
        st = self._dev_state
        self._dev_state = st[:2] + (bt_dev,) + st[3:]

    def _page_target(self, seq: Sequence, lookahead: int) -> int:
        """KV positions ``seq`` must have pages for, given ``lookahead``
        in-flight/future steps — capped by its own finish horizon AND the
        per-sequence page-map capacity (otherwise a full-budget sequence
        would look perpetually short and churn block-table swaps)."""
        horizon = len(seq.prompt_ids) + seq.params.max_tokens + 1
        cap = self._pages_per_seq * self.cfg.page_size
        return min(seq.num_tokens + lookahead, horizon, cap)

    def _append_and_check(
        self, seq: Sequence, token: int, finished: List[RequestOutput]
    ) -> None:
        if seq.prefill_only and not seq.output_ids:
            # Disaggregated prefill boundary: the prompt KV is complete and
            # the device just sampled the first token. Discard the token
            # (the adopting decode worker re-derives the key chain and
            # re-samples it bit-identically), snapshot the prompt KV while
            # the pages are still held, and finish. The snapshot's
            # kv_valid = len(prompt)-1 matches insert_request's contract
            # for an empty-output snapshot, so the decode side recomputes
            # only the last prompt position.
            self._prefill_snapshots[seq.rid] = self._snapshot_seq(seq)
            self.prefill_done += 1
            self._finish_seq(
                seq, "prefill_done", device_detected=False, finished=finished
            )
            return
        seq.output_ids.append(token)
        self.total_generated_tokens += 1
        interactive = seq.priority == "interactive"
        self.class_tokens["interactive" if interactive else "batch"] += 1
        now = time.monotonic()
        if seq.t_first_token == 0.0:
            seq.t_first_token = now
            if seq.t_enqueue > 0.0:
                self.ttft_hist.observe(now - seq.t_enqueue)
                if interactive:
                    self.ttft_hist_interactive.observe(now - seq.t_enqueue)
        elif seq.t_last_token > 0.0:
            # Host-boundary gap: tokens of one fused decode block arrive
            # in a burst, so sub-ms gaps are expected there (the
            # fine-grained ITL_BUCKETS low end exists for exactly this).
            self.itl_hist.observe(now - seq.t_last_token)
            if interactive:
                self.itl_hist_interactive.observe(now - seq.t_last_token)
        seq.t_last_token = now
        # Stops are checked BEFORE the page top-up: a stopping sequence
        # needs no more pages, and the pool-pressure retry below must not
        # swallow a stop/budget finish (a preempted-at-budget row would
        # re-prefill and sample one token past max_tokens).
        n_before = len(seq.output_ids)
        reason = self._stop_reason(seq, token)
        if reason is not None:
            # The token survived the stop check iff it is still in the
            # output (length finishes keep it; stop tokens were popped;
            # stop-string hits pre-truncate text, so nothing streams).
            if (
                self.on_token is not None
                and len(seq.output_ids) == n_before
                and seq.finish_text is None
            ):
                self.on_token(seq, token)
            # The device detects token-based stops and length caps itself
            # (advance_state); only host-exclusive finishes (stop strings)
            # force a resync.
            device_detected = seq.finish_text is None
            self._finish_seq(seq, reason, device_detected=device_detected,
                             finished=finished)
            return
        if self.on_token is not None:
            self.on_token(seq, token)
        try:
            # Pages were pre-allocated at dispatch time; this is a no-op
            # except under pool exhaustion (no preemption here — in-flight
            # steps forbid freeing a victim's pages).
            self.scheduler.ensure_pages(
                seq, seq.num_tokens + 1, allow_preempt=False
            )
        except OutOfPages:
            # Release anything already past the watermark, then retry —
            # an earlier finish/self-preempt in this very drain may have
            # deferred exactly the pages we need.
            self._flush_deferred()
            try:
                self.scheduler.ensure_pages(
                    seq, seq.num_tokens + 1, allow_preempt=False
                )
                return
            except OutOfPages:
                pass
            if (
                len(self.scheduler.running) == 1
                and not self._deferred_pages
            ):
                # Truly alone with nothing pending release: the pool is
                # the cap and retrying would replay to this exact point
                # forever — truncate.
                self._finish_seq(seq, "length", device_detected=False,
                                 finished=finished)
            else:
                # Others hold the pool (or deferred pages will free it):
                # retry later instead of truncating (recompute
                # preemption) — generated tokens are kept.
                self._self_preempt_deferred(seq)

    def _finish_seq(
        self,
        seq: Sequence,
        reason: str,
        *,
        device_detected: bool,
        finished: List[RequestOutput],
    ) -> None:
        pages, cacheable = self.scheduler.finish(seq, reason, defer_pages=True)
        if pages:
            self._deferred_pages.append((self._dispatch_idx, pages, cacheable))
        if not device_detected:
            self._dirty = True
        finished.append(self._output_for(seq))

    def _stop_reason(self, seq: Sequence, token: int) -> Optional[str]:
        p = seq.params
        # Token-based stops are popped from the output, so the surviving
        # output must still hold min_tokens afterwards (strict compare).
        past_min_tok = len(seq.output_ids) > p.min_tokens
        past_min = len(seq.output_ids) >= p.min_tokens
        if past_min_tok and token in p.stop_token_ids:
            seq.output_ids.pop()  # stop token excluded from output
            return "stop"
        if past_min_tok and not p.ignore_eos and token in self._eos_ids:
            seq.output_ids.pop()
            return "stop"
        if len(seq.output_ids) >= p.max_tokens:
            return "length"
        if p.stop and past_min:
            # Incremental detokenization: the decoded head is cached per
            # sequence (Sequence.detok_text covers output_ids[:detok_len])
            # and only the tail past it is decoded each token — the cache
            # trails the end by at least `window` tokens (a stop string
            # spans at most its char count in tokens, +8 slack for
            # multi-char tokens), so a match can never hide entirely
            # inside the frozen head. Before the cache, every token paid
            # a window re-decode and a match paid O(output) full decodes.
            window = max(len(s) for s in p.stop) + 8
            tail = self._detok_tail(seq, window)
            # Only chars that can span the head/tail seam plus the fresh
            # tail need searching; the cached head was already searched
            # when its chars were in the tail of an earlier check.
            seam = max(len(s) for s in p.stop) - 1
            hay = seq.detok_text[-seam:] + tail if seam > 0 else tail
            if any(s in hay for s in p.stop):
                text = seq.detok_text + tail
                hits = [i for i in (text.find(s) for s in p.stop) if i >= 0]
                if hits:
                    idx = min(hits)  # earliest match, not list order
                    seq.finish_text = text[:idx]
                    self._trim_to_match(seq, p.stop)
                    return "stop"
        return None

    def _detok_tail(self, seq: Sequence, window: int) -> str:
        """Text of ``output_ids[detok_len:]``, advancing the cached head
        so it stays exactly ``window`` tokens behind the end (never
        fewer: late tokens could complete a stop string that starts in
        the margin, and BPE detokenization of a token range is only
        seam-stable a safe distance from the end)."""
        n = len(seq.output_ids)
        if seq.detok_len > n:  # output was truncated past the cache
            seq.detok_len, seq.detok_text = 0, ""
        if n - seq.detok_len > window:
            m = n - window
            seq.detok_text += self.tokenizer.decode(
                seq.output_ids[seq.detok_len : m]
            )
            seq.detok_len = m
        return self.tokenizer.decode(seq.output_ids[seq.detok_len :])

    def _trim_to_match(self, seq: Sequence, stops) -> None:
        """Drop output tokens past the stop-string match so token_ids and
        usage agree with the truncated text (bounded: only tokens past
        the cached head can ever be trimmed, and only their tail text is
        re-decoded)."""
        seam = max(len(s) for s in stops) - 1
        head_tail = seq.detok_text[-seam:] if seam > 0 else ""
        lo = seq.detok_len
        for n in range(lo, len(seq.output_ids) + 1):
            head = head_tail + self.tokenizer.decode(seq.output_ids[lo:n])
            if any(s in head for s in stops):
                seq.output_ids = seq.output_ids[:n]
                return

    def _output_for(self, seq: Sequence) -> RequestOutput:
        # Goodput accounting: a clean finish delivered useful work; a
        # shed/expired/cancelled one did not (its tokens were wasted).
        if (seq.finish_reason or "stop") in ("stop", "length"):
            self.class_finished[
                "interactive" if seq.priority == "interactive" else "batch"
            ] += 1
        text = seq.finish_text
        if text is None:
            text = self.tokenizer.decode(seq.output_ids)
        timing: Optional[Dict[str, float]] = None
        if seq.t_enqueue > 0.0:
            timing = {
                "engine_submit": seq.t_submit,
                "enqueued": seq.t_enqueue,
                "admitted": seq.t_admit,
                "prefill_start": seq.t_prefill_start,
                "first_token": seq.t_first_token,
                "last_token": seq.t_last_token,
                "finished": time.monotonic(),
                "preempt_count": float(seq.preempt_count),
            }
            if self.spans.on:
                self.spans.note_request(seq.rid, **timing)
        return RequestOutput(
            rid=seq.rid,
            text=text,
            token_ids=list(seq.output_ids),
            prompt_tokens=len(seq.prompt_ids),
            completion_tokens=len(seq.output_ids),
            finish_reason=seq.finish_reason or "stop",
            timing=timing,
            snapshot=self._prefill_snapshots.pop(seq.rid, None),
        )

    # --- snapshot plane ---------------------------------------------------
    def _snapshot_seq(self, seq: Sequence) -> RequestSnapshot:
        """Host-serializable state of one unfinished sequence. KV pages
        come from the sequence's pending host restore (swap-preempted),
        or a device gather (prefilled and running), or not at all
        (waiting/mid-prefill — re-insertion re-prefills, which is the
        same recovery recompute preemption already performs)."""
        p = seq.params
        kv_k = kv_v = None
        kv_valid = 0
        if seq.restore is not None:
            r = seq.restore
            kv_k, kv_v, kv_valid = r.k, r.v, r.valid
        elif seq.prefilled and seq.rid in self.scheduler.running and seq.pages:
            kv_valid = seq.num_tokens - 1
            n = snapshot_mod.pages_for(kv_valid, self.cfg.page_size)
            if 0 < n <= len(seq.pages):
                with self._wd("snapshot_gather"):
                    kv_k, kv_v = self._kv_gather_np(seq.pages[:n])
            else:
                kv_valid = 0
        return RequestSnapshot(
            rid=seq.rid,
            model_sig=self.cache.snapshot_sig(),
            page_size=self.cfg.page_size,
            prompt_ids=list(seq.prompt_ids),
            output_ids=list(seq.output_ids),
            params=dataclasses.replace(p),
            # µs-scale PRNG-key fetch; the snapshot's KV gathers above
            # are the heavy reads and run bracketed.
            key_data=np.asarray(  # llmq: ignore[unguarded-device-fetch]
                make_base_key(p.seed, request_tag(seq.rid)), np.uint32
            ),
            epoch=seq.epoch,
            preempt_count=seq.preempt_count,
            detok_len=seq.detok_len,
            detok_text=seq.detok_text,
            kv_valid=kv_valid,
            kv_k=kv_k,
            kv_v=kv_v,
        )

    def _remove_extracted(self, seq: Sequence) -> None:
        if seq.rid in self.scheduler.running:
            was_prefilled = seq.prefilled
            # Pipeline is drained (extract paths drain first), so pages
            # release immediately — no watermark needed.
            self.scheduler.finish(seq, "extracted")
            if was_prefilled:
                self._dirty = True
        else:
            try:
                self.scheduler.waiting.remove(seq)
            except ValueError:
                pass
        seq.restore = None

    def extract_request(
        self,
        rid: str,
        *,
        finished: Optional[List[RequestOutput]] = None,
    ) -> RequestSnapshot:
        """Pull one in-flight request out of the engine as a
        :class:`RequestSnapshot` and remove it. Drains the run-ahead
        pipeline first so scheduler truth is current; outputs observed
        during that drain are appended to ``finished`` (pass a list to
        keep them — a request that finishes during the drain raises
        KeyError here but surfaces there). Greedy continuation after
        :meth:`insert_request` is bit-identical to never extracting."""
        if self._hybrid:
            raise NotImplementedError(
                self.cache.refusal("extract_request")
            )
        out = finished if finished is not None else []
        self._drain(out)
        seq = self.scheduler.running.get(rid)
        if seq is None:
            seq = next(
                (s for s in self.scheduler.waiting if s.rid == rid), None
            )
        if seq is None or seq.finish_reason is not None:
            raise KeyError(f"no in-flight request {rid!r} to extract")
        snap = self._snapshot_seq(seq)
        self._remove_extracted(seq)
        self.snapshots_extracted += 1
        return snap

    def extract_all(
        self, *, finished: Optional[List[RequestOutput]] = None
    ) -> List[RequestSnapshot]:
        """Extract every unfinished request (drain-with-handoff). See
        :meth:`extract_request`."""
        if self._hybrid:
            raise NotImplementedError(
                self.cache.refusal("extract_all")
            )
        out = finished if finished is not None else []
        self._drain(out)
        snaps: List[RequestSnapshot] = []
        for seq in list(self.scheduler.running.values()) + list(
            self.scheduler.waiting
        ):
            if seq.finish_reason is not None:
                continue
            snaps.append(self._snapshot_seq(seq))
            self._remove_extracted(seq)
            self.snapshots_extracted += 1
        return snaps

    # --- fault recovery ---------------------------------------------------
    def discard_pending(self, *, reuse_pool: bool = False) -> None:
        """Drop every in-flight dispatch result without fetching it.
        Fault recovery only: after a device fault the pending outputs
        are unreadable (wedged or poisoned), while the sequences' host
        state — ``output_ids`` up to the last *processed* step — is
        still consistent. Re-inserting their snapshots recomputes the
        lost iterations deterministically (same key chain, same step
        counts), so greedy output is token-identical to a fault-free
        run; only a little progress is repaid.

        ``reuse_pool`` is the in-place (same backend) restore flavor:
        deferred pages go back to the allocator instead of being
        abandoned — safe because any in-flight writes to them are
        device-stream-ordered before whatever reuses them next."""
        self._pending.clear()
        self._pending_decodes = 0
        # Dropped swap captures fall back to re-prefill on re-admission:
        # always correct, just repays the preempted prefix.
        self._pending_swaps.clear()
        self._processed_idx = self._dispatch_idx
        if reuse_pool:
            for _, pages, cacheable in self._deferred_pages:
                self.scheduler.release_pages(pages, cacheable)
        # Otherwise deferred pages would now be past their watermark, but
        # the pool they'd return to is being abandoned with the faulted
        # backend; just drop the bookkeeping.
        self._deferred_pages.clear()
        self._dirty = True

    def extract_for_rebuild(
        self, *, reuse_pool: bool = False
    ) -> Tuple[List[Tuple[RequestSnapshot, Optional[float]]], List[str]]:
        """Best-effort snapshot of every in-flight request after a
        device fault, for re-insertion into a rebuilt engine. In-flight
        dispatch results are discarded first (see
        :meth:`discard_pending`), then each sequence snapshots
        *independently* — per-request isolation, unlike
        :meth:`extract_all`, because a gather from a wedged or poisoned
        backend can itself fault. Returns ``(snapshots_with_deadlines,
        lost_rids)``: rows whose snapshot failed (wedged in the faulted
        dispatch) go in the second list and recover via the worker's
        requeue path instead."""
        self.discard_pending(reuse_pool=reuse_pool)
        snaps: List[Tuple[RequestSnapshot, Optional[float]]] = []
        lost: List[str] = []
        for seq in list(self.scheduler.running.values()) + list(
            self.scheduler.waiting
        ):
            if seq.finish_reason is not None:
                continue
            try:
                snap = self._snapshot_seq(seq)
            except Exception:  # noqa: BLE001 — per-row isolation
                logger.exception(
                    "fault recovery: snapshot of %s failed; the request "
                    "will requeue instead",
                    seq.rid,
                )
                lost.append(seq.rid)
                # Still remove it: in the same-backend (reuse_pool)
                # restore a row left behind would keep generating against
                # a future already resolved as a requeue — a duplicate.
                self._remove_extracted(seq)
                continue
            snaps.append((snap, seq.deadline_at))
            self._remove_extracted(seq)
            self.snapshots_extracted += 1
        return snaps, lost

    def degrade_for_oom(self) -> Optional[str]:
        """One rung of the HBM-OOM degradation ladder per call, in
        order: (1) demote refcount-0 prefix device pages to the host
        cold tier, (2) cap the run-ahead pipeline at half its depth (fewer
        in-flight result buffers resident in HBM), (3) preempt one
        victim with swap-to-host. Returns the rung taken, or None when
        the ladder is dry — the caller then falls through to fault
        recovery (rebuild / dead-letter). Rungs never reset: a pool
        that OOMed stays degraded for the life of this engine."""
        self.hbm_oom_events += 1
        while self._oom_rung < 3:
            rung = self._oom_rung
            self._oom_rung += 1
            if rung == 0:
                if self.prefix_store is not None:
                    dropped = self.flush_prefix_to_host()
                    if dropped > 0:
                        self._oom_ladder_log.append("demote_prefix")
                        logger.warning(
                            "hbm_oom ladder: demoted %d prefix pages to "
                            "the host tier",
                            dropped,
                        )
                        return "demote_prefix"
            elif rung == 1:
                if self.cfg.runahead > 1:
                    # Half of what is in flight, which may be under the cap.
                    self.cfg.runahead = max(
                        1, min(self.cfg.runahead, self._ahead) // 2
                    )
                    self._oom_ladder_log.append("shrink_runahead")
                    logger.warning(
                        "hbm_oom ladder: run-ahead shrunk to %d",
                        self.cfg.runahead,
                    )
                    return "shrink_runahead"
            else:
                victim = next(
                    (
                        s
                        for s in reversed(
                            list(self.scheduler.running.values())
                        )
                        if s.prefilled and s.finish_reason is None
                    ),
                    None,
                )
                if victim is not None:
                    # Force the swap flavor for this one preemption: the
                    # point of the rung is freeing HBM *without* paying a
                    # re-prefill on top of an already-starved device.
                    prev = self.preempt_mode
                    self.preempt_mode = "swap"
                    try:
                        self._self_preempt_deferred(victim)
                    finally:
                        self.preempt_mode = prev
                    self._oom_ladder_log.append("preempt_swap")
                    logger.warning(
                        "hbm_oom ladder: swap-preempted %s", victim.rid
                    )
                    return "preempt_swap"
        return None

    def insert_request(
        self,
        snap: RequestSnapshot,
        *,
        deadline_at: Optional[float] = None,
    ) -> Sequence:
        """Re-insert an extracted request, here or on a different engine.
        KV pages are remapped to whatever physical pages admission hands
        out (repacked host-side if the page size differs); the sampling
        key chain is re-derived from (seed, rid) and verified against the
        snapshot bit-for-bit. A snapshot without KV re-prefills
        prompt+output instead — same math, same tokens."""
        if self._hybrid:
            raise NotImplementedError(
                self.cache.refusal("insert_request")
            )
        sig, mine = dict(snap.model_sig), self.cache.snapshot_sig()
        if sig != mine:
            raise SnapshotCompatError(
                f"snapshot model signature {sig} does not match engine "
                f"{mine}"
            )
        if snap.rid in self.scheduler.running or any(
            s.rid == snap.rid for s in self.scheduler.waiting
        ):
            raise ValueError(
                f"request {snap.rid!r} is already in flight on this engine"
            )
        params = dataclasses.replace(snap.params)
        # µs-scale PRNG-key fetch at insert time, not a dispatch wait.
        expect = np.asarray(  # llmq: ignore[unguarded-device-fetch]
            make_base_key(params.seed, request_tag(snap.rid)), np.uint32
        )
        # Snapshot payload is already host bytes.
        got = np.asarray(snap.key_data, np.uint32)  # llmq: ignore[unguarded-device-fetch]
        if got.shape != expect.shape or not np.array_equal(got, expect):
            raise SnapshotCompatError(
                "sampling-key chain mismatch: the snapshot's base key does "
                "not re-derive from (seed, rid) on this engine"
            )
        need = len(
            set(params.stop_token_ids)
            | (set() if params.ignore_eos else self._eos_ids)
        )
        if need > self._stop_capacity:
            self._grow_stop_capacity(need)
        seq = Sequence(
            rid=snap.rid,
            prompt_ids=[int(t) for t in snap.prompt_ids],
            params=params,
            output_ids=[int(t) for t in snap.output_ids],
            # Fresh epoch lineage on this engine; +1 mirrors what a
            # preemption would have done to any stale in-flight rows.
            epoch=snap.epoch + 1,
            preempt_count=snap.preempt_count,
            detok_len=snap.detok_len,
            detok_text=snap.detok_text,
            deadline_at=deadline_at,
        )
        if deadline_at is not None:
            self._deadlines_enabled = True
        if (
            snap.kv_k is not None
            and snap.kv_v is not None
            and snap.kv_valid > 0
        ):
            if snap.kv_valid != seq.num_tokens - 1:
                raise SnapshotCompatError(
                    f"snapshot KV covers {snap.kv_valid} positions but "
                    f"{seq.num_tokens - 1} are needed to continue decode"
                )
            k, v = snap.kv_k, snap.kv_v
            if snap.page_size != self.cfg.page_size:
                n_dst = snapshot_mod.pages_for(
                    snap.kv_valid, self.cfg.page_size
                )
                k = snapshot_mod.repack_pages(
                    k, snap.kv_valid, self.cfg.page_size, n_dst
                )
                v = snapshot_mod.repack_pages(
                    v, snap.kv_valid, self.cfg.page_size, n_dst
                )
            seq.restore = KVRestore(k=k, v=v, valid=snap.kv_valid)
        self.total_prompt_tokens += len(seq.prompt_ids)
        self.scheduler.add_restored(seq)
        self.snapshots_inserted += 1
        return seq

    def _restore_batch(self, seqs: List[Sequence]) -> None:
        """Scatter admitted sequences' host KV pages back into the pools
        and mark them prefilled. The decode-state rows join via the dirty
        resync on the next dispatch — resync rebuilds all 13 leaves from
        scheduler truth, which now includes these rows."""
        for seq in seqs:
            r = seq.restore
            seq.restore = None
            n = r.k.shape[1]
            # admit() allocated pages for num_tokens+1 positions, which
            # always covers the ceil(valid/page) pages of data.
            assert n <= len(seq.pages), (n, len(seq.pages))
            # Host page-index list → numpy; no device value involved.
            self._kv_insert_np(seq.pages[:n], r.k, r.v)
            seq.prefilled = True
            if seq.t_prefill_start == 0.0:
                seq.t_prefill_start = time.monotonic()
            self.scheduler.register_prefix(seq)
            self.kv_restores += 1
        self._dirty = True

    # --- numerics-integrity plane ----------------------------------------
    def _canary_generate(self) -> List[int]:
        """Run the deterministic golden prompt to completion on an idle
        core and return the greedy token ids. The prompt is fixed small
        ids (valid in any vocab), temperature 0, EOS ignored — the only
        sources of variance left are the weights and the compute, which
        is exactly what the canary is meant to witness."""
        v = self.model_config.vocab_size
        prompt = [(i * 7 + 1) % v for i in range(8)]
        self.add_request(
            "__canary__",
            prompt_ids=prompt,
            params=SamplingParams(
                temperature=0.0, max_tokens=8, ignore_eos=True
            ),
        )
        tokens: List[int] = []
        for _ in range(256):  # bounded: 8 tokens needs far fewer steps
            for out in self.step():
                if out.rid == "__canary__":
                    tokens = list(out.token_ids)
            if not self.has_work:
                break
        return tokens

    def _generate_canary(self) -> List[int]:
        """Record the golden canary tokens at engine build (idle core,
        fresh weights — by construction the trusted reference)."""
        from llmq_tpu.engine import integrity as integrity_mod

        golden = self._canary_generate()
        logger.info(
            "canary golden recorded: %d token(s), fold=%s",
            len(golden),
            integrity_mod.token_fold(golden),
        )
        return golden

    def run_canary(self) -> bool:
        """Replay the golden prompt and compare greedy tokens bit-exactly
        against the build-time recording. Only meaningful on an idle core
        (skipped otherwise — a busy core replays on the next idle sweep).
        A mismatch (or a guard trip during the replay) counts as a canary
        failure; the caller decides escalation."""
        if self._canary_golden is None:
            return True
        if self.has_work:
            return True
        self.canary_runs += 1
        try:
            got = self._canary_generate()
        except Exception as exc:  # noqa: BLE001 — a trip IS a failure
            self.canary_failures += 1
            # The failed replay may have left the canary sequence and its
            # pipeline entries behind; clear them so the core is reusable.
            self.abort_all("canary_failed")
            logger.error("canary replay raised: %s", exc)
            raise
        if got == self._canary_golden:
            return True
        from llmq_tpu.engine import integrity as integrity_mod

        self.canary_failures += 1
        logger.error(
            "canary FAILURE: got %s (fold=%s) want %s (fold=%s)",
            got,
            integrity_mod.token_fold(got),
            self._canary_golden,
            integrity_mod.token_fold(self._canary_golden),
        )
        return False

    def audit_weights(self) -> List[str]:
        """Re-digest every parameter leaf on device and diff against the
        build-time baseline. A non-empty return names the leaves whose
        HBM bytes changed since load — weight corruption, as opposed to
        the transient compute errors the logit guard catches. Two reads
        of intact HBM always agree, so false positives are impossible;
        the digest is associative, so sharded leaves fold identically."""
        if self._weight_baseline is None:
            return []
        from llmq_tpu.engine import integrity as integrity_mod

        self.weight_audits += 1
        with self._wd("weight_audit"):
            current = integrity_mod.digest_params(self.params)
        mismatched = integrity_mod.diff_digests(
            self._weight_baseline, current
        )
        if mismatched:
            self.weight_audit_mismatches += len(mismatched)
            self._last_audit_mismatch = list(mismatched)
            logger.error(
                "weight audit: %d leaf/leaves changed in HBM since load: %s",
                len(mismatched),
                mismatched[:8],
            )
        return mismatched

    def kv_spot_check(self, max_pages: int = 4) -> List[str]:
        """Read-stability spot check of the paged KV cache: gather a
        deterministic sample of in-use pages twice and compare blake2b
        digests. Unlike the weight audit there is no load-time baseline
        (KV churns constantly), so the check detects pages that do not
        read back consistently — the HBM-corruption signature that
        poisons every sequence sharing the page."""
        in_use = sorted(
            {
                p
                for s in self.scheduler.running.values()
                for p in s.pages
            }
        )
        if not in_use:
            return []
        from llmq_tpu.engine import integrity as integrity_mod

        stride = max(1, len(in_use) // max_pages)
        sample = in_use[::stride][:max_pages]
        # Host page-index list → numpy; no device value involved.
        idx = np.asarray(sample, np.int32)  # llmq: ignore[unguarded-device-fetch]
        self.kv_spot_checks += 1
        mismatched: List[str] = []
        with self._wd("kv_spot"):
            # Two independent full gathers (per-stage under pp: the
            # helper concatenates stage slabs back to the full layer
            # stack, so one digest still covers every stage's HBM).
            k1, v1 = self._kv_gather_np(idx)
            k2, v2 = self._kv_gather_np(idx)
        for name, first, second in (("k", k1, k2), ("v", v1, v2)):
            # gather returns [L, n, page, kv, d]; digest per sampled page.
            da = integrity_mod.page_digests(np.moveaxis(first, 1, 0))
            db = integrity_mod.page_digests(np.moveaxis(second, 1, 0))
            mismatched.extend(
                f"{name}:page{p}"
                for p, x, y in zip(sample, da, db)
                if x != y
            )
        if mismatched:
            logger.error(
                "kv spot check: %d page read(s) unstable: %s",
                len(mismatched),
                mismatched,
            )
        return mismatched

    def maybe_idle_integrity(self) -> Optional[str]:
        """Idle-step background sweep (engine thread, between batches):
        run whichever integrity checks have hit their cadence. Returns a
        failure detail string when something is wrong — the caller (the
        async loop) raises it into the device-fault containment path —
        or None when clean / nothing due."""
        now = time.monotonic()
        if (
            self._weight_baseline is not None
            and self.weight_audit_every > 0
            and now >= self._next_weight_audit
        ):
            self._next_weight_audit = now + self.weight_audit_every
            bad = self.audit_weights()
            bad.extend(self.kv_spot_check())
            if bad:
                return f"weight/KV audit mismatch: {bad[:8]}"
        if (
            self._canary_golden is not None
            and self.canary_every > 0
            and now >= self._next_canary
            and not self.has_work
        ):
            self._next_canary = now + self.canary_every
            if not self.run_canary():
                return "canary replay diverged from golden tokens"
        return None

    def integrity_status(self) -> str:
        """One-word integrity verdict for heartbeats: ``ok`` until any
        audit/canary evidence of corruption, then ``suspect``."""
        if (
            self.weight_audit_mismatches
            or self.canary_failures
            or self._last_audit_mismatch
        ):
            return "suspect"
        return "ok"

    def abort_all(self, note: str = "aborted") -> None:
        """Drop every running/waiting sequence and release their pages —
        recovery hook after a failed step, so the loop doesn't re-step a
        half-updated batch forever."""
        if self._pending:
            try:  # wait out in-flight steps; discard their results
                # Deliberately unbracketed: abort_all runs on the failure
                # path where the watchdog may have already tripped — a
                # second trip here would shadow the original fault.
                np.asarray(self._pending[-1][2])  # llmq: ignore[unguarded-device-fetch]
            except Exception:  # noqa: BLE001 — the step itself failed
                pass
            self._processed_idx = self._pending[-1][0]
            self._pending.clear()
            self._pending_decodes = 0
        # Swap captures reference the pool being torn down; their
        # sequences are gone with the abort anyway.
        self._pending_swaps.clear()
        self._flush_deferred()
        # The prefix cache must not survive an abort: the KV buffers may
        # be rebuilt (zeroed) below, and a cached hash pointing at a page
        # of the new pool would hand future requests empty context. The
        # host tier goes with it — its blobs were gathered from the same
        # now-untrusted buffers (invalidate_prefix_cache suppresses
        # demotion, so nothing re-parks during the teardown either).
        self.scheduler.invalidate_prefix_cache()
        if self.prefix_store is not None:
            self.prefix_store.invalidate()
        for seq in list(self.scheduler.running.values()):
            self.scheduler.finish(seq, note)
        self.scheduler.waiting.clear()
        self._dirty = True
        # A failed step may have consumed its donated inputs (kv/state
        # buffers deleted). KV contents are irrelevant now — every
        # sequence is gone — but the buffers must exist for the next
        # prefill, so rebuild any that died with the failed executable.
        if self.pp > 1:
            for s, (lo, hi) in enumerate(self._stage_ranges):
                try:
                    dead = (
                        self.k_pages[s].is_deleted()
                        or self.v_pages[s].is_deleted()
                    )
                except Exception:  # noqa: BLE001
                    dead = True
                if dead:
                    self.k_pages[s], self.v_pages[s] = self.cache.allocate(
                        self.scheduler.config.num_pages,
                        self._kv_formats[s],
                        num_layers=hi - lo,
                    )
            return
        try:
            dead = self.k_pages.is_deleted() or self.v_pages.is_deleted()
        except Exception:  # noqa: BLE001
            dead = True
        if dead:
            self.k_pages, self.v_pages = self.cache.allocate(
                self.scheduler.config.num_pages, self._kv_format
            )

    # --- metrics ----------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        elapsed = max(1e-9, time.monotonic() - self._started_at)
        s = self.scheduler.stats()
        kern, kda = self._decode_kernel_plan(), self._kda_decode_plan()
        s.update(
            prompt_tokens=self.total_prompt_tokens,
            generated_tokens=self.total_generated_tokens,
            decode_steps=self.decode_steps,
            # Host round trips: with fused decode blocks the host
            # dispatches/snapshots/fetches once per decode_block device
            # iterations, so dispatches <= ceil(decode_steps / K).
            decode_dispatches=self.decode_dispatches,
            decode_block=self.cfg.decode_block,
            # Dispatches the engine keeps in flight now (runahead_target).
            runahead_target=self._ahead,
            # Speculation health: accepted/proposed drafts. A dispatch
            # emits 1 + (accepted this step) tokens, so tok/s scales
            # with acceptance_rate at fixed step time (PERF_NOTES math).
            spec_tokens=self.cfg.spec_tokens,
            spec_proposed=self.spec_proposed,
            spec_accepted=self.spec_accepted,
            acceptance_rate=(
                self.spec_accepted / self.spec_proposed
                if self.spec_proposed
                else 0.0
            ),
            prefills=self.prefills,
            # Piggyback scheduling: fused decode+prefill dispatches and
            # the prompt positions they carried — nonzero proves the
            # mixed path actually ran (ISSUE 6 acceptance line).
            mixed_step=self.mixed_step,
            mixed_steps=self.mixed_steps,
            mixed_prefill_tokens=self.mixed_prefill_tokens,
            # Snapshot plane: swap-to-host preemption and extract/insert
            # traffic. kv_restores counts admissions that scattered host
            # KV back instead of re-prefilling.
            preempt_mode=self.preempt_mode,
            swap_preempts=self.swap_preempts,
            kv_restores=self.kv_restores,
            snapshots_extracted=self.snapshots_extracted,
            snapshots_inserted=self.snapshots_inserted,
            # Prefix reuse plane: prompt positions actually computed vs
            # reused, host-tier traffic, and shipping counters. A
            # templated batch with working reuse shows prefill_tokens
            # well below prompt_tokens.
            prefill_tokens=self.prefill_tokens,
            prefill_grid_tokens=self.prefill_grid_tokens,
            prefix_demotes=self.prefix_demotes,
            prefix_promotes=self.prefix_promotes,
            prefix_chunks_exported=self.prefix_chunks_exported,
            prefix_chunks_ingested=self.prefix_chunks_ingested,
            tokens_per_sec=self.total_generated_tokens / elapsed,
            devices=int(np.prod(list(self.full_mesh.shape.values()))),
            # What this engine actually runs — the attention backend,
            # the decode kernel and the pool's dtype and geometry — so
            # operators (and chip_smoke.py) see the device path itself
            # instead of guessing from env vars.
            attn_backend=(
                _dispatch.resolve_backend()
                if self.model.attn_backend == "auto"
                else self.model.attn_backend
            ),
            decode_kernel=kern,
            # a pattern with KDA layers only: "inplace" (the one-pass
            # kernel) or "xla"
            **({"kda_decode_plan": kda} if kda is not None else {}),
            # a pattern with latent layers only: the prefill buckets whose
            # expanded attention is the flash kernel, and those left to XLA
            # (read at build: the weights may be mid-swap)
            **({"mla_prefill_plan": self._mla_plan} if self._mla_plan is not None else {}),
            kv_dtype=str(jnp.dtype(self.cfg.kv_dtype)),
            page_size=self.cfg.page_size,
            num_pages=self.scheduler.config.num_pages,
            kv_pool_bytes=self.kv_pool_bytes,
            # Resolved at build time (LLMQ_TP_OVERLAP / config / its probe)
            # — may differ from cfg.tp_overlap ("auto", or forced off on
            # tp=1).
            tp_overlap=self.tp_overlap,
            # Latency percentiles (ms; None until the histogram has data).
            ttft_p50_ms=to_ms(self.ttft_hist.percentile(0.50)),
            ttft_p95_ms=to_ms(self.ttft_hist.percentile(0.95)),
            itl_p50_ms=to_ms(self.itl_hist.percentile(0.50)),
            itl_p95_ms=to_ms(self.itl_hist.percentile(0.95)),
        )
        if self._hybrid:
            # Expert layers held by share: assignments that landed on the
            # experts held here and held experts that got any, summed
            # over layers and decode steps (their quotient is the tokens
            # an expert that is hit sees a step).
            s["moe_assignments_held"] = self.moe_assignments_held
            s["moe_experts_hit"] = self.moe_experts_hit
        if self._closing_window is not None:
            s["eva_windows_closed"] = self.eva_windows_closed
        s.update(self.cache.stats())
        if self.cfg.spec_tokens > 0:
            # What speculation actually dispatches: the multi-query
            # verify resolves through its own plan, not the decode one.
            s["verify_kernel"] = _dispatch.verify_kernel_plan(
                self.model_config.num_heads,
                self.model_config.num_kv_heads,
                mesh=self.mesh,
            )
        if self.mixed_step == "on":
            s["mixed_kernel"] = _dispatch.mixed_kernel_plan(
                self.model_config.num_heads,
                self.model_config.num_kv_heads,
                mesh=self.mesh,
            )
        if self.prefix_store is not None:
            s.update(self.prefix_store.stats())
        # Pipeline parallelism (superset-only: pp=1 engines publish
        # byte-identical heartbeats). The bubble fraction is the GPipe
        # analytic (pp-1)/(m+pp-1) with the decode run-ahead depth (K
        # iterations per dispatch × runahead dispatches in flight) as
        # the microbatch count — the number the bench pp rung reports.
        if self.pp > 1:
            m = max(1, self.cfg.decode_block * self.cfg.runahead)
            s["pp_stages"] = self.pp
            s["pp_boundary_bytes"] = self.pp_boundary_bytes
            s["pp_boundary_transfers"] = self.pp_boundary_transfers
            s["pp_bubble_fraction"] = round(
                pp_mod.bubble_fraction(m, self.pp), 6
            )
            s["pp_boundary_bytes_per_token"] = (
                pp_mod.boundary_bytes_per_token(
                    self.model_config.hidden_size
                )
            )
            s["pp_wire"] = "codec" if self.pp_wire else "device"
        # SLO priority plane (superset-only: appears once the first
        # interactive request arrived — priority-free engines publish
        # byte-identical stats).
        if self._priority_enabled:
            s["priority_preemptions"] = self.priority_preemptions
            s["ttft_p50_ms_interactive"] = to_ms(
                self.ttft_hist_interactive.percentile(0.50)
            )
            s["ttft_p95_ms_interactive"] = to_ms(
                self.ttft_hist_interactive.percentile(0.95)
            )
            s["itl_p50_ms_interactive"] = to_ms(
                self.itl_hist_interactive.percentile(0.50)
            )
            s["itl_p95_ms_interactive"] = to_ms(
                self.itl_hist_interactive.percentile(0.95)
            )
            s["tokens_interactive"] = self.class_tokens["interactive"]
            s["tokens_batch"] = self.class_tokens["batch"]
            s["finished_interactive"] = self.class_finished["interactive"]
            s["finished_batch"] = self.class_finished["batch"]
        # Client-disconnect cancellation (superset-only: appears once a
        # cancel actually landed).
        if self.cancellations:
            s["cancellations"] = self.cancellations
        # Disaggregated serving (superset-only: appears once this engine
        # has finished a prefill-only request at the phase boundary).
        if self.prefill_done:
            s["prefill_done"] = self.prefill_done
        # Fleet self-healing counters (superset-only: appear once moved).
        if self.deadline_expirations:
            s["deadline_expirations"] = self.deadline_expirations
        if self.swap_refused:
            s["swap_refused"] = self.swap_refused
        # Device-fault containment (superset-only: the watchdog block
        # appears only when the watchdog is on, the OOM block only after
        # an allocation fault — defaults publish neither).
        if self.watchdog is not None:
            s["watchdog_trips"] = self.watchdog.trips
            s["last_dispatch_ok_age_s"] = round(
                self.watchdog.last_ok_age_s(), 3
            )
            wedged = self.watchdog.wedged_kind()
            if wedged is not None:
                # A dispatch is in flight AND past its deadline right now
                # — the signature that separates a wedged engine from a
                # healthy idle one (whose ok-age also grows, jobless).
                s["wedged_dispatch"] = wedged
        if self.hbm_oom_events:
            s["hbm_oom_events"] = self.hbm_oom_events
            s["oom_degradations"] = list(self._oom_ladder_log)
        # Device memory as the backend reports it (TPU; the CPU backend
        # reports nothing and the keys are absent).
        mem = self.mesh.devices.flat[0].memory_stats()
        if mem and "bytes_limit" in mem:
            s["hbm_bytes_limit"] = mem["bytes_limit"]
            s["hbm_peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
        # Numerics-integrity plane (superset-only: each block appears
        # once its knob is on / its counter moved — default-off
        # heartbeats stay byte-identical to pre-integrity builds).
        if self.guard_trips:
            s["guard_trips"] = self.guard_trips
        if self.weight_audits:
            s["weight_audits"] = self.weight_audits
            s["weight_audit_mismatches"] = self.weight_audit_mismatches
            s["kv_spot_checks"] = self.kv_spot_checks
            if self._last_audit_mismatch:
                s["last_audit_mismatch"] = list(self._last_audit_mismatch)
        if self.canary_runs:
            s["canary_runs"] = self.canary_runs
            s["canary_failures"] = self.canary_failures
        if (
            self.logit_guard == "on"
            or self.weight_audit_every > 0
            or self.canary_every > 0
        ):
            s["integrity"] = self.integrity_status()
        gov = get_governor()
        if gov.enabled:
            s["host_mem"] = gov.stats()
        return s


@dataclasses.dataclass
class HandoffOutput:
    """What :meth:`AsyncEngine.handoff` resolves an in-flight request
    with instead of a :class:`RequestOutput`: the request's snapshot (or
    None when it never entered the engine — no partial state to carry)
    and the count of tokens already generated (the resume offset for
    result-side dedup)."""

    rid: str
    snapshot: Optional[RequestSnapshot]
    emitted: int = 0


#: Hard ceiling on one in-process fault recovery (extract + rebuild +
#: re-insert). A device wedged badly enough that the *recovery* blocks
#: past this is unrecoverable in-process: the backstop hard-exits so the
#: orphan janitor reclaims the worker's affinity queue and its jobs
#: requeue, instead of a zombie holding them forever.
REBUILD_HARD_EXIT_S = 180.0


def _hard_exit_wedged(reason: str) -> None:
    logger.critical(
        "engine rebuild after %s exceeded %.0fs — process is wedged "
        "beyond in-process recovery; hard-exiting for janitor reclaim",
        reason,
        REBUILD_HARD_EXIT_S,
    )
    os._exit(86)


class AsyncEngine:
    """Async facade: step loop on a dedicated thread, asyncio-awaitable
    results (the surface the reference consumed from AsyncLLMEngine)."""

    def __init__(self, core: EngineCore) -> None:
        self.core = core
        self._intake: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._futures: Dict[str, Future] = {}
        self._wake = threading.Event()
        self._stop = False
        self._draining = False
        self._handoff_requested = False
        self._handoff_event: Optional[threading.Event] = None
        self._handoff_results: List[HandoffOutput] = []
        # Device-fault containment (all optional; workers wire them):
        # rebuild_core() returns a fresh EngineCore in a fresh backend —
        # when set, a classified device fault rebuilds in-process and
        # re-inserts every restorable request instead of failing the
        # batch. on_device_fault(reason) is the worker's breaker
        # notification (called on the engine thread; must be cheap /
        # thread-safe).
        self.rebuild_core: Optional[Any] = None
        self.on_device_fault: Optional[Any] = None
        # A step program the compiler refused (StepCompileError) ends the
        # engine: on_fatal(exc) tells the owner, fatal_error keeps it.
        self.on_fatal: Optional[Any] = None
        # on_tracing(on): a profile of the process began or ended and the
        # engine's ring followed it (called on the engine thread); the
        # worker switches its own ring.
        self.on_tracing: Optional[Any] = None
        self.fatal_error: Optional[StepCompileError] = None
        self.engine_rebuilds = 0
        self.last_fault_reason: Optional[str] = None
        # Trips recorded by watchdogs of cores already rebuilt away;
        # stats() adds them so the counter never moves backwards.
        self._prior_watchdog_trips = 0
        # Blame attribution for numerical faults: rid -> trip count.
        # First trip re-runs the request on a rebuilt core (device
        # blamed); a second trip classifies the job as poison. Entries
        # pop on clean completion or on the poison verdict, so the map
        # never outlives its requests.
        self._numerical_probation: Dict[str, int] = {}
        # rid -> [(event_name, t_mono, fields)] recorded during fault
        # recovery; workers pop these into the request trace.
        self._fault_events: Dict[str, List[Tuple[str, float, Dict[str, Any]]]] = {}
        self._fault_lock = threading.Lock()
        # Closures marshalled onto the engine thread (prefix-tier export/
        # ingest touch the device pools, which the step loop donates).
        self._calls: "queue.Queue[Tuple[Any, Future]]" = queue.Queue()
        # rid -> per-token callback (streaming deltas). Fired on the
        # ENGINE thread for every surviving token, so callbacks must be
        # cheap and thread-safe (workers bridge with
        # loop.call_soon_threadsafe). Keyed per-request: jobs that never
        # register one cost a single dict miss per token.
        self._token_cbs: Dict[str, Any] = {}
        core.on_token = self._dispatch_token
        self._thread = threading.Thread(
            target=self._run, name="llmq-engine", daemon=True
        )
        self._thread.start()

    # --- public surface ---------------------------------------------------
    async def generate(
        self,
        *,
        rid: str,
        prompt: Optional[str] = None,
        messages: Optional[List[Dict[str, str]]] = None,
        prompt_ids: Optional[List[int]] = None,
        params: Optional[SamplingParams] = None,
        deadline_at: Optional[float] = None,
        prefill_only: bool = False,
        priority: str = "batch",
    ) -> RequestOutput:
        import asyncio

        if self.fatal_error is not None:
            raise self.fatal_error
        if self._draining:
            raise RuntimeError("engine is draining for handoff")
        fut: Future = Future()
        self._futures[rid] = fut
        self._intake.put(
            (rid, prompt, messages, prompt_ids, params, None, deadline_at,
             prefill_only, priority, time.monotonic())
        )
        self._wake.set()
        try:
            return await asyncio.wrap_future(fut)
        finally:
            self._futures.pop(rid, None)

    async def resume(
        self,
        *,
        rid: str,
        snapshot: RequestSnapshot,
        deadline_at: Optional[float] = None,
    ) -> RequestOutput:
        """Continue a request from a :class:`RequestSnapshot` (published
        by a peer's drain-with-handoff). Completes exactly like generate();
        may itself resolve with a HandoffOutput if THIS engine drains."""
        import asyncio

        if self.fatal_error is not None:
            raise self.fatal_error
        if self._draining:
            raise RuntimeError("engine is draining for handoff")
        fut: Future = Future()
        self._futures[rid] = fut
        self._intake.put(
            (rid, None, None, None, None, snapshot, deadline_at, False,
             "batch", time.monotonic())
        )
        self._wake.set()
        try:
            return await asyncio.wrap_future(fut)
        finally:
            self._futures.pop(rid, None)

    def generate_sync(self, *, rid: str, **kwargs) -> RequestOutput:
        if self.fatal_error is not None:
            raise self.fatal_error
        fut: Future = Future()
        self._futures[rid] = fut
        self._intake.put(
            (
                rid,
                kwargs.get("prompt"),
                kwargs.get("messages"),
                kwargs.get("prompt_ids"),
                kwargs.get("params"),
                kwargs.get("snapshot"),
                kwargs.get("deadline_at"),
                kwargs.get("prefill_only", False),
                kwargs.get("priority", "batch"),
                time.monotonic(),
            )
        )
        self._wake.set()
        try:
            return fut.result()
        finally:
            self._futures.pop(rid, None)

    def handoff(self, timeout: float = 120.0) -> List[HandoffOutput]:
        """Drain-with-handoff (thread-safe, called from any thread): let
        in-flight device steps land, extract every unfinished request as
        a snapshot, and resolve its pending future with a
        :class:`HandoffOutput` instead of a RequestOutput. New
        generate()/resume() calls fail fast afterwards. Returns the
        handoffs; requests that finish during the drain resolve with
        their normal RequestOutput and are not in the list."""
        self._draining = True  # refuse new intake even before the drain
        if not self._thread.is_alive():
            return []
        self._handoff_results = []
        self._handoff_event = threading.Event()
        self._handoff_requested = True
        self._wake.set()
        if not self._handoff_event.wait(timeout=timeout):
            logger.warning("engine handoff timed out after %.1fs", timeout)
        return self._handoff_results

    def stats(self) -> Dict[str, Any]:
        s = self.core.stats()
        if self._prior_watchdog_trips and "watchdog_trips" in s:
            # Trips survive rebuilds: the faulted core's watchdog died
            # with it, but the count is a worker-lifetime monotonic.
            s["watchdog_trips"] += self._prior_watchdog_trips
        return s

    @property
    def watchdog_trips(self) -> int:
        """Worker-lifetime watchdog trip count, across engine rebuilds."""
        wd = getattr(self.core, "watchdog", None)
        return self._prior_watchdog_trips + (wd.trips if wd else 0)

    # --- streaming / cancellation ----------------------------------------
    def _dispatch_token(self, seq: Any, token: int) -> None:
        """EngineCore.on_token bridge (engine thread): route a surviving
        token to the request's registered callback, if any. Callback
        errors are swallowed — a broken stream consumer must not take
        down the step loop or the other requests in the batch."""
        cb = self._token_cbs.get(seq.rid)
        if cb is None:
            return
        try:
            cb(token, len(seq.output_ids))
        except Exception:  # noqa: BLE001 — consumer bug, not engine fault
            logger.exception("token callback for %s failed", seq.rid)

    def set_token_callback(self, rid: str, cb: Any) -> None:
        """Register ``cb(token, n_out)`` for one request's streaming
        deltas. Fired on the engine thread for each token that survives
        the stop check; ``n_out`` is the output length *including* this
        token (its 1-based index), so consumers can place tokens by
        absolute position and stay idempotent across fault-recovery
        replays. Register before generate() to see every token."""
        self._token_cbs[rid] = cb

    def clear_token_callback(self, rid: str) -> None:
        self._token_cbs.pop(rid, None)

    def cancel(self, rid: str) -> None:
        """Request cancellation of one in-flight request (thread-safe,
        non-blocking). Marshalled onto the engine thread; the request
        finishes with finish_reason='cancelled' through the normal output
        path (pages freed, future resolved), or is silently dropped from
        the waiting queue. Unknown rids are remembered briefly by the
        core so a cancel racing the intake drain still lands."""
        if not self._thread.is_alive():
            return
        self._calls.put((lambda: self.core.cancel_request(rid), Future()))
        self._wake.set()

    def call_on_engine(self, fn, timeout: float = 30.0):
        """Run ``fn()`` on the engine thread and return its result.
        Device-pool access (gathers, inserts) races the step loop's
        buffer donation from any other thread — everything that touches
        ``core.k_pages``/``v_pages`` outside the loop goes through here."""
        if not self._thread.is_alive():
            return fn()  # thread gone: no donation race left to lose
        fut: Future = Future()
        self._calls.put((fn, fut))
        self._wake.set()
        return fut.result(timeout=timeout)

    def export_prefix_chunks(self, digests_hex: List[str]) -> List[str]:
        """Thread-safe :meth:`EngineCore.export_prefix_chunks`."""
        return self.call_on_engine(
            lambda: self.core.export_prefix_chunks(digests_hex)
        )

    def ingest_prefix_chunks(self, chunks_b64: List[str]) -> int:
        """Thread-safe :meth:`EngineCore.ingest_prefix_chunks`."""
        return self.call_on_engine(
            lambda: self.core.ingest_prefix_chunks(chunks_b64)
        )

    def hot_prefix_chains(self, n: int = 8) -> List[str]:
        """Heartbeat helper; reads host-side maps only, but runs on the
        engine thread anyway so the dicts aren't mutated mid-iteration."""
        try:
            return self.call_on_engine(
                lambda: self.core.hot_prefix_chains(n), timeout=5.0
            )
        except Exception:  # noqa: BLE001 — advertisement is best-effort
            return []

    def missing_prefix_digests(self, digests_hex: List[str]) -> List[str]:
        """Thread-safe want-list check; [] on any failure (the fetch
        path treats "nothing missing" as "nothing to fetch")."""
        try:
            return self.call_on_engine(
                lambda: self.core.missing_prefix_digests(digests_hex),
                timeout=5.0,
            )
        except Exception:  # noqa: BLE001 — fetch is best-effort
            return []

    def shutdown(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=30)

    # --- fault recovery ---------------------------------------------------
    def pop_fault_events(self, rid: str) -> List[Tuple[str, float, Dict[str, Any]]]:
        """Take (and clear) the fault-recovery events recorded for one
        request: ``(name, t_mono, fields)`` tuples the worker projects
        onto the request trace. Thread-safe; [] when none."""
        with self._fault_lock:
            return self._fault_events.pop(rid, [])

    def _record_fault_event(
        self, rids: List[str], name: str, **fields: Any
    ) -> None:
        t = time.monotonic()
        with self._fault_lock:
            for rid in rids:
                self._fault_events.setdefault(rid, []).append(
                    (name, t, dict(fields))
                )

    def _degrade_and_restore(self, exc: Exception) -> bool:
        """Absorb one HBM-OOM fault on the SAME backend: take one rung of
        the degradation ladder, then restore every in-flight request from
        its host-truth snapshot. The faulted step may already have
        advanced device state past the last *processed* result (the
        dispatched block's outputs are lost with the exception), so a
        blind re-step would silently skip those tokens — restoring from
        snapshots recomputes them deterministically instead (same key
        chain, same step counts: greedy output stays token-identical).
        Returns False when the ladder is dry or the restore itself
        faults; the caller then falls through to the rebuild hammer."""
        rung = None
        try:
            rung = self.core.degrade_for_oom()
        except Exception:  # noqa: BLE001 — ladder best-effort
            logger.exception("hbm_oom degradation failed")
        if rung is None:
            return False
        affected = [
            rid for rid, fut in list(self._futures.items()) if not fut.done()
        ]
        self._record_fault_event(affected, "device_fault", reason=FAULT_OOM)
        for rid in affected:
            emit_trace_event(rid, "device_fault", reason=FAULT_OOM)
        try:
            snaps, lost = self.core.extract_for_rebuild(reuse_pool=True)
        except Exception:  # noqa: BLE001 — fall through to rebuild
            logger.exception(
                "hbm_oom restore: extraction failed; falling through to "
                "engine rebuild"
            )
            return False
        lost_set = set(lost)
        restored = 0
        for snap, deadline_at in snaps:
            try:
                self.core.insert_request(snap, deadline_at=deadline_at)
                restored += 1
            except Exception:  # noqa: BLE001 — per-row isolation
                logger.exception(
                    "hbm_oom restore: re-insert of %s failed; requeueing",
                    snap.rid,
                )
                lost_set.add(snap.rid)
        for rid in lost_set:
            fut = self._futures.get(rid)
            if fut is not None and not fut.done():
                fut.set_result(HandoffOutput(rid=rid, snapshot=None, emitted=0))
        self._record_fault_event(
            affected,
            "oom_degraded",
            rung=rung,
            restored=restored,
            requeued=len(lost_set),
        )
        logger.warning(
            "hbm_oom (%s) absorbed by degradation ladder (%s): %d "
            "request(s) restored in place, %d requeued",
            exc,
            rung,
            restored,
            len(lost_set),
        )
        return True

    def _recover_numerical(self, exc: Exception) -> bool:
        """Blame-attributed recovery for a numerical fault (logit-guard
        trip, failed weight/KV audit, or canary divergence). First trip
        for a request presumes the DEVICE is at fault: rebuild the core
        in a fresh backend (weights re-streamed from the trusted source),
        re-insert the suspects from their snapshots, and let greedy
        determinism replay them token-identically. A request whose
        re-run trips AGAIN is poison — its input deterministically
        breaks the numerics — so its future fails with a classified
        DeviceFaultError (the worker ladder quarantines it with
        ``x-failure-reason=numerical_fault``) instead of hot-looping
        rebuilds forever. Returns False when no rebuild path is wired
        (fall through to the batch-abort path)."""
        if self.rebuild_core is None:
            return False
        suspects = tuple(getattr(exc, "suspects", ()) or ())
        poison = [r for r in suspects if r in self._numerical_probation]
        fresh = [r for r in suspects if r not in self._numerical_probation]
        for rid in fresh:
            self._numerical_probation[rid] = 1
        if poison:
            logger.error(
                "numerical fault re-tripped by %s — poison job(s); "
                "quarantining instead of rebuilding again",
                poison,
            )
        if not self._rebuild_after_fault(
            FAULT_NUMERICAL, exc, drop=frozenset(poison)
        ):
            return False
        failure = DeviceFaultError(
            FAULT_NUMERICAL,
            f"request re-tripped the numerics guard after a rebuild: {exc}",
        )
        for rid in poison:
            self._numerical_probation.pop(rid, None)
            self._record_fault_event([rid], "poison_numerical")
            emit_trace_event(rid, "poison_numerical")
            fut = self._futures.get(rid)
            if fut is not None and not fut.done():
                fut.set_exception(failure)
        # Device-blamed path: before the rebuilt core takes traffic, it
        # re-verifies its weights and replays the canary (both recorded
        # fresh by its own build) — a chip that is still corrupting
        # fails here instead of on user requests.
        try:
            if self.core._weight_baseline is not None:
                self.core.audit_weights()
            if self.core._canary_golden is not None:
                self.core.run_canary()
        except Exception:  # noqa: BLE001 — re-verify is best-effort
            logger.exception("post-rebuild integrity re-verify failed")
        return True

    def _rebuild_after_fault(
        self,
        reason: str,
        exc: Exception,
        drop: frozenset = frozenset(),
    ) -> bool:
        """On the engine thread: contain a classified device fault by
        rebuilding the EngineCore in a fresh backend in-process. Every
        restorable request re-inserts from its snapshot and resumes
        (greedy token-identical — same key chain, same step counts);
        rows wedged in the faulted dispatch resolve with a snapshot-less
        HandoffOutput so the worker requeues them whole. Returns False
        to fall through to the batch-abort path (rebuild unavailable or
        itself failed). A recovery that *hangs* — the device wedged so
        hard that even extraction or the rebuild blocks forever — trips
        the hard-exit backstop, and the orphan janitor reclaims this
        worker's queue. Requests named in ``drop`` are neither
        re-inserted nor requeued — the caller has already decided their
        fate (poison verdicts fail their futures directly)."""
        logger.error(
            "device fault (%s): %s — attempting in-process engine rebuild",
            reason,
            exc,
        )
        self.last_fault_reason = reason
        if self.on_device_fault is not None:
            try:
                self.on_device_fault(reason)
            except Exception:  # noqa: BLE001 — observer must not block us
                logger.exception("on_device_fault callback failed")
        affected = [
            rid for rid, fut in list(self._futures.items()) if not fut.done()
        ]
        self._record_fault_event(affected, "device_fault", reason=reason)
        for rid in affected:
            emit_trace_event(rid, "device_fault", reason=reason)
        timer = threading.Timer(
            REBUILD_HARD_EXIT_S, _hard_exit_wedged, args=(reason,)
        )
        timer.daemon = True
        timer.start()
        try:
            old = self.core
            try:
                snaps, lost = old.extract_for_rebuild()
            except Exception:  # noqa: BLE001 — extraction is best-effort
                logger.exception(
                    "fault recovery: extraction failed outright; every "
                    "in-flight request will requeue"
                )
                snaps, lost = [], list(affected)
            old.stop_watchdog()
            old_wd = getattr(old, "watchdog", None)
            if old_wd is not None:
                self._prior_watchdog_trips += old_wd.trips
            try:
                new_core = self.rebuild_core()
            except Exception:  # noqa: BLE001 — fall back to batch abort
                logger.exception(
                    "fault recovery: rebuild failed; aborting the batch"
                )
                return False
            # One ring per engine thread, whatever core it steps.
            new_core.spans = self.core.spans
            new_core.spans.extra = new_core._dump_scopes
            self.core = new_core
            new_core.on_token = self._dispatch_token  # streams survive rebuild
            del old  # free the faulted backend's buffers before stepping
            self.engine_rebuilds += 1
            lost_set = set(lost) - drop
            restored = 0
            for snap, deadline_at in snaps:
                if snap.rid in drop:
                    continue
                try:
                    new_core.insert_request(snap, deadline_at=deadline_at)
                    restored += 1
                except Exception:  # noqa: BLE001 — per-row isolation
                    logger.exception(
                        "fault recovery: re-insert of %s failed; requeueing",
                        snap.rid,
                    )
                    lost_set.add(snap.rid)
            # Wedged / unsnapshottable rows recover via the worker's
            # existing republish path: a snapshot-less HandoffOutput is
            # a plain requeue.
            for rid in lost_set:
                fut = self._futures.get(rid)
                if fut is not None and not fut.done():
                    fut.set_result(
                        HandoffOutput(rid=rid, snapshot=None, emitted=0)
                    )
            self._record_fault_event(
                affected,
                "engine_rebuilt",
                restored=restored,
                requeued=len(lost_set),
            )
            for rid in affected:
                emit_trace_event(rid, "engine_rebuilt")
            logger.warning(
                "engine rebuilt in-process after %s: %d request(s) "
                "restored, %d requeued",
                reason,
                restored,
                len(lost_set),
            )
            return True
        finally:
            timer.cancel()

    def _fail_fatally(self, exc: Exception) -> None:
        """On the engine thread: a program does not compile (a step
        program caught by ``_StepProgram``, or any other whose error says
        so — ``is_compile_failure``). No rebuild, no retry — fail every
        pending request with the error (the worker leaves the jobs for
        redelivery), refuse new ones, tell the owner (``on_fatal``; the
        worker exits non-zero) and let the loop end."""
        if not isinstance(exc, StepCompileError):
            exc = StepCompileError(f"{type(exc).__name__}: {exc}")
        logger.critical("engine stopping: %s", exc)
        self.fatal_error = exc
        self._draining = True
        self._stop = True
        if self.on_fatal is not None:
            self.on_fatal(exc)  # the owner stops taking work first
        self.core.abort_all("error")
        while True:
            try:
                self._intake.get_nowait()
            except queue.Empty:
                break
        for fut in list(self._futures.values()):
            if not fut.done():
                fut.set_exception(exc)

    # --- engine thread ----------------------------------------------------
    def _run_handoff(self) -> None:
        """On the engine thread: drain, extract, resolve. Outputs that
        finish during the drain resolve normally; everything unfinished
        resolves with a HandoffOutput carrying its snapshot. Intake-queue
        stragglers (accepted before _draining flipped) resolve with a
        snapshot-less HandoffOutput — the worker requeues those whole."""
        self._handoff_requested = False
        results: List[HandoffOutput] = []
        try:
            outs: List[RequestOutput] = []
            snaps = self.core.extract_all(finished=outs)
            for out in outs:
                fut = self._futures.get(out.rid)
                if fut is not None and not fut.done():
                    fut.set_result(out)
            for snap in snaps:
                ho = HandoffOutput(
                    rid=snap.rid,
                    snapshot=snap,
                    emitted=len(snap.output_ids),
                )
                results.append(ho)
                fut = self._futures.get(snap.rid)
                if fut is not None and not fut.done():
                    fut.set_result(ho)
            while True:
                try:
                    item = self._intake.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    continue
                ho = HandoffOutput(rid=item[0], snapshot=None, emitted=0)
                results.append(ho)
                fut = self._futures.get(item[0])
                if fut is not None and not fut.done():
                    fut.set_result(ho)
        except Exception:  # noqa: BLE001 — handoff must never wedge shutdown
            logger.exception("engine handoff failed; aborting batch")
            self.core.abort_all("handoff_failed")
            for fut in list(self._futures.values()):
                if not fut.done():
                    fut.set_exception(RuntimeError("engine handoff failed"))
        finally:
            self._handoff_results = results
            ev = self._handoff_event
            if ev is not None:
                ev.set()

    def set_tracing(self, on: bool) -> None:
        """Switch the engine thread's span ring (``obs/spans.py``) on or
        off, from any thread; it then stays so whether or not a profile
        is being taken."""
        self.call_on_engine(lambda: self.core.spans.set(on))

    def trace_dump(self) -> Dict[str, Any]:
        """The engine ring's dump: spans, request stamps, counters and
        ``scopes`` (``EngineCore._dump_scopes``)."""
        return self.core.spans.dump()

    def _run(self) -> None:
        while not self._stop:
            ring = self.core.spans
            if _profile_active() != ring.profiled:
                # A profile of the process began or ended: the rings are
                # on for as long as it is taken (on_tracing: the worker's).
                ring.follow_profiler(not ring.profiled)
                if self.on_tracing is not None:
                    self.on_tracing(ring.profiled)
            if ring.on:
                ring.close_all()  # the last turn
                ring.begin(
                    "turn", 0, pending=len(self.core._pending),
                    waiting=len(self.core.scheduler.waiting),
                    running=len(self.core.scheduler.running),
                )
            if self._handoff_requested:
                self._run_handoff()
            while True:  # marshalled calls (prefix export/ingest)
                try:
                    fn, call_fut = self._calls.get_nowait()
                except queue.Empty:
                    break
                try:
                    call_fut.set_result(fn())
                except Exception as exc:  # noqa: BLE001 — caller's error
                    call_fut.set_exception(exc)
            drained = False
            if ring.on and not self._intake.empty():
                ring.begin("intake", n=self._intake.qsize())
            while True:
                try:
                    item = self._intake.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    continue
                (rid, prompt, messages, prompt_ids, params, snapshot, dl,
                 prefill_only, prio, t_submit) = item
                try:
                    if snapshot is not None:
                        seq = self.core.insert_request(snapshot, deadline_at=dl)
                    else:
                        seq = self.core.add_request(
                            rid,
                            prompt=prompt,
                            messages=messages,
                            prompt_ids=prompt_ids,
                            params=params,
                            deadline_at=dl,
                            prefill_only=prefill_only,
                            priority=prio,
                        )
                    seq.t_submit = t_submit
                    drained = True
                except Exception as exc:  # tokenization/validation error
                    fut = self._futures.get(rid)
                    if fut is not None and not fut.done():
                        fut.set_exception(exc)
            if ring.on and ring.inside("intake"):
                ring.end()
            if not self.core.has_work and not drained:
                # Idle integrity sweep (weight audit / KV spot-check /
                # canary replay on their cadences; no-op at defaults).
                # Evidence of corruption routes into the same numerical
                # containment path a guard trip takes.
                try:
                    suspicion = self.core.maybe_idle_integrity()
                except Exception as idle_exc:  # noqa: BLE001 — replay tripped
                    suspicion = f"canary replay raised: {idle_exc}"
                if suspicion is not None:
                    if not self._recover_numerical(
                        DeviceFaultError(FAULT_NUMERICAL, suspicion)
                    ):
                        logger.error(
                            "numerical fault with no rebuild path: %s",
                            suspicion,
                        )
                self._wake.wait(timeout=0.02)
                self._wake.clear()
                continue
            try:
                for out in self.core.step():
                    if ring.on and not ring.inside("resolve"):
                        ring.begin("resolve")
                    self._numerical_probation.pop(out.rid, None)
                    fut = self._futures.get(out.rid)
                    if fut is not None and not fut.done():
                        fut.set_result(out)
                if ring.on and ring.inside("resolve"):
                    ring.end()
            except Exception as exc:  # noqa: BLE001 — keep the loop alive
                if is_compile_failure(exc):
                    self._fail_fatally(exc)
                    break
                reason = classify_failure(exc)
                if reason == FAULT_OOM and self._degrade_and_restore(exc):
                    continue
                if reason == FAULT_NUMERICAL and self._recover_numerical(exc):
                    continue
                if reason is not None and self.rebuild_core is not None:
                    if self._rebuild_after_fault(reason, exc):
                        continue
                logger.exception("engine step failed")
                # Fail all in-flight requests AND clear the core's batch:
                # re-stepping a half-updated batch would loop hot on the
                # same exception. The worker requeues the jobs.
                self.core.abort_all("error")
                # Drain the intake queue too: those requests' futures are
                # failed below, so adding them next iteration would
                # generate orphaned completions nobody is awaiting.
                while True:
                    try:
                        self._intake.get_nowait()
                    except queue.Empty:
                        break
                # Classified device faults keep their class on the way
                # out so the worker dead-letters with a precise
                # x-failure-reason; everything else is byte-identical to
                # the pre-containment path.
                failure: Exception = (
                    DeviceFaultError(reason, f"engine step failed: {exc}")
                    if reason is not None
                    else RuntimeError("engine step failed")
                )
                for fut in list(self._futures.values()):
                    if not fut.done():
                        fut.set_exception(failure)
        if self.core.spans.on:
            self.core.spans.close_all()
        # Loop exit (shutdown): catch the host up so in-flight steps are
        # processed and deferred pages release — the last futures resolve
        # several iterations before the run-ahead pipeline fully lands,
        # and stopping mid-pipeline would strand refcounts.
        try:
            self.core._drain([])
        except Exception:  # noqa: BLE001 — best-effort cleanup
            logger.exception("drain on shutdown failed")
        self.core.stop_watchdog()
