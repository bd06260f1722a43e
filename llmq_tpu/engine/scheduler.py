"""Continuous-batching scheduler: slots, paged KV allocation, preemption.

This is the host-side half of what vLLM's C++/CUDA scheduler did for the
reference (SURVEY.md §2b "continuous batching scheduler"). The device half
is a *fixed-shape* compiled decode step over ``max_num_seqs`` slots; this
module decides which sequence lives in which slot and which physical KV
pages back it, so the device program never recompiles as requests churn.

Invariants (property-tested in tests/test_scheduler.py):
  - every physical page's refcount equals the number of running sequences
    listing it (exactly one owner unless prefix caching shares it; page 0
    is a reserved scratch page and is never handed out),
  - every admitted sequence has pages covering len(tokens)+1 positions
    (room for the KV write of the token being decoded),
  - slots hold at most one sequence; finished/preempted sequences release
    their references immediately (cache-registered pages park in an
    evictable LRU pool instead of the free list),
  - admission is head-first: every wave takes the waiting queue's head
    (the oldest waiter; priority-aware schedulers put the oldest
    interactive waiter there, and admit FIFO within each class). With
    more waiting than a wave admits and than there are free slots, the
    rest of the wave is the oldest waiters of the head's prefill bucket
    among the first ``ADMIT_WINDOW`` (``next_wave``), so that one program
    prefills them; the sequence at place p is admitted within p + 1
    waves, whatever overtakes it.
    Preemption evicts the *youngest* running sequence (its re-prefill
    wastes the least work; priority-aware schedulers prefer batch
    victims).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, Hashable, List, Optional, Tuple

from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.obs.metrics import Histogram
from llmq_tpu.utils.hashing import token_prefix_chain


class OutOfPages(Exception):
    """No free KV pages; caller should preempt or defer."""


# How far into the waiting queue a wave looks for sequences of its head's
# prefill bucket. On the benchmark's own queues (tests/test_admission_replay.py)
# 32 fills 3.5-3.7 of a program's 4 rows and 64 a little more; a worker keeps
# half its slots' count waiting (64 behind 128 slots).
ADMIT_WINDOW = 64


def _ms(seconds: Optional[float]) -> Optional[float]:
    """Histogram percentile (seconds) → rounded ms for stats dicts."""
    return None if seconds is None else round(seconds * 1000.0, 3)


def mixed_token_budget(
    chunk_size: int, decode_rows: int, remaining: int, *, min_tokens: int = 1
) -> int:
    """Prefill positions one piggybacked chunk segment may claim in a
    mixed (decode + prefill) dispatch iteration.

    The per-iteration token budget is ``chunk_size`` (the fused
    executable's fixed chunk width): each decodable row consumes one
    budget token for its own decode position, and the head-of-line
    prefill gets the remainder. A busy batch therefore trickles the
    prompt in small segments (the decode rows' latency is protected),
    while an idle batch prefills at full chunk width. ``min_tokens``
    floors the segment so prefill always makes progress even when
    decode_rows >= chunk_size; the segment can never exceed the chunk
    row's physical width (``chunk_size``) or the prompt's ``remaining``
    positions. Returns 0 when nothing remains."""
    if remaining <= 0:
        return 0
    return min(remaining, max(chunk_size - decode_rows, min_tokens), chunk_size)


class PageAllocator:
    """Refcounted free-list allocator over the physical KV page pool.

    Page 0 is reserved: masked/padded token positions scatter there
    (``ops/attention.py::write_kv_pages``), so it must never back live data.

    Three page states:
      - *allocated*: refcount ≥ 1 (prefix-cached pages shared by several
        sequences carry one reference per sharer);
      - *cached*: refcount dropped to 0 but the page was registered as
        evictable (its KV content may be reused by a future prefix
        match) — it is reclaimed lazily, LRU, under pool pressure;
      - *free*: on the free list.
    Without prefix caching every page has refcount 1 and the allocator
    degenerates to the plain free list.
    """

    def __init__(self, num_pages: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        # LRU order of refcount-0 evictable pages (dict = ordered set).
        self._cached: Dict[int, None] = {}
        # Called with the page id when a cached page is evicted, so the
        # prefix cache can drop entries pointing at it.
        self.on_evict = None

    @property
    def available(self) -> int:
        return len(self._free) + len(self._cached)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int = 1) -> List[int]:
        """Allocate n fresh pages atomically; raises OutOfPages if short
        (evicting cached pages as needed, oldest first)."""
        if n > self.available:
            raise OutOfPages(f"want {n} pages, have {self.available}")
        while len(self._free) < n:
            self._evict_one()
        pages = [self._free.pop() for _ in range(n)]
        for page in pages:
            self._refs[page] = 1
        return pages

    def share(self, page: int) -> None:
        """Take an additional reference on an allocated or cached page."""
        rc = self._refs.get(page)
        if rc is None:
            raise ValueError(f"share of unallocated page {page}")
        if rc == 0:  # revive from the evictable pool
            del self._cached[page]
        self._refs[page] = rc + 1

    def free(self, pages: List[int], *, cacheable: bool = False) -> None:
        """Drop one reference per page. At refcount 0 the page returns to
        the free list — or parks in the evictable LRU pool when
        ``cacheable`` (its content may serve a future prefix match)."""
        for page in pages:
            rc = self._refs.get(page)
            if rc is None or rc < 1:
                raise ValueError(f"double-free or foreign page {page}")
            if rc > 1:
                self._refs[page] = rc - 1
                continue
            if cacheable:
                self._refs[page] = 0
                self._cached[page] = None
            else:
                del self._refs[page]
                self._free.append(page)

    def drop_cached(self, page: int) -> None:
        """Forget a cached (refcount-0) page, returning it to the free
        list. Notifies ``on_evict`` like pressure eviction does, so the
        prefix cache drops the hashes pointing at it — a silently freed
        page whose hash survived would hand its next owner's content to
        strangers."""
        if page in self._cached:
            del self._cached[page]
            del self._refs[page]
            if self.on_evict is not None:
                self.on_evict(page)
            self._free.append(page)

    def _evict_one(self) -> None:
        page = next(iter(self._cached))  # oldest
        del self._cached[page]
        del self._refs[page]
        if self.on_evict is not None:
            self.on_evict(page)
        self._free.append(page)


@dataclasses.dataclass
class Sequence:
    """One request's generation state (host side)."""

    rid: str
    prompt_ids: List[int]
    params: SamplingParams
    output_ids: List[int] = dataclasses.field(default_factory=list)
    pages: List[int] = dataclasses.field(default_factory=list)
    # SLO class ("interactive" | "batch"). Under a priority-aware
    # scheduler interactive sequences are admitted first and may
    # preempt batch victims; it never changes a sequence's own token
    # stream (greedy parity with priority off holds per request).
    priority: str = "batch"
    # Prefix caching: leading prompt positions whose KV is already in the
    # (shared) leading pages — prefill starts at prefix_len. cacheable_pages
    # counts the leading pages registered in the prefix cache (they park
    # in the evictable pool instead of the free list when released).
    prefix_len: int = 0
    cacheable_pages: int = 0
    # Bumped on every preemption: in-flight device results snapshotted
    # under an older epoch must not be appended after re-admission.
    epoch: int = 0
    slot: int = -1
    admitted_at: int = -1  # scheduler tick of (last) admission, for LIFO preempt
    preempt_count: int = 0
    prefilled: bool = False  # KV cache holds this sequence (engine sets it)
    # Disaggregated prefill role: stop after the prompt phase — the first
    # sampled token is discarded, the prompt KV is snapshotted, and the
    # sequence finishes with finish_reason="prefill_done" so the worker
    # hands it to the decode pool (which re-samples that token from the
    # same key chain, bit-identically).
    prefill_only: bool = False
    # Wall-clock (time.time()) deadline, or None. The engine's sweep
    # expires waiting/running sequences past it between decode steps with
    # finish_reason="deadline_exceeded"; the worker dead-letters those.
    deadline_at: Optional[float] = None
    finish_reason: Optional[str] = None
    finish_text: Optional[str] = None  # pre-truncated text on stop-string hit
    # Incremental detokenization cache (engine-owned, stop-string
    # requests only): ``detok_text`` is the decoded text of
    # ``output_ids[:detok_len]``. The engine keeps the cached head a
    # safe token margin behind the end, so per-token stop-string checks
    # decode only the short tail instead of re-decoding the output.
    # Survives preemption (output_ids are kept, so the prefix decode is
    # still valid); the engine resets it whenever output_ids are
    # truncated past detok_len.
    detok_len: int = 0
    detok_text: str = ""
    # Host-held KV pages awaiting re-insertion (a snapshot.KVRestore).
    # Set by swap-to-host preemption and by insert_request; consumed at
    # admission — the engine scatters the pages back instead of
    # re-prefilling. None = re-prefill from prompt+output as usual.
    restore: Optional[Any] = None
    # Host-tier prefix promotion: [(page, chain_hash, PrefixEntry), ...]
    # assigned at admission when the host prefix store extends the
    # device-cache match. The engine inserts the entries' KV into the
    # listed pages before this sequence's first dispatch and clears the
    # field; prefix_len already counts these positions.
    host_restore: Optional[List[Any]] = None
    # Host-side lifecycle stamps (time.monotonic(); 0.0 = not yet).
    # These feed the queue-wait / TTFT / ITL histograms and the
    # per-request trace record; they never influence scheduling.
    t_enqueue: float = 0.0
    t_admit: float = 0.0
    t_prefill_start: float = 0.0
    t_first_token: float = 0.0
    t_last_token: float = 0.0
    t_preempt: float = 0.0
    # AsyncEngine.generate's entry, on the caller's thread (0.0 for a
    # sequence that came another way).
    t_submit: float = 0.0

    @property
    def num_tokens(self) -> int:
        return len(self.prompt_ids) + len(self.output_ids)

    @property
    def last_token(self) -> int:
        return self.output_ids[-1] if self.output_ids else self.prompt_ids[-1]


@dataclasses.dataclass
class SchedulerConfig:
    max_num_seqs: int
    num_pages: int
    page_size: int
    max_model_len: int
    # Automatic prefix caching: sequences sharing full leading prompt
    # pages (position-identical, so RoPE'd K matches) reuse them via
    # refcounts instead of recomputing — the engine then prefills only
    # from prefix_len on (requires chunked prefill).
    enable_prefix_caching: bool = False
    # SLO-aware admission: interactive waiters are admitted before batch
    # waiters (FIFO within each class). Off (default) = pure FIFO, the
    # exact pre-priority order, and stats() omits the per-class keys so
    # default payloads stay byte-identical.
    priority_aware: bool = False

    @property
    def pages_per_seq(self) -> int:
        return -(-self.max_model_len // self.page_size)  # ceil


class Scheduler:
    """Slot/page bookkeeping for the continuous batch."""

    def __init__(self, config: SchedulerConfig, layout) -> None:
        self.config = config
        # The cache's row map (``models/cache.CacheLayout``): the places a
        # block table needs for the rows of positions ``[start, stop)``.
        self._table_pages = layout.table_pages
        self.allocator = PageAllocator(config.num_pages)
        self.slots: List[Optional[Sequence]] = [None] * config.max_num_seqs
        self.waiting: Deque[Sequence] = deque()
        self.running: Dict[str, Sequence] = {}
        self._tick = 0
        # Prefix cache: chain-hash of the prompt's leading full pages →
        # page id holding that KV, plus the reverse map for eviction.
        self._prefix_cache: Dict[bytes, int] = {}
        self._prefix_rev: Dict[int, List[bytes]] = {}
        self.prefix_hits = 0  # pages reused via the cache (stats)
        self.prefix_misses = 0  # full prompt pages that had to prefill
        self.preemptions = 0  # recompute preemptions (stats)
        # Called as on_preempt(seq, defer_pages) at the top of preempt(),
        # before the epoch bump and page release (engine swap-to-host).
        self.on_preempt = None
        # Host prefix tier hooks (engine-owned; both optional):
        #   on_demote(page, hashes) — fires when a cache-registered page
        #     is evicted, while its device content is still intact, with
        #     the chain hashes that pointed at it (park the KV in host
        #     RAM instead of losing it);
        #   host_lookup(hashes) — returns the longest contiguous
        #     [(hash, entry), ...] run the host tier holds for a chain
        #     tail the device cache missed.
        self.on_demote = None
        self.host_lookup = None
        self._suppress_demote = False  # invalidation must not demote
        self.allocator.on_evict = self._drop_page_hashes
        # Per-scheduler latency histograms (the owning engine registers
        # them into the process-wide registry for /metrics export).
        self.queue_wait_hist = Histogram(
            "llmq_queue_wait_seconds",
            "Enqueue-to-first-admission wait per request",
        )
        self.preempt_delay_hist = Histogram(
            "llmq_preemption_delay_seconds",
            "Preemption-to-readmission delay per recompute preemption",
        )

    # --- prefix caching ---------------------------------------------------
    def _prefix_hashes(self, prompt_ids: List[int]) -> List[bytes]:
        """Chain digests of the prompt's leading FULL pages
        (utils/hashing.py: the fleet-wide KV page identity — the host
        prefix store and cross-worker shipping key on the same bytes)."""
        return token_prefix_chain(prompt_ids, self.config.page_size)

    def _match_prefix(self, prompt_ids: List[int]) -> List[int]:
        """Longest run of cached pages matching the prompt's hash chain."""
        return self._match_prefix_hashes(self._prefix_hashes(prompt_ids))

    def _match_prefix_hashes(self, hashes: List[bytes]) -> List[int]:
        matched: List[int] = []
        for h in hashes:
            page = self._prefix_cache.get(h)
            if page is None:
                break
            matched.append(page)
        return matched

    def register_prefix(self, seq: Sequence) -> None:
        """Offer a prefilled sequence's full prompt pages to the cache.
        First writer wins per hash; only the leading pages that ARE the
        cache's pages count as cacheable on release (a losing page would
        park in the evictable pool with no hash pointing at it)."""
        if not self.config.enable_prefix_caching:
            return
        cacheable = 0
        for i, h in enumerate(self._prefix_hashes(seq.prompt_ids)):
            if i >= len(seq.pages):
                break
            page = self._prefix_cache.get(h)
            if page is None:
                self._prefix_cache[h] = seq.pages[i]
                self._prefix_rev.setdefault(seq.pages[i], []).append(h)
                cacheable = i + 1
            elif page == seq.pages[i]:
                cacheable = i + 1  # re-admission re-matched the same page
            else:
                break  # a different page already serves this chain
        seq.cacheable_pages = cacheable

    def _drop_page_hashes(self, page: int) -> None:
        hashes = [
            h
            for h in self._prefix_rev.pop(page, [])
            if self._prefix_cache.get(h) == page
        ]
        for h in hashes:
            del self._prefix_cache[h]
        # Demote to the host tier while the page's device content is
        # still intact (on_evict fires before the page hits the free
        # list) — unless invalidation is in flight, in which case the
        # content is exactly what must NOT survive.
        if hashes and self.on_demote is not None and not self._suppress_demote:
            self.on_demote(page, hashes)

    def invalidate_prefix_cache(self) -> None:
        """Forget every cached prefix and return the parked pages to the
        free list — required when the engine rebuilds the KV buffers
        (after a failed step): the page ids would otherwise still match
        hash chains while pointing at zeroed content. Demotion is
        suppressed throughout — parking a page from an aborted/zeroed
        buffer would re-serve poisoned KV from host RAM later."""
        self._suppress_demote = True
        try:
            for page in list(self.allocator._cached):
                self.allocator.drop_cached(page)
        finally:
            self._suppress_demote = False
        self._prefix_cache.clear()
        self._prefix_rev.clear()
        for seq in list(self.running.values()) + list(self.waiting):
            seq.cacheable_pages = 0  # nothing may re-park as cached

    # --- queue ------------------------------------------------------------
    def add(self, seq: Sequence) -> None:
        # Overlong prompts are truncated to fit the context window, and
        # generation is capped so prompt+output never exceeds max_model_len
        # (vLLM max_model_len parity); finish_reason=length surfaces it.
        limit = self.config.max_model_len - 1
        if len(seq.prompt_ids) > limit:
            seq.prompt_ids = seq.prompt_ids[:limit]
        if seq.num_tokens + seq.params.max_tokens > self.config.max_model_len:
            seq.params.max_tokens = max(
                0, self.config.max_model_len - seq.num_tokens
            )
        if self._pages_needed(seq.num_tokens) > self.config.num_pages - 1:
            # Even an empty pool could never hold the prompt: reject now —
            # otherwise admit() retries forever and the engine livelocks.
            raise ValueError(
                f"prompt of {seq.num_tokens} tokens needs "
                f"{self._pages_needed(seq.num_tokens)} KV pages; pool has "
                f"{self.config.num_pages - 1}"
            )
        if seq.t_enqueue == 0.0:
            seq.t_enqueue = time.monotonic()
        self.waiting.append(seq)

    def add_restored(self, seq: Sequence) -> None:
        """Enqueue a snapshot-restored sequence.

        Unlike :meth:`add`, the prompt is never truncated — the snapshot's
        KV and key chain cover exactly these positions, so silently
        shortening them would desynchronize state — and the generation cap
        is re-derived from the PROMPT length alone. Running the restored
        sequence through add()'s cap (which counts ``num_tokens``, i.e.
        prompt PLUS already-generated output) would tighten ``max_tokens``
        below what the source engine granted and could instantly
        length-finish a request that still had budget.
        """
        if seq.num_tokens >= self.config.max_model_len:
            raise ValueError(
                f"restored request {seq.rid!r} holds {seq.num_tokens} "
                f"tokens; this engine's window is {self.config.max_model_len}"
            )
        window = self.config.max_model_len - len(seq.prompt_ids)
        if seq.params.max_tokens > window:
            seq.params.max_tokens = window
        if self._pages_needed(seq.num_tokens) > self.config.num_pages - 1:
            raise ValueError(
                f"restored request {seq.rid!r} of {seq.num_tokens} tokens "
                f"needs {self._pages_needed(seq.num_tokens)} KV pages; pool "
                f"has {self.config.num_pages - 1}"
            )
        if seq.t_enqueue == 0.0:
            seq.t_enqueue = time.monotonic()
        self.waiting.append(seq)

    @property
    def has_waiting(self) -> bool:
        return bool(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def _pages_needed(self, num_tokens: int) -> int:
        # +1 position of headroom: the decode step writes the *next* token's
        # KV before the host learns the sequence finished. (Under a row
        # map the next token's row is past every row a prefill of
        # ``num_tokens`` writes, so its place is the need.)
        return self._table_pages(num_tokens, num_tokens + 1)

    def pages_for(self, seq: Sequence, num_positions: int) -> int:
        """Pages ``seq`` needs to cover ``num_positions`` KV slots: every
        position a step may still write, from the one its next decode step
        writes (``num_tokens - 1``; the rows before it have their pages)."""
        return self._table_pages(max(seq.num_tokens - 1, 0), num_positions)

    # --- admission --------------------------------------------------------
    def _next_admit_index(self) -> int:
        """Index of the next waiting sequence to admit: FIFO head, unless
        the scheduler is priority-aware and an interactive sequence waits
        anywhere in the queue — then the OLDEST interactive waiter jumps
        the line (FIFO within each class; a preempted interactive
        sequence sits at the head already via appendleft)."""
        if self.config.priority_aware:
            for i, seq in enumerate(self.waiting):
                if seq.priority == "interactive":
                    return i
        return 0

    def next_wave(
        self,
        max_new: Optional[int] = None,
        bucket_of: Optional[Callable[[Sequence], Hashable]] = None,
    ) -> List[int]:
        """Places in the waiting queue of the sequences the next ``admit``
        would take, in the order it takes them (slots and pages allowing).

        ``bucket_of`` names the program that would prefill a sequence (the
        engine's prefill bucket; None for a sequence, or instead of the
        function, where there is none to share). While no more wait than
        ``max_new`` or than there are free slots (nobody has to wait for a
        slot, so there is no order to choose: a server with room, whose
        requests are there for their latency), without a ``bucket_of``, and
        while a priority-aware scheduler has an interactive waiter, the
        wave is FIFO (interactive waiters first). Otherwise it is the head
        plus the oldest waiters among the first ``ADMIT_WINDOW`` whose
        bucket is the head's, in queue order. The head is in every wave,
        so nothing starves: the sequence at place p is admitted within
        p + 1 waves."""
        n = len(self.waiting)
        if max_new is not None:
            n = min(n, max_new)
        if n == 0:
            return []
        first = self.waiting[self._next_admit_index()]
        if self.config.priority_aware and first.priority == "interactive":
            wave = list(itertools.islice(
                (i for i, seq in enumerate(self.waiting)
                 if seq.priority == "interactive"), n
            ))
            rest = (i for i in range(len(self.waiting)) if i not in wave)
            return wave + list(itertools.islice(rest, n - len(wave)))
        # From here the head is the queue's first.
        key = None
        if bucket_of is not None and len(self.waiting) > max(
            n, self.slots.count(None)
        ):
            key = bucket_of(first)
        if key is None:
            return list(range(n))
        wave = [0]
        for i in range(1, min(ADMIT_WINDOW, len(self.waiting))):
            if len(wave) == n:
                break
            if bucket_of(self.waiting[i]) == key:
                wave.append(i)
        return wave

    def admit(
        self,
        max_new: Optional[int] = None,
        bucket_of: Optional[Callable[[Sequence], Hashable]] = None,
    ) -> List[Sequence]:
        """Move waiting sequences into free slots while pages allow.

        Returns the newly admitted sequences (their ``slot`` and ``pages``
        set); each needs a prefill pass before joining decode. Admission
        takes ``next_wave``: the head first (the oldest waiter; the oldest
        interactive one under a priority-aware scheduler), then FIFO or,
        with more than ``max_new`` waiting, the head's bucket mates. It
        stops at the first sequence the pool has no pages for.
        """
        admitted: List[Sequence] = []
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        taken: List[int] = []
        for idx in self.next_wave(max_new, bucket_of):
            if not free_slots:
                break
            seq = self.waiting[idx]
            matched: List[int] = []
            host: List[Any] = []
            hashes: List[bytes] = []
            if self.config.enable_prefix_caching:
                hashes = self._prefix_hashes(seq.prompt_ids)
                matched = self._match_prefix_hashes(hashes)
                # Share FIRST: matched refcount-0 pages leave the
                # evictable pool, so the fresh alloc below cannot evict
                # them out from under us.
                for page in matched:
                    self.allocator.share(page)
                # Extend the device match from the host tier (snapshot
                # restores bring their own KV — don't double-restore).
                if self.host_lookup is not None and seq.restore is None:
                    host = self.host_lookup(hashes[len(matched) :])
            need = self._pages_needed(seq.num_tokens) - len(matched)
            try:
                fresh = self.allocator.alloc(need) if need > 0 else []
            except OutOfPages:
                for page in matched:  # undo the shares; stay cacheable
                    self.allocator.free([page], cacheable=True)
                break
            seq.pages = matched + fresh
            if host:
                # Promoted pages come out of the fresh allocation (the
                # chain always has at least one more page than its full
                # prefix pages, so fresh covers them). Register their
                # hashes NOW: the engine inserts the host KV before this
                # sequence's first dispatch, so later admits may share.
                promoted = fresh[: len(host)]
                seq.host_restore = [
                    (page, h, entry)
                    for page, (h, entry) in zip(promoted, host)
                ]
                for page, h, _ in seq.host_restore:
                    self._prefix_cache[h] = page
                    self._prefix_rev.setdefault(page, []).append(h)
            n_reused = len(matched) + len(host)
            seq.prefix_len = n_reused * self.config.page_size
            # Matched pages are cache-registered by construction; they
            # must park back in the evictable pool on release even if
            # this sequence never re-registers (e.g. finishes early).
            seq.cacheable_pages = n_reused
            self.prefix_hits += n_reused
            self.prefix_misses += len(hashes) - n_reused
            taken.append(idx)
            seq.slot = free_slots.pop(0)
            seq.admitted_at = self._tick
            self._tick += 1
            now = time.monotonic()
            if seq.t_preempt > 0.0:  # re-admission after a preemption
                self.preempt_delay_hist.observe(now - seq.t_preempt)
                seq.t_preempt = 0.0
            elif seq.t_enqueue > 0.0 and seq.t_admit == 0.0:
                self.queue_wait_hist.observe(now - seq.t_enqueue)
            seq.t_admit = now
            self.slots[seq.slot] = seq
            self.running[seq.rid] = seq
            admitted.append(seq)
        for idx in sorted(taken, reverse=True):
            del self.waiting[idx]
        return admitted

    # --- decode-step bookkeeping -----------------------------------------
    def append_token(self, seq: Sequence, token: int) -> None:
        """Record a generated token, growing the page map as it crosses a
        page boundary. May preempt *other* sequences to find a page; raises
        OutOfPages only if even preemption can't help (seq is last alive)."""
        seq.output_ids.append(token)
        self.ensure_pages(seq, seq.num_tokens + 1)

    def ensure_pages(
        self,
        seq: Sequence,
        num_positions: int,
        *,
        allow_preempt: bool = True,
        preemptible=None,
    ) -> None:
        """Grow ``seq``'s page map to cover ``num_positions`` KV slots
        (capped at the per-sequence maximum). The engine's run-ahead
        pipeline calls this *at dispatch time* with a lookahead, so pages
        always exist on-device before the step that writes them — with
        fused decode blocks the lookahead is measured in blocks of
        ``decode_block`` positions (every in-flight dispatch may write K
        KV rows per sequence before the host sees any of its tokens), so
        each block's full K positions are pre-reserved here. Speculative
        decoding multiplies that per-iteration demand by spec_tokens+1:
        a verify step writes KV for EVERY candidate position whether or
        not it is accepted (rejected writes are simply overwritten
        later), so the engine's lookahead covers
        ``(pending + 1) * (decode_block * (spec_tokens + 1)) + 1``
        positions; preemption and epoch semantics are unchanged. May
        preempt other sequences (unless ``allow_preempt`` is off — the
        engine forbids it while steps are in flight, because a victim's
        freed pages could still be written); ``preemptible`` optionally
        filters victims (the engine excludes mid-prefill sequences, whose
        in-flight chunk loop would keep writing into freed pages); raises
        OutOfPages otherwise."""
        cap = self.config.pages_per_seq * self.config.page_size
        num_positions = min(num_positions, cap)
        while self.pages_for(seq, num_positions) > len(seq.pages):
            try:
                seq.pages.extend(self.allocator.alloc(1))
            except OutOfPages:
                if not allow_preempt:
                    raise
                victim = self._youngest_running(
                    exclude=seq.rid, preemptible=preemptible
                )
                if victim is None:
                    raise
                self.preempt(victim)

    def _youngest_running(
        self, exclude: str, preemptible=None
    ) -> Optional[Sequence]:
        candidates = [
            s
            for s in self.running.values()
            if s.rid != exclude and (preemptible is None or preemptible(s))
        ]
        if not candidates:
            return None
        if self.config.priority_aware:
            # Page pressure evicts batch work before interactive work:
            # an interactive victim pays its whole SLO back in re-prefill.
            batch = [s for s in candidates if s.priority != "interactive"]
            if batch:
                candidates = batch
        return max(candidates, key=lambda s: s.admitted_at)

    def preempt(
        self, seq: Sequence, *, defer_pages: bool = False
    ) -> Tuple[List[int], int]:
        """Evict a running sequence back to the waiting queue (head, so it
        resumes first). Its generated tokens are kept; re-admission
        re-prefills prompt+generated to rebuild the KV cache. With
        ``defer_pages`` (self-preemption while steps are in flight) the
        pages are detached and returned like ``finish(defer_pages=True)``
        instead of freed — the engine releases them at the watermark."""
        # Engine hook (swap-to-host preemption): fires while the victim
        # still holds its pages and its prefilled flag — for an immediate
        # (non-deferred, pipeline-drained) preemption the engine gathers
        # the KV to host right here, before the pages hit the free list.
        if self.on_preempt is not None:
            self.on_preempt(seq, defer_pages)
        seq.epoch += 1  # stale in-flight results must not resurface
        pages, cacheable = [], 0
        if defer_pages:
            pages = seq.pages
            cacheable = min(seq.cacheable_pages, len(pages))
            seq.pages = []
            seq.cacheable_pages = 0
        self._release(seq)
        seq.preempt_count += 1
        seq.t_preempt = time.monotonic()
        self.preemptions += 1
        seq.prefilled = False  # KV is gone; re-admission re-prefills
        self.waiting.appendleft(seq)
        return pages, cacheable

    def finish(
        self, seq: Sequence, reason: str, *, defer_pages: bool = False
    ) -> Tuple[List[int], int]:
        """Finish a sequence. With ``defer_pages`` the slot is released
        but the KV pages are detached and *returned* (with the count of
        leading cache-registered pages) instead of freed — the engine
        holds them until every in-flight device step that may still write
        them has completed, then calls ``release_pages``."""
        seq.finish_reason = reason
        pages, cacheable = [], 0
        if defer_pages:
            pages = seq.pages
            cacheable = min(seq.cacheable_pages, len(pages))
            seq.pages = []
        self._release(seq)
        return pages, cacheable

    def release_pages(self, pages: List[int], cacheable: int = 0) -> None:
        """Return deferred pages (from ``finish(defer_pages=True)``); the
        leading ``cacheable`` pages park in the evictable prefix pool."""
        if cacheable:
            self.allocator.free(pages[:cacheable], cacheable=True)
        if pages[cacheable:]:
            self.allocator.free(pages[cacheable:])

    def _release(self, seq: Sequence) -> None:
        if seq.slot >= 0:
            self.slots[seq.slot] = None
            seq.slot = -1
        self.running.pop(seq.rid, None)
        if seq.pages:
            self.release_pages(
                seq.pages, min(seq.cacheable_pages, len(seq.pages))
            )
            seq.pages = []

    # --- introspection ----------------------------------------------------
    def stats(self) -> Dict[str, float]:
        total_pages = self.config.num_pages - 1
        out = {
            "running": len(self.running),
            "waiting": len(self.waiting),
            "slots": self.config.max_num_seqs,
            "batch_occupancy": len(self.running) / self.config.max_num_seqs,
            "kv_page_utilization": (total_pages - self.allocator.available)
            / max(1, total_pages),
            "preemptions": self.preemptions,
        }
        if self.config.priority_aware:
            out["waiting_interactive"] = sum(
                1 for s in self.waiting if s.priority == "interactive"
            )
            out["running_interactive"] = sum(
                1
                for s in self.running.values()
                if s.priority == "interactive"
            )
        qw = self.queue_wait_hist
        pd = self.preempt_delay_hist
        out["queue_wait_p50_ms"] = _ms(qw.percentile(0.50))
        out["queue_wait_p95_ms"] = _ms(qw.percentile(0.95))
        out["preemption_delay_p50_ms"] = _ms(pd.percentile(0.50))
        if self.config.enable_prefix_caching:
            out["prefix_cache_hit_pages"] = self.prefix_hits
            out["prefix_cache_miss_pages"] = self.prefix_misses
            seen = self.prefix_hits + self.prefix_misses
            out["prefix_hit_rate"] = (
                self.prefix_hits / seen if seen else 0.0
            )
        return out

    def check_invariants(self) -> None:
        """Debug/test hook: assert the documented invariants."""
        counts: Dict[int, int] = {}
        for seq in self.running.values():
            assert self.slots[seq.slot] is seq
            assert self._pages_needed(seq.num_tokens) <= len(seq.pages)
            for page in seq.pages:
                counts[page] = counts.get(page, 0) + 1
        assert 0 not in counts, "scratch page handed out"
        for page, n in counts.items():
            rc = self.allocator.refcount(page)
            assert rc == n, f"page {page}: refcount {rc} != {n} owners"
        if not self.config.enable_prefix_caching:
            assert all(n == 1 for n in counts.values()), "page owned twice"
        assert (
            len(counts) + self.allocator.available
            == self.config.num_pages - 1
        )
