"""Scheduler/allocator invariants (SURVEY.md §4: property tests on
scheduler invariants replace vLLM's internal scheduler tests)."""

import random

import pytest

from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.scheduler import (
    OutOfPages,
    PageAllocator,
    Scheduler,
    SchedulerConfig,
    Sequence,
)
from llmq_tpu.models.cache import cache_layout
from llmq_tpu.models.presets import get_preset


def make_seq(rid, prompt_len=10, max_tokens=100):
    return Sequence(
        rid=rid,
        prompt_ids=list(range(1, prompt_len + 1)),
        params=SamplingParams(max_tokens=max_tokens),
    )


def make_sched(slots=4, pages=32, page_size=4, max_len=64):
    return Scheduler(
        SchedulerConfig(
            max_num_seqs=slots,
            num_pages=pages,
            page_size=page_size,
            max_model_len=max_len,
        ),
        cache_layout(
            get_preset("tiny"), page_size=page_size, max_model_len=max_len,
            max_num_seqs=slots,
        ),
    )


class TestPageAllocator:
    def test_page_zero_reserved(self):
        alloc = PageAllocator(8)
        pages = alloc.alloc(7)
        assert 0 not in pages
        assert sorted(pages) == list(range(1, 8))

    def test_exhaustion_is_atomic(self):
        alloc = PageAllocator(4)
        alloc.alloc(2)
        with pytest.raises(OutOfPages):
            alloc.alloc(2)  # only 1 left
        assert alloc.available == 1

    def test_free_and_reuse(self):
        alloc = PageAllocator(4)
        pages = alloc.alloc(3)
        alloc.free(pages)
        assert alloc.available == 3
        assert sorted(alloc.alloc(3)) == sorted(pages)

    def test_double_free_rejected(self):
        alloc = PageAllocator(4)
        pages = alloc.alloc(1)
        alloc.free(pages)
        with pytest.raises(ValueError):
            alloc.free(pages)


class TestAdmission:
    def test_fifo_admission_fills_slots(self):
        sched = make_sched(slots=2)
        for i in range(3):
            sched.add(make_seq(f"r{i}"))
        admitted = sched.admit()
        assert [s.rid for s in admitted] == ["r0", "r1"]
        assert sched.num_running == 2
        assert len(sched.waiting) == 1
        sched.check_invariants()

    def test_admission_blocked_by_pages(self):
        # 7 usable pages, each 10-token prompt needs ceil(11/4)=3 pages.
        sched = make_sched(slots=4, pages=8)
        for i in range(3):
            sched.add(make_seq(f"r{i}"))
        admitted = sched.admit()
        assert len(admitted) == 2  # third would need a 3rd allocation of 3
        sched.check_invariants()

    def test_prompt_truncated_to_model_len(self):
        sched = make_sched(max_len=16)
        seq = make_seq("r0", prompt_len=100)
        sched.add(seq)
        assert len(seq.prompt_ids) == 15
        assert seq.params.max_tokens == 1

    def test_max_tokens_capped(self):
        sched = make_sched(max_len=32)
        seq = make_seq("r0", prompt_len=10, max_tokens=1000)
        sched.add(seq)
        assert seq.params.max_tokens == 22


class TestDecodeGrowth:
    def test_page_growth_on_boundary(self):
        sched = make_sched(page_size=4)
        seq = make_seq("r0", prompt_len=3)
        sched.add(seq)
        sched.admit()
        assert len(seq.pages) == 1  # 3+1 fits one page
        sched.append_token(seq, 42)  # now 4+1 → needs 2 pages
        assert len(seq.pages) == 2
        sched.check_invariants()

    def test_finish_releases_everything(self):
        sched = make_sched()
        seq = make_seq("r0")
        sched.add(seq)
        sched.admit()
        before = sched.allocator.available
        sched.finish(seq, "stop")
        assert sched.num_running == 0
        assert sched.allocator.available > before
        assert seq.slot == -1
        sched.check_invariants()

    def test_preemption_evicts_youngest(self):
        # Pool sized so two sequences fit, but growth forces eviction.
        sched = make_sched(slots=2, pages=7, page_size=4, max_len=64)
        a, b = make_seq("a", prompt_len=10), make_seq("b", prompt_len=10)
        sched.add(a)
        sched.add(b)
        assert len(sched.admit()) == 2  # 3 pages each, 6 of 6 used
        # a crosses a page boundary → must preempt b (younger).
        for _ in range(2):
            sched.append_token(a, 7)
        assert "b" not in sched.running
        assert sched.waiting[0].rid == "b"
        assert b.preempt_count == 1
        assert b.pages == [] and b.slot == -1
        sched.check_invariants()

    def test_out_of_pages_when_alone(self):
        sched = make_sched(slots=1, pages=3, page_size=2, max_len=64)
        seq = make_seq("r0", prompt_len=3)  # needs 2 pages, uses both
        sched.add(seq)
        sched.admit()
        with pytest.raises(OutOfPages):
            for _ in range(10):
                sched.append_token(seq, 1)


def test_randomized_invariants():
    """Fuzz admission/growth/finish/preempt; invariants must always hold."""
    rng = random.Random(0)
    sched = make_sched(slots=8, pages=64, page_size=4, max_len=96)
    next_id = 0
    live = []
    for _ in range(500):
        op = rng.random()
        if op < 0.3:
            seq = make_seq(f"s{next_id}", prompt_len=rng.randint(1, 40))
            next_id += 1
            sched.add(seq)
        elif op < 0.5:
            for s in sched.admit():
                live.append(s)
        elif op < 0.85 and live:
            seq = rng.choice(live)
            if seq.rid in sched.running:
                try:
                    sched.append_token(seq, rng.randint(0, 100))
                except OutOfPages:
                    pass
                live = [s for s in live if s.rid in sched.running]
        elif live:
            seq = rng.choice(live)
            if seq.rid in sched.running:
                sched.finish(seq, "stop")
            live.remove(seq)
        sched.check_invariants()


class TestPrefixCaching:
    def _sched(self, **over):
        from llmq_tpu.engine.scheduler import Scheduler, SchedulerConfig

        cfg = dict(
            max_num_seqs=4, num_pages=20, page_size=4, max_model_len=32,
            enable_prefix_caching=True,
        )
        cfg.update(over)
        layout = cache_layout(
            get_preset("tiny"), page_size=cfg["page_size"],
            max_model_len=cfg["max_model_len"], max_num_seqs=cfg["max_num_seqs"],
        )
        return Scheduler(SchedulerConfig(**cfg), layout)

    def _seq(self, rid, ids, max_tokens=4):
        from llmq_tpu.engine.sampling import SamplingParams
        from llmq_tpu.engine.scheduler import Sequence

        return Sequence(rid=rid, prompt_ids=list(ids),
                        params=SamplingParams(max_tokens=max_tokens))

    def test_allocator_refcounts_and_eviction(self):
        from llmq_tpu.engine.scheduler import OutOfPages, PageAllocator

        alloc = PageAllocator(6)  # pages 1..5 usable
        evicted = []
        alloc.on_evict = evicted.append
        a = alloc.alloc(2)
        alloc.share(a[0])
        assert alloc.refcount(a[0]) == 2
        alloc.free([a[0]], cacheable=True)  # rc 2 -> 1, still allocated
        assert alloc.refcount(a[0]) == 1
        alloc.free([a[0]], cacheable=True)  # rc 0 -> evictable pool
        assert alloc.refcount(a[0]) == 0
        assert alloc.available == 4  # 3 free + 1 cached
        alloc.share(a[0])  # revive from the pool
        assert alloc.refcount(a[0]) == 1 and not evicted
        alloc.free([a[0]], cacheable=True)
        alloc.alloc(4)  # forces eviction of the cached page
        assert evicted == [a[0]]
        with pytest.raises(OutOfPages):
            alloc.alloc(1)
        alloc.free([a[1]])
        assert alloc.alloc(1)  # plain free-list reuse

    def test_shared_prefix_pages_and_tail_divergence(self):
        sched = self._sched()
        shared = list(range(100, 109))  # 2 full pages + 1 extra token
        s1 = self._seq("a", shared + [1, 2])
        sched.add(s1)
        sched.admit()
        assert s1.prefix_len == 0  # cold cache
        sched.register_prefix(s1)
        assert s1.cacheable_pages == 2
        s2 = self._seq("b", shared + [7, 8, 9])  # same prefix, new tail
        sched.add(s2)
        sched.admit()
        assert s2.prefix_len == 8  # 2 pages x 4 reused
        assert s2.pages[:2] == s1.pages[:2]
        assert s2.pages[2] != s1.pages[2]  # tails stay private
        assert sched.allocator.refcount(s1.pages[0]) == 2
        sched.check_invariants()
        # releasing one sharer keeps the other's pages valid
        sched.finish(s1, "stop")
        assert sched.allocator.refcount(s2.pages[0]) == 1
        sched.check_invariants()
        # a third request after s1 is gone still hits the cache
        s3 = self._seq("c", shared)
        sched.add(s3)
        sched.admit()
        assert s3.prefix_len == 8
        sched.check_invariants()

    def test_full_page_prompt_keeps_last_position_private(self):
        sched = self._sched()
        ids = list(range(50, 58))  # exactly 2 full pages
        s1 = self._seq("a", ids)
        sched.add(s1)
        sched.admit()
        sched.register_prefix(s1)
        assert s1.cacheable_pages == 1  # (8-1)//4: last position recomputed
        s2 = self._seq("b", ids)
        sched.add(s2)
        sched.admit()
        assert s2.prefix_len == 4  # only the first page reused

    def test_cached_pages_survive_release_and_get_evicted_under_pressure(self):
        sched = self._sched(num_pages=8)  # 7 usable
        s1 = self._seq("a", list(range(60, 69)))  # 3 pages (2 full)
        sched.add(s1)
        sched.admit()
        sched.register_prefix(s1)
        sched.finish(s1, "stop")
        assert sched.allocator.available == 7  # 2 cached + 5 free
        s2 = self._seq("b", list(range(60, 69)))
        sched.add(s2)
        sched.admit()
        assert s2.prefix_len == 8  # revived from the evictable pool
        sched.finish(s2, "stop")
        # unrelated demand evicts the cached pages and drops their hashes
        big = self._seq("c", list(range(200, 227)))  # 7 pages
        sched.add(big)
        sched.admit()
        assert big.prefix_len == 0
        sched.check_invariants()
        sched.finish(big, "stop")
        s3 = self._seq("d", list(range(60, 69)))
        sched.add(s3)
        sched.admit()
        assert s3.prefix_len == 0  # cache was invalidated by eviction
        sched.check_invariants()


class TestMixedTokenBudget:
    """Pure token-budget policy for piggyback (mixed) dispatches."""

    def test_idle_batch_gets_full_chunk(self):
        from llmq_tpu.engine.scheduler import mixed_token_budget

        assert mixed_token_budget(256, 0, 1000) == 256

    def test_decode_rows_claim_budget_first(self):
        from llmq_tpu.engine.scheduler import mixed_token_budget

        assert mixed_token_budget(256, 192, 1000) == 64
        assert mixed_token_budget(8, 3, 100) == 5

    def test_min_tokens_floor_guarantees_progress(self):
        from llmq_tpu.engine.scheduler import mixed_token_budget

        # Even a decode batch wider than the chunk leaves the prefill
        # one position per iteration — it must never starve.
        assert mixed_token_budget(8, 8, 100) == 1
        assert mixed_token_budget(8, 500, 100) == 1
        assert mixed_token_budget(8, 500, 100, min_tokens=4) == 4

    def test_capped_by_remaining_and_chunk(self):
        from llmq_tpu.engine.scheduler import mixed_token_budget

        assert mixed_token_budget(256, 0, 10) == 10  # prompt tail
        assert mixed_token_budget(8, 0, 100) == 8  # physical chunk width
        # min_tokens can never push past the chunk row's width.
        assert mixed_token_budget(8, 100, 100, min_tokens=99) == 8

    def test_done_prompt_takes_nothing(self):
        from llmq_tpu.engine.scheduler import mixed_token_budget

        assert mixed_token_budget(256, 5, 0) == 0
        assert mixed_token_budget(256, 5, -3) == 0
