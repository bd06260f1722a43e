"""Which waiting sequences make up an admission wave (Scheduler.next_wave,
Scheduler.admit): the head is in every wave, its mates are the oldest waiters
of its prefill bucket inside the first ADMIT_WINDOW, and with no more waiting
than a wave admits or than slots are free, or no bucket to share, the wave is
FIFO as it always was.
On the CPU, with no model."""

import random

import pytest

from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.scheduler import (
    ADMIT_WINDOW,
    Scheduler,
    SchedulerConfig,
    Sequence,
)
from llmq_tpu.models.cache import cache_layout
from llmq_tpu.models.presets import get_preset

WAVE = 4
LADDER = (16, 32, 64, 128)


def bucket_of(seq):
    return next(b for b in LADDER if b >= seq.num_tokens)


def make_seq(rid, prompt_len, priority="batch"):
    return Sequence(
        rid=rid,
        prompt_ids=[1] * prompt_len,
        params=SamplingParams(max_tokens=4),
        priority=priority,
    )


def make_sched(slots=4, pages=4096, priority_aware=False):
    return Scheduler(
        SchedulerConfig(
            max_num_seqs=slots,
            num_pages=pages,
            page_size=8,
            max_model_len=128,
            priority_aware=priority_aware,
        ),
        cache_layout(
            get_preset("tiny"), page_size=8, max_model_len=128, max_num_seqs=slots
        ),
    )


def fill(sched, lengths, priorities=None):
    for i, n in enumerate(lengths):
        sched.add(make_seq(f"r{i}", n, (priorities or {}).get(i, "batch")))


def fifo_wave(sched, max_new):
    """The admission order before waves were grouped: the scheduler's head,
    again and again."""
    waiting = list(sched.waiting)
    out = []
    while waiting and len(out) < max_new:
        idx = 0
        if sched.config.priority_aware:
            idx = next(
                (i for i, s in enumerate(waiting) if s.priority == "interactive"), 0
            )
        out.append(waiting.pop(idx).rid)
    return out


def rids(seqs):
    return [s.rid for s in seqs]


def admit_and_finish(sched, key=bucket_of):
    """One wave, run to its end at once: its slots are free for the next."""
    wave = sched.admit(WAVE, key)
    for seq in wave:
        sched.finish(seq, "stop")
    return rids(wave)


def test_wave_is_the_head_and_the_oldest_of_its_bucket_in_queue_order():
    sched = make_sched()
    #            r0  r1  r2  r3  r4  r5  r6  r7  r8
    fill(sched, [20, 60, 30, 10, 25, 100, 32, 17, 31])
    assert sched.next_wave(WAVE, bucket_of) == [0, 2, 4, 6]
    assert admit_and_finish(sched) == ["r0", "r2", "r4", "r6"]
    # What was overtaken keeps its order, and the next head is the oldest.
    assert rids(sched.waiting) == ["r1", "r3", "r5", "r7", "r8"]
    assert admit_and_finish(sched) == ["r1"]
    sched.check_invariants()


def test_head_with_no_mate_goes_alone():
    sched = make_sched()
    fill(sched, [100, 20, 20, 20, 20, 20])
    assert sched.next_wave(WAVE, bucket_of) == [0]
    assert admit_and_finish(sched) == ["r0"]
    assert admit_and_finish(sched) == ["r1", "r2", "r3", "r4"]


@pytest.mark.parametrize("slots, grouped", [(4, True), (5, True), (6, False), (8, False)])
def test_while_every_waiter_can_have_a_slot_the_wave_is_fifo(slots, grouped):
    """Six wait. With a slot free for each nobody has to wait for a slot, so
    there is no order to choose (a server with room: its requests are there
    for their latency); with fewer, somebody waits anyway and the wave is
    the head's bucket."""
    sched = make_sched(slots=slots)
    fill(sched, [20, 100, 20, 60, 20, 20])
    want = [0, 2, 4, 5] if grouped else [0, 1, 2, 3]
    assert sched.next_wave(WAVE, bucket_of) == want
    assert rids(sched.admit(WAVE, bucket_of)) == [f"r{i}" for i in want]


def test_mates_come_from_the_first_window_only():
    sched = make_sched(slots=4)
    lengths = [20] + [100] * (ADMIT_WINDOW - 1) + [20, 20, 20]
    fill(sched, lengths)
    assert sched.next_wave(WAVE, bucket_of) == [0]
    # One place nearer and the first of them is inside the window.
    del sched.waiting[1]
    assert sched.next_wave(WAVE, bucket_of) == [0, ADMIT_WINDOW - 1]


@pytest.mark.parametrize("priority_aware", [False, True])
@pytest.mark.parametrize("n_waiting", [1, 2, 3, 4])
def test_no_more_waiting_than_a_wave_admits_is_fifo(n_waiting, priority_aware):
    rng = random.Random(n_waiting)
    sched = make_sched(priority_aware=priority_aware)
    fill(
        sched,
        [rng.choice([10, 20, 60, 100]) for _ in range(n_waiting)],
        {n_waiting - 1: "interactive"} if priority_aware else None,
    )
    want = fifo_wave(sched, WAVE)
    assert sched.next_wave(WAVE, bucket_of) == sched.next_wave(WAVE, None)
    assert rids(sched.admit(WAVE, bucket_of)) == want


@pytest.mark.parametrize("max_new", [None, 2, 4])
def test_without_a_bucket_admission_is_fifo(max_new):
    """Chunked, mixed and prefix-cached prefill hand no key."""
    rng = random.Random(7)
    for trial in range(20):
        sched = make_sched(slots=8, priority_aware=bool(trial % 2))
        n = rng.randint(1, 12)
        prio = {i: "interactive" for i in range(n) if rng.random() < 0.3}
        fill(sched, [rng.choice([10, 20, 60, 100]) for _ in range(n)],
             prio if trial % 2 else None)
        want = fifo_wave(sched, 8 if max_new is None else max_new)
        assert rids(sched.admit(max_new)) == want
        sched.check_invariants()


def test_a_sequence_with_no_bucket_takes_and_joins_no_mates():
    """A key of None for one sequence (one that brings its KV with it): as
    the head it goes FIFO, as a waiter it is nobody's mate."""
    sched = make_sched()
    fill(sched, [20] * 7)
    key = lambda seq: None if seq.rid in ("r0", "r3") else bucket_of(seq)
    assert sched.next_wave(WAVE, key) == [0, 1, 2, 3]
    del sched.waiting[0]  # r1 is the head now, r3 sits at place 2
    assert sched.next_wave(WAVE, key) == [0, 1, 3, 4]


def test_interactive_waiters_still_jump_the_line():
    sched = make_sched(priority_aware=True)
    fill(sched, [20, 20, 20, 100, 20, 20, 60, 20],
         {3: "interactive", 6: "interactive"})
    # While an interactive request waits the wave is the one it always was:
    # interactive first, oldest first, then FIFO, whatever the buckets.
    want = fifo_wave(sched, WAVE)
    assert want == ["r3", "r6", "r0", "r1"]
    assert admit_and_finish(sched) == want
    # With none waiting the batch class is grouped like any queue.
    fill(sched, [100, 20, 60])
    assert rids(sched.waiting)[:3] == ["r2", "r4", "r5"]
    assert admit_and_finish(sched) == ["r2", "r4", "r5", "r7"]


def test_a_preempted_sequence_at_the_front_goes_first():
    sched = make_sched()
    fill(sched, [100, 20, 20, 20, 20, 20, 60])
    (victim,) = sched.admit(1, bucket_of)
    assert victim.rid == "r0"
    sched.preempt(victim)
    assert sched.waiting[0] is victim
    wave = sched.admit(WAVE, bucket_of)
    assert wave[0] is victim and len(wave) == 1
    sched.check_invariants()


def test_a_wave_stops_at_out_of_pages_and_loses_nothing():
    # 20 tokens need ceil(21 / 8) = 3 pages; 8 usable pages hold two.
    sched = make_sched(pages=9)
    fill(sched, [20, 50, 20, 20, 60, 20])
    admitted = sched.admit(WAVE, bucket_of)
    assert rids(admitted) == ["r0", "r2"]
    assert rids(sched.waiting) == ["r1", "r3", "r4", "r5"]
    sched.check_invariants()
    for seq in admitted:
        sched.finish(seq, "stop")
    # r1 takes 7 of the 8 pages; its mate r4 stays where it was.
    assert rids(sched.admit(WAVE, bucket_of)) == ["r1"]
    assert rids(sched.waiting) == ["r3", "r4", "r5"]
    sched.check_invariants()


def test_a_wave_stops_at_the_free_slots():
    sched = make_sched(slots=2)
    fill(sched, [20, 100, 20, 20, 60, 20])
    assert rids(sched.admit(WAVE, bucket_of)) == ["r0", "r2"]
    assert rids(sched.waiting) == ["r1", "r3", "r4", "r5"]


@pytest.mark.parametrize("seed", range(8))
def test_place_p_is_admitted_within_p_plus_one_waves(seed):
    """No starvation: the head is in every wave, so whatever overtakes it a
    sequence is the head after as many waves as stood in front of it. Over
    random queues, with arrivals between waves, slots that come and go, and
    preempted sequences put back at the front (each moves every place, and
    so every bound, back by one)."""
    rng = random.Random(seed)
    sched = make_sched(slots=6)
    deadline = {}  # rid -> the last wave that may admit it
    wave_no = arrived = 0

    def arrive(n):
        nonlocal arrived
        for _ in range(n):
            sched.add(make_seq(f"q{arrived}", rng.choice([5, 12, 20, 40, 70, 120])))
            arrived += 1

    arrive(40)
    while sched.waiting or arrived < 400:
        running = list(sched.running.values())
        for seq in rng.sample(running, k=rng.randint(0, len(running))):
            if rng.random() < 0.15:
                sched.preempt(seq)
                deadline = {rid: w + 1 for rid, w in deadline.items()}
                deadline[seq.rid] = wave_no + 1
            else:
                sched.finish(seq, "stop")
        if arrived < 400:
            arrive(rng.randint(0, 5))
        for place, seq in enumerate(sched.waiting):
            deadline.setdefault(seq.rid, wave_no + place + 1)
        head = sched.waiting[0] if sched.waiting else None
        admitted = sched.admit(WAVE, bucket_of)
        if not admitted:
            continue  # nobody waits or no slot is free: not a wave
        wave_no += 1
        assert admitted[0] is head
        for seq in admitted:
            assert wave_no <= deadline.pop(seq.rid), (seq.rid, wave_no)
        for seq in sched.waiting:
            assert wave_no < deadline[seq.rid], (seq.rid, wave_no)
        sched.check_invariants()
    assert arrived >= 400 and not deadline


@pytest.mark.parametrize("seed", range(4))
def test_interactive_place_p_is_admitted_within_p_plus_one_waves(seed):
    """The same bound inside the interactive class of a priority-aware
    scheduler, whose waiters are put in front of every batch waiter."""
    rng = random.Random(100 + seed)
    sched = make_sched(slots=6, priority_aware=True)
    deadline = {}
    wave_no = arrived = 0
    while arrived < 300 or any(s.priority == "interactive" for s in sched.waiting):
        for seq in list(sched.running.values()):
            if rng.random() < 0.5:
                sched.finish(seq, "stop")
        for _ in range(rng.randint(0, 6) if arrived < 300 else 0):
            prio = "interactive" if rng.random() < 0.2 else "batch"
            sched.add(make_seq(f"q{arrived}", rng.choice([5, 20, 40, 120]), prio))
            arrived += 1
        place = 0
        for seq in sched.waiting:
            if seq.priority == "interactive":
                deadline.setdefault(seq.rid, wave_no + place + 1)
                place += 1
        want = fifo_wave(sched, WAVE) if place else None
        admitted = sched.admit(WAVE, bucket_of)
        if not admitted:
            continue
        wave_no += 1
        if want:  # as many of the FIFO wave as there were slots
            assert rids(admitted) == want[: len(admitted)]
        for seq in admitted:
            assert wave_no <= deadline.pop(seq.rid, wave_no)
        sched.check_invariants()
    assert not deadline


def test_the_head_is_in_every_wave():
    rng = random.Random(3)
    sched = make_sched(slots=4)
    fill(sched, [rng.choice([5, 12, 20, 40, 70, 120]) for _ in range(200)])
    order = []
    while sched.waiting:
        head = sched.waiting[0]
        admitted = sched.admit(WAVE, bucket_of)
        assert admitted[0] is head
        if len(sched.waiting) + len(admitted) > WAVE:  # there was a choice
            assert len({bucket_of(s) for s in admitted}) == 1
        mates = [int(s.rid[1:]) for s in admitted[1:]]
        assert mates == sorted(mates)
        order += rids(admitted)
        for seq in admitted:
            sched.finish(seq, "stop")
    assert sorted(order) == sorted(f"r{i}" for i in range(200))
