"""Engine end-to-end on the CPU backend: continuous batching, stops,
preemption, and sharded (tp/dp) execution matching single-device output."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmq_tpu.engine.engine import AsyncEngine, EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.parallel import make_mesh

CFG = ModelConfig.tiny(vocab_size=304)
PARAMS = init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def make_core(**overrides) -> EngineCore:
    defaults = dict(
        max_num_seqs=4,
        max_model_len=64,
        page_size=8,
        num_pages=40,
        kv_dtype=jnp.float32,
        min_prefill_bucket=16,
    )
    defaults.update(overrides.pop("engine", {}))
    mesh = overrides.pop("mesh", None) or make_mesh(tensor_parallel=1)
    return EngineCore(
        CFG, PARAMS, ByteTokenizer(), mesh=mesh,
        engine_config=EngineConfig(**defaults),
    )


def run_sync(core, requests):
    """Drive the core synchronously until all requests finish."""
    for rid, prompt, params in requests:
        core.add_request(rid, prompt=prompt, params=params)
    outs = {}
    for _ in range(500):
        for out in core.step():
            outs[out.rid] = out
        if not core.has_work:
            break
    assert len(outs) == len(requests), "engine stalled"
    return outs


def greedy(max_tokens=8, **kw):
    return SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True, **kw
    )


class TestEngineCore:
    def test_single_request_generates(self):
        outs = run_sync(make_core(), [("r0", "hello", greedy(6))])
        out = outs["r0"]
        assert out.completion_tokens == 6
        assert out.finish_reason == "length"
        assert out.prompt_tokens == 5

    def test_batch_matches_solo_greedy(self):
        """Continuous batching must not change greedy outputs."""
        solo = run_sync(make_core(), [("a", "first prompt", greedy(8))])
        batch = run_sync(
            make_core(),
            [
                ("a", "first prompt", greedy(8)),
                ("b", "second!", greedy(8)),
                ("c", "third prompt here", greedy(8)),
            ],
        )
        assert batch["a"].token_ids == solo["a"].token_ids

    def test_more_requests_than_slots(self):
        reqs = [(f"r{i}", f"prompt {i}", greedy(4)) for i in range(10)]
        outs = run_sync(make_core(), reqs)  # 4 slots
        assert len(outs) == 10
        assert all(o.completion_tokens == 4 for o in outs.values())

    def test_admission_age_cap_overrides_batch_deferral(self):
        """Batch admission defers partial prefill chunks for throughput,
        but an overdue head-of-line request must be admitted into whatever
        slots exist (admit_max_wait_s latency floor)."""
        core = make_core(
            engine=dict(max_prefill_batch=4, admit_max_wait_s=30.0)
        )
        for i in range(3):
            core.add_request(f"bg{i}", prompt="busy", params=greedy(40))
        core.step()
        assert core.scheduler.num_running == 3
        core.add_request("w0", prompt="late one", params=greedy(2))
        core.add_request("w1", prompt="late two", params=greedy(2))
        core.step()
        # free(1) < want(2): the chunk deferral holds both back, and the
        # deferral clock starts ticking at this step (not at enqueue —
        # backlogged requests must not defeat batching on arrival)...
        assert core.scheduler.num_running == 3
        assert core._defer_since is not None
        # ...until the *deferral* is overdue (injectable clock).
        core._defer_since -= 60.0
        core.step()
        assert "w0" in core.scheduler.running
        assert core._defer_since is None  # admission resets the clock
        # drain everything for hygiene
        outs = {}
        for _ in range(500):
            for out in core.step():
                outs[out.rid] = out
            if not core.has_work:
                break
        assert set(outs) == {"bg0", "bg1", "bg2", "w0", "w1"}

    def test_stop_token_ids(self):
        core = make_core()
        first = run_sync(core, [("probe", "hi", greedy(4))])["probe"]
        second_token = first.token_ids[1]
        core2 = make_core()
        out = run_sync(
            core2,
            [("r", "hi", greedy(8, stop_token_ids=(second_token,)))],
        )["r"]
        assert out.finish_reason == "stop"
        assert out.token_ids == first.token_ids[:1]

    def test_eos_respected_unless_ignored(self):
        # Build params whose greedy output contains EOS(0) rarely; instead
        # force it: stop_token_ids on the first emitted token → empty output.
        core = make_core()
        probe = run_sync(core, [("p", "xyz", greedy(3))])["p"]
        out = run_sync(
            make_core(),
            [("r", "xyz", greedy(6, stop_token_ids=(probe.token_ids[0],)))],
        )["r"]
        assert out.completion_tokens == 0
        assert out.finish_reason == "stop"

    def test_stop_string(self):
        core = make_core()
        probe = run_sync(core, [("p", "abc", greedy(6))])["p"]
        needle = ByteTokenizer().decode(probe.token_ids[2:4])
        if not needle:  # pragma: no cover — depends on random weights
            pytest.skip("undecodable tokens for this seed")
        out = run_sync(
            make_core(), [("r", "abc", greedy(6, stop=(needle,)))]
        )["r"]
        assert out.finish_reason == "stop"
        assert needle not in out.text

    def test_max_model_len_truncation(self):
        core = make_core(engine=dict(max_model_len=32))
        long_prompt = "x" * 100
        out = run_sync(core, [("r", long_prompt, greedy(50))])["r"]
        assert out.prompt_tokens == 31
        assert out.finish_reason == "length"
        assert out.completion_tokens <= 1

    def test_preemption_recovers(self):
        """Tiny page pool forces eviction + re-prefill; everything still
        finishes and greedy output is unaffected."""
        roomy = run_sync(
            make_core(),
            [(f"r{i}", f"pr {i} " * 3, greedy(10)) for i in range(3)],
        )
        tight_core = make_core(engine=dict(num_pages=8, page_size=4))
        tight = run_sync(
            tight_core,
            [(f"r{i}", f"pr {i} " * 3, greedy(10)) for i in range(3)],
        )
        for rid, out in roomy.items():
            assert tight[rid].token_ids == out.token_ids
        stats = tight_core.stats()
        assert stats["prefills"] >= 3

    def test_min_tokens_suppresses_stop(self):
        core = make_core()
        probe = run_sync(core, [("p", "hi", greedy(6))])["p"]
        stopper = probe.token_ids[1]
        out = run_sync(
            make_core(),
            [("r", "hi", greedy(6, stop_token_ids=(stopper,), min_tokens=4))],
        )["r"]
        assert out.completion_tokens >= 4

    def test_shared_params_not_mutated(self):
        shared = greedy(1000)
        core = make_core(engine=dict(max_model_len=32))
        core.add_request("a", prompt="x" * 60, params=shared)
        assert shared.max_tokens == 1000  # engine took a copy

    def test_impossible_prompt_rejected(self):
        core = make_core(engine=dict(num_pages=3, page_size=4, max_model_len=64))
        with pytest.raises(ValueError):
            core.add_request("r", prompt="a" * 40, params=greedy(4))
        assert not core.has_work

    def test_seeded_sampling_reproducible(self):
        reqs = [("r", "hello", SamplingParams(temperature=1.0, seed=42,
                                              max_tokens=8, ignore_eos=True))]
        a = run_sync(make_core(), reqs)["r"]
        b = run_sync(make_core(), reqs)["r"]
        assert a.token_ids == b.token_ids

    def test_stats_counters(self):
        core = make_core()
        run_sync(core, [("r0", "hello", greedy(5))])
        s = core.stats()
        assert s["generated_tokens"] == 5
        assert s["prefills"] == 1
        assert s["prompt_tokens"] == 5
        # Calibration surfaces in heartbeats: what the engine actually
        # runs, not what env vars suggest.
        assert s["decode_kernel"] == "xla"  # CPU backend
        assert s["kv_dtype"] == "float32"


class TestSharding:
    def _golden(self):
        return run_sync(
            make_core(),
            [(f"r{i}", f"hello world {i}", greedy(8)) for i in range(4)],
        )

    @pytest.mark.parametrize("tp,dp", [(2, 1), (4, 1), (1, 2), (2, 2)])
    def test_sharded_matches_single_device(self, tp, dp):
        golden = self._golden()
        mesh = make_mesh(tensor_parallel=tp, data_parallel=dp)
        outs = run_sync(
            make_core(mesh=mesh),
            [(f"r{i}", f"hello world {i}", greedy(8)) for i in range(4)],
        )
        for rid, out in golden.items():
            assert outs[rid].token_ids == out.token_ids, f"{rid} diverged"


class TestAsyncEngine:
    def test_concurrent_generate(self):
        eng = AsyncEngine(make_core())

        async def main():
            return await asyncio.gather(
                *[
                    eng.generate(
                        rid=f"r{i}", prompt=f"req {i}", params=greedy(5)
                    )
                    for i in range(8)
                ]
            )

        try:
            outs = asyncio.run(main())
            assert len(outs) == 8
            assert all(o.completion_tokens == 5 for o in outs)
        finally:
            eng.shutdown()

    def test_messages_path(self):
        eng = AsyncEngine(make_core())

        async def main():
            return await eng.generate(
                rid="chat",
                messages=[{"role": "user", "content": "hi"}],
                params=greedy(4),
            )

        try:
            out = asyncio.run(main())
            assert out.completion_tokens == 4
        finally:
            eng.shutdown()

    def test_bad_request_raises(self):
        eng = AsyncEngine(make_core())

        async def main():
            with pytest.raises(ValueError):
                await eng.generate(rid="bad")

        try:
            asyncio.run(main())
        finally:
            eng.shutdown()


class TestReviewRegressions:
    """Regressions from the run-ahead-pipeline review."""

    def test_min_tokens_token_never_emitted_early(self):
        """min_tokens must *suppress* the stop token's logits, not just
        ignore the stop — the id must not appear in the early output."""
        probe = run_sync(make_core(), [("p", "hi", greedy(6))])["p"]
        stopper = probe.token_ids[1]
        out = run_sync(
            make_core(),
            [("r", "hi", greedy(6, stop_token_ids=(stopper,), min_tokens=4))],
        )["r"]
        assert out.completion_tokens >= 4
        assert stopper not in out.token_ids[:4]

    def test_stop_string_trims_token_ids(self):
        """token_ids/usage must agree with the truncated text."""
        core = make_core()
        probe = run_sync(core, [("p", "hello", greedy(8))])["p"]
        tok = ByteTokenizer()
        full = probe.text
        if len(full) < 3:
            pytest.skip("probe output too short")
        stop = full[2]
        out = run_sync(
            make_core(), [("r", "hello", greedy(8, stop=(stop,)))]
        )["r"]
        assert out.finish_reason == "stop"
        assert out.completion_tokens == len(out.token_ids)
        decoded = tok.decode(out.token_ids)
        assert decoded.startswith(out.text)
        # at most the matched stop itself may trail the text
        assert len(decoded) <= len(out.text) + len(stop) + 8

    def test_stop_string_earliest_match_wins(self):
        core = make_core()
        tok = ByteTokenizer()
        from llmq_tpu.engine.scheduler import Sequence

        seq = Sequence(
            rid="s",
            prompt_ids=[1],
            params=SamplingParams(stop=("b", "ab"), max_tokens=10),
        )
        seq.output_ids = list(tok.encode("xab"))
        reason = core._stop_reason(seq, seq.output_ids[-1])
        assert reason == "stop"
        assert seq.finish_text == "x"  # "ab" matches at 1, before "b" at 2

    def test_abort_all_recovers_donated_buffers(self):
        """After a failed step consumed the donated KV buffers, abort_all
        must leave the engine usable."""
        core = make_core()
        run_sync(core, [("a", "hi", greedy(4))])
        core.k_pages.delete()  # simulate a step that died mid-donation
        core.abort_all("error")
        out = run_sync(core, [("b", "still alive?", greedy(4))])["b"]
        assert out.completion_tokens == 4

    def test_stop_capacity_grows_past_default(self):
        """A stop set wider than stop_id_capacity must widen the device
        arrays (drain + retrace), not silently truncate — every id stays
        suppressed under min_tokens (ADVICE.md round 1, engine.py:547)."""
        core = make_core()
        assert core._stop_capacity == 8
        probe = run_sync(core, [("p", "hi", greedy(8))])["p"]
        # 12 distinct stop ids, including ones the model actually emits.
        stops = tuple(dict.fromkeys(
            list(probe.token_ids) + list(range(1, 13))
        ))[:12]
        out = run_sync(
            core,
            [("r", "hi", greedy(8, stop_token_ids=stops, min_tokens=5))],
        )["r"]
        assert core._stop_capacity >= 12
        assert core.cfg.stop_id_capacity == 8  # shared config not mutated
        assert out.completion_tokens >= 5
        for tok in out.token_ids[:5]:
            assert tok not in stops  # all 12 suppressed, not just 8
        # Continuous batching still works after the grow (mixed widths).
        outs = run_sync(
            core,
            [
                ("a", "one", greedy(6)),
                ("b", "two", greedy(6, stop_token_ids=stops, min_tokens=3)),
            ],
        )
        assert outs["a"].completion_tokens == 6


class TestMoEEngine:
    """A sparse-MoE model (qwen2_moe-style) through the full engine, on a
    single device and tensor-parallel — the grouped-matmul expert path
    (ragged_dot + sort/segment routing) must survive jit, the layer scan,
    and GSPMD sharding of the per-expert intermediate dim."""

    MOE_CFG = ModelConfig.tiny(
        vocab_size=304,
        num_heads=4,
        num_kv_heads=2,
        attention_bias=True,
        model_type="qwen2_moe",
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=32,
        shared_expert_intermediate_size=48,
    )
    MOE_PARAMS = init_params(MOE_CFG, jax.random.key(3), dtype=jnp.float32)

    def _core(self, mesh=None):
        return EngineCore(
            self.MOE_CFG,
            self.MOE_PARAMS,
            ByteTokenizer(),
            mesh=mesh or make_mesh(tensor_parallel=1),
            engine_config=EngineConfig(
                max_num_seqs=4,
                max_model_len=64,
                page_size=8,
                num_pages=40,
                kv_dtype=jnp.float32,
                min_prefill_bucket=16,
            ),
        )

    def test_moe_generates(self):
        outs = run_sync(
            self._core(),
            [(f"m{i}", f"moe prompt {i}", greedy(6)) for i in range(3)],
        )
        assert all(o.completion_tokens == 6 for o in outs.values())

    @pytest.mark.parametrize("tp", [2, 4])
    def test_moe_sharded_matches_single(self, tp):
        golden = run_sync(
            self._core(), [(f"m{i}", f"moe prompt {i}", greedy(6)) for i in range(3)]
        )
        sharded = run_sync(
            self._core(mesh=make_mesh(tensor_parallel=tp)),
            [(f"m{i}", f"moe prompt {i}", greedy(6)) for i in range(3)],
        )
        for rid, out in golden.items():
            assert sharded[rid].token_ids == out.token_ids, f"{rid} diverged"


class TestChunkedPrefill:
    """prefill_chunk_size mode: fixed-[B, C] chunk executable against the
    paged cache, decode interleaved between chunks. Outputs must be
    identical to bucketed whole-prompt prefill."""

    def _run(self, reqs, **engine):
        return run_sync(make_core(engine=engine), reqs)

    def test_chunked_matches_bucketed(self):
        reqs = [
            ("short", "hi", greedy(6)),
            ("mid", "a prompt that is longer", greedy(6)),
            ("long", "x" * 37, greedy(6)),  # crosses several chunks
            # exact chunk multiple: goes final precisely at a chunk edge
            # while "long" keeps chunking (regression: a re-read length
            # must not re-final the row after interleaved decodes append)
            ("edge", "e" * 16, greedy(6)),
        ]
        golden = self._run(reqs)
        chunked = self._run(reqs, prefill_chunk_size=8)
        for rid, out in golden.items():
            assert chunked[rid].token_ids == out.token_ids, rid

    @pytest.mark.slow
    def test_long_context_chunked_matches_bucketed(self):
        """A 4k-token prompt through 256-token chunks against the paged
        cache must reproduce the bucketed whole-prompt greedy output —
        the long-context path (many chunks, many pages, frontier math at
        scale) not covered by the short soaks."""
        reqs = [
            ("long4k", "z" * 4096, greedy(8)),
            ("bystander", "short prompt", greedy(8)),
        ]
        engine = dict(
            max_model_len=8192, num_pages=1100, max_num_seqs=2, page_size=8
        )
        golden = self._run(reqs, **engine)
        chunked = self._run(reqs, prefill_chunk_size=256, **engine)
        for rid, out in golden.items():
            assert chunked[rid].token_ids == out.token_ids, rid
        assert len(chunked["long4k"].token_ids) == 8

    def test_chunk_interleaves_with_running_decode(self):
        """A long admission while others decode must not change anyone's
        greedy output (interleaved decode steps between chunks)."""
        core = make_core(engine=dict(prefill_chunk_size=8))
        core.add_request("bg", prompt="busy", params=greedy(30))
        for _ in range(3):
            core.step()
        core.add_request("late", prompt="y" * 30, params=greedy(5))
        outs = {}
        for _ in range(500):
            for o in core.step():
                outs[o.rid] = o
            if not core.has_work:
                break
        assert set(outs) == {"bg", "late"}
        golden = self._run(
            [("bg", "busy", greedy(30)), ("late", "y" * 30, greedy(5))]
        )
        assert outs["bg"].token_ids == golden["bg"].token_ids
        assert outs["late"].token_ids == golden["late"].token_ids

    def test_more_requests_than_slots_chunked(self):
        reqs = [(f"r{i}", f"prompt number {i} padding", greedy(4)) for i in range(10)]
        outs = self._run(reqs, prefill_chunk_size=8)
        assert len(outs) == 10
        assert all(o.completion_tokens == 4 for o in outs.values())

    def test_chunked_stop_and_sampling_paths(self):
        """Stop tokens + stochastic sampling survive the chunk scatter."""
        probe = self._run([("p", "hello world", greedy(6))], prefill_chunk_size=8)["p"]
        out = self._run(
            [("r", "hello world", greedy(8, stop_token_ids=(probe.token_ids[1],)))],
            prefill_chunk_size=8,
        )["r"]
        assert out.finish_reason == "stop"
        assert out.token_ids == probe.token_ids[:1]
        seeded = SamplingParams(temperature=0.9, seed=5, max_tokens=6, ignore_eos=True)
        a = self._run([("s", "same seed", seeded)], prefill_chunk_size=8)["s"]
        b = self._run([("s", "same seed", seeded)])["s"]
        assert a.token_ids == b.token_ids  # same slot, same base key


class TestPrefixCaching:
    """enable_prefix_caching through the full engine: identical leading
    pages are computed once and shared; outputs match the uncached run."""

    def _core(self, cache):
        return make_core(
            engine=dict(
                prefill_chunk_size=8,
                enable_prefix_caching=cache,
                num_pages=60,
                max_num_seqs=4,
            )
        )

    def test_cached_matches_uncached(self):
        shared = "common instruction prefix! " * 2  # > several pages
        reqs = [
            (f"r{i}", shared + f"document {i}", greedy(6)) for i in range(6)
        ]
        golden = run_sync(self._core(False), reqs)
        core = self._core(True)
        outs = run_sync(core, reqs)
        for rid, out in golden.items():
            assert outs[rid].token_ids == out.token_ids, rid
        # later requests actually reused pages
        assert core.scheduler.prefix_hits > 0
        core.scheduler.check_invariants()

    def test_prefix_survives_sharer_churn(self):
        """Short cached requests finish and release while later ones are
        still matching the same prefix — refcounts must stay consistent
        through the deferred-release pipeline."""
        shared = "x" * 20
        core = self._core(True)
        reqs = [(f"r{i}", shared + str(i), greedy(2 + i % 3)) for i in range(10)]
        outs = run_sync(core, reqs)
        assert len(outs) == 10
        core.scheduler.check_invariants()
        golden = run_sync(self._core(False), reqs)
        for rid, out in golden.items():
            assert outs[rid].token_ids == out.token_ids, rid

    def test_requires_chunked_prefill(self):
        with pytest.raises(ValueError):
            make_core(engine=dict(enable_prefix_caching=True))

    def test_abort_invalidates_prefix_cache(self):
        """After abort_all rebuilds (zeroes) the KV buffers, stale prefix
        hashes must not hand future requests empty context."""
        core = self._core(True)
        shared = "common instruction prefix! " * 2
        run_sync(core, [("warm", shared + "tail", greedy(3))])
        assert core.scheduler._prefix_cache  # cache is warm
        core.abort_all("error")
        assert not core.scheduler._prefix_cache
        outs = run_sync(core, [("after", shared + "t2", greedy(3))])
        assert core.scheduler.prefix_hits == 0  # recomputed, not matched
        assert outs["after"].completion_tokens == 3
        core.scheduler.check_invariants()


def test_prefill_bucket_quarter_steps():
    """Above 128 the bucket ladder carries quarter steps between octaves
    (a 200-token prompt pads to 224, not 256 — prefill is compute-bound
    and padding is real FLOPs); below 128 it stays pure powers of two;
    every bucket is a multiple of the sp degree."""
    from llmq_tpu.engine.engine import _prefill_buckets

    cfg = EngineConfig(
        max_num_seqs=4, max_model_len=512, page_size=128,
        min_prefill_bucket=32,
    )
    buckets = _prefill_buckets(cfg)
    assert buckets == [32, 64, 128, 160, 192, 224, 256, 320, 384, 448, 512]
    assert next(b for b in buckets if b >= 200) == 224
    sp_buckets = _prefill_buckets(cfg, sp=4)
    assert all(b % 4 == 0 for b in sp_buckets)
    assert sp_buckets[-1] == 512


def test_param_auto_layout_matches_default(monkeypatch):
    """LLMQ_PARAM_AUTO_LAYOUT=1 (XLA-chosen parameter layouts) must not
    change outputs — layout is memory order, not math."""
    golden = run_sync(make_core(), [("r", "hello layout", greedy(5))])
    monkeypatch.setenv("LLMQ_PARAM_AUTO_LAYOUT", "1")
    outs = run_sync(make_core(), [("r", "hello layout", greedy(5))])
    assert outs["r"].token_ids == golden["r"].token_ids

def test_param_auto_layout_with_int8(monkeypatch):
    """Auto-layout re-puts a QUANTIZED param tree ({q, scale} dict
    nodes) without changing outputs — the layout probe and leaf-by-leaf
    re-put must handle int8 leaves."""
    from llmq_tpu.models.quant import quantize_params

    qparams = quantize_params(PARAMS)

    def qcore():
        return EngineCore(
            CFG, qparams, ByteTokenizer(), mesh=make_mesh(tensor_parallel=1),
            engine_config=EngineConfig(
                max_num_seqs=4, max_model_len=64, page_size=8, num_pages=40,
                kv_dtype=jnp.float32, min_prefill_bucket=16,
            ),
        )

    golden = run_sync(qcore(), [("r", "hello int8 layout", greedy(5))])
    monkeypatch.setenv("LLMQ_PARAM_AUTO_LAYOUT", "1")
    outs = run_sync(qcore(), [("r", "hello int8 layout", greedy(5))])
    assert outs["r"].token_ids == golden["r"].token_ids



class TestDecodeBlock:
    """Fused multi-step decode (EngineConfig.decode_block > 1): K device
    iterations per host dispatch must be invisible in the outputs."""

    def test_block4_matches_k1_all_sampling_modes(self):
        reqs = [
            ("g", "hello world", greedy(7)),
            ("s", "hello world",
             SamplingParams(temperature=0.8, seed=7, max_tokens=6,
                            ignore_eos=True)),
            ("f", "another one",
             SamplingParams(temperature=0.5, top_k=8, top_p=0.9, seed=3,
                            max_tokens=5, ignore_eos=True)),
        ]
        ref = run_sync(make_core(), reqs)
        core = make_core(engine=dict(decode_block=4))
        outs = run_sync(core, reqs)
        for rid, _, _ in reqs:
            assert outs[rid].token_ids == ref[rid].token_ids, rid
        st = core.stats()
        assert st["decode_block"] == 4
        assert st["decode_dispatches"] <= -(-st["decode_steps"] // 4)

    def test_k1_dispatch_accounting_unchanged(self):
        """At the default K=1 every decode step is its own dispatch (and
        the engine compiles the exact pre-block executable)."""
        core = make_core()
        run_sync(core, [("r", "hi", greedy(5))])
        st = core.stats()
        assert st["decode_block"] == 1
        assert st["decode_dispatches"] == st["decode_steps"] > 0

    def test_mid_block_stop_discards_lagged_tokens(self):
        """A row that hits its stop token at block iteration j rides out
        the remaining iterations inactive; the host must discard those
        lagged tokens and report the same finish as K=1."""
        ref = run_sync(make_core(), [("r", "stop test", greedy(8))])["r"]
        stop_id = ref.token_ids[2]
        params = greedy(8, stop_token_ids=(stop_id,))
        a = run_sync(make_core(), [("r", "stop test", params)])["r"]
        b = run_sync(
            make_core(engine=dict(decode_block=4)), [("r", "stop test", params)]
        )["r"]
        assert a.token_ids == b.token_ids
        assert a.finish_reason == b.finish_reason == "stop"

    def test_decode_block_validation(self):
        with pytest.raises(ValueError):
            EngineConfig(decode_block=0)


class TestSpeculativeDecoding:
    """Lossless speculative decoding (EngineConfig.spec_tokens > 0):
    prompt-lookup drafting + fused on-device verification must be
    invisible in greedy outputs and exact-in-distribution elsewhere."""

    def test_spec_matches_non_spec_greedy(self):
        reqs = [
            ("g", "hello world hello wor", greedy(10)),
            ("rep", "abcabcabcabc", greedy(8)),
            ("short", "hi", greedy(3)),
        ]
        ref = run_sync(make_core(), reqs)
        core = make_core(engine=dict(spec_tokens=3))
        outs = run_sync(core, reqs)
        for rid, _, _ in reqs:
            assert outs[rid].token_ids == ref[rid].token_ids, rid
            assert outs[rid].finish_reason == ref[rid].finish_reason, rid
        st = core.stats()
        assert st["spec_tokens"] == 3
        assert st["spec_proposed"] > 0
        assert st["acceptance_rate"] == pytest.approx(
            st["spec_accepted"] / st["spec_proposed"]
        )
        assert st["verify_kernel"] in ("chunked_prefill", "xla")

    def test_spec_composes_with_decode_block(self):
        reqs = [("g", "hello world hello wor", greedy(9))]
        ref = run_sync(make_core(), reqs)
        core = make_core(engine=dict(spec_tokens=2, decode_block=2))
        outs = run_sync(core, reqs)
        assert outs["g"].token_ids == ref["g"].token_ids
        st = core.stats()
        # Two verify iterations per dispatch regardless of acceptance.
        assert st["decode_dispatches"] <= -(-st["decode_steps"] // 2)

    def test_spec_off_keeps_twelve_leaf_state_and_array_output(self):
        """spec_tokens=0 must preserve the literal pre-speculation decode
        path: a 12-leaf device state (no history leaf), plain-array step
        outputs, and per-token dispatch accounting."""
        core = make_core()
        assert len(core._dev_state) == 12
        assert core._h_history is None
        run_sync(core, [("r", "hi", greedy(4))])
        st = core.stats()
        assert st["spec_tokens"] == 0
        assert st["spec_proposed"] == st["spec_accepted"] == 0
        assert st["acceptance_rate"] == 0.0
        assert "verify_kernel" not in st
        assert st["decode_dispatches"] == st["decode_steps"] > 0

    def test_spec_on_appends_history_leaf(self):
        core = make_core(engine=dict(spec_tokens=2))
        assert len(core._dev_state) == 13
        assert core._dev_state[12].shape == (4, 64)  # [S, max_model_len]

    def test_spec_stop_token_cuts_accepted_run(self):
        """A stop token emitted mid-verify must cut the accepted run at
        that position, exactly like the sequential engine."""
        ref = run_sync(make_core(), [("r", "stop test", greedy(8))])["r"]
        stop_id = ref.token_ids[2]
        params = greedy(8, stop_token_ids=(stop_id,))
        a = run_sync(make_core(), [("r", "stop test", params)])["r"]
        b = run_sync(
            make_core(engine=dict(spec_tokens=3)), [("r", "stop test", params)]
        )["r"]
        assert a.token_ids == b.token_ids
        assert a.finish_reason == b.finish_reason == "stop"

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            EngineConfig(spec_tokens=-1)
        with pytest.raises(ValueError):
            EngineConfig(spec_ngram=0)


class TestAdmissionWaves:
    """A deep queue sent at once: admission fills a prefill program with
    waiting prompts of its head's bucket, and no request's tokens change."""

    N = 48

    def _serve(self, grouped: bool):
        rng = np.random.default_rng(36)
        core = make_core(
            engine=dict(max_num_seqs=8, max_model_len=256, num_pages=200)
        )
        assert core._buckets == [16, 32, 64, 128, 160, 192, 224, 256]
        if not grouped:
            core._admit_bucket = None  # FIFO, as chunked prefill admits
        chunks = []
        prefill_chunk = core._prefill_chunk

        def recording(chunk, bucket):
            chunks.append(([s.rid for s in chunk], [s.num_tokens for s in chunk], bucket))
            return prefill_chunk(chunk, bucket)

        core._prefill_chunk = recording
        core.spans.set(True)
        requests = [
            (
                f"r{i}",
                [int(t) for t in rng.integers(1, 300, size=int(rng.choice([9, 20, 40, 90, 150])))],
                greedy(int(rng.integers(3, 10))),
            )
            for i in range(self.N)
        ]
        for rid, ids, params in requests:
            core.add_request(rid, prompt_ids=ids, params=params)
        outs, finishes = {}, 0
        for _ in range(2000):
            for out in core.step():
                outs[out.rid] = out
                finishes += 1
            if not core.has_work:
                break
        assert finishes == len(outs) == self.N  # each exactly once
        return core, outs, chunks, core.spans.dump()["spans"]

    def test_deep_queue_fills_programs_and_keeps_every_token(self):
        core, outs, chunks, spans = self._serve(grouped=True)
        dispatches = [s for s in spans if s["name"] == "prefill_dispatch"]
        assert len(dispatches) == len(chunks)
        assert sum(s["rows"] for s in dispatches) / len(dispatches) >= 3
        # The harness's contract (benchmark/system.py hangs its recorder
        # here): 1-4 sequences that all fit the bucket they are run in.
        for rids, lens, bucket in chunks:
            assert 1 <= len(rids) <= core.cfg.max_prefill_batch
            assert bucket in core._buckets and max(lens) <= bucket
        assert sorted(r for rids, _, _ in chunks for r in rids) == sorted(outs)
        for s, (rids, lens, bucket) in zip(dispatches, chunks):
            batch = 1 if len(rids) == 1 else core.cfg.max_prefill_batch
            assert (s["rows"], s["tokens"], s["grid"]) == (len(rids), sum(lens), batch * bucket)
            assert s["variant"] == f"{batch}x{bucket}"
        stats = core.stats()
        assert stats["prefill_tokens"] == sum(sum(lens) for _, lens, _ in chunks)
        assert stats["prefill_grid_tokens"] == sum(s["grid"] for s in dispatches)
        assert stats["prefill_grid_tokens"] >= stats["prefill_tokens"]
        # `reach`: the place of the furthest request a wave took; a wave
        # that reached past its own rows overtook somebody.
        admits = [s for s in spans if s["name"] == "admit"]
        assert all(s["reach"] >= s["rows"] - 1 for s in admits)
        assert any(s["reach"] > s["rows"] - 1 for s in admits)

        fifo_core, fifo, fifo_chunks, fifo_spans = self._serve(grouped=False)
        assert all(
            s["reach"] == s["rows"] - 1 for s in fifo_spans if s["name"] == "admit"
        )
        assert len(fifo_chunks) > 1.5 * len(chunks)
        assert fifo_core.stats()["prefill_grid_tokens"] > stats["prefill_grid_tokens"]
        for rid, out in outs.items():
            assert out.token_ids == fifo[rid].token_ids, rid
