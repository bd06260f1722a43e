"""fp8 (float8_e5m2) KV cache: kernels, writes, and the engine.

The reference's engine inherits quantized KV caches from vLLM
(``kv-cache-dtype=fp8`` — scale-free e5m2 storage); here the page pools
simply allocate as ``float8_e5m2``: writes cast on store, every reader
(XLA references and the Pallas kernels, which already convert pages to
f32 on-chip) dequantizes on load. Half the KV bytes — double the page
pool in the same HBM, half the decode-attention bandwidth.

Test strategy: fp8 quantization is deterministic, so the Pallas kernels
are compared against the XLA references over the SAME fp8 pool at tight
tolerance (both dequantize identical bits); engine-level runs assert
completion + determinism, not cross-dtype token equality (rounding can
legitimately flip a greedy pick on random tiny models).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import init_params
from llmq_tpu.ops import attention as ref_ops
from llmq_tpu.ops import pallas_attention as pk
from llmq_tpu.ops.dispatch import _WINDOW_DISABLED

pytestmark = pytest.mark.unit

FP8 = jnp.float8_e5m2


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.3


def _fp8_paged_setup(key, *, S, n_kv, d, page_size, pages_per_seq, ctx_lens,
                     layers=None):
    P = 1 + S * pages_per_seq
    shape = (P, page_size, n_kv, d) if layers is None else (
        layers, P, page_size, n_kv, d
    )
    k1, k2 = jax.random.split(key)
    k_pages = _rand(k1, shape).astype(FP8)
    v_pages = _rand(k2, shape).astype(FP8)
    bt = jnp.arange(1, 1 + S * pages_per_seq, dtype=jnp.int32).reshape(S, -1)
    return k_pages, v_pages, bt, jnp.asarray(ctx_lens, jnp.int32)


class TestFp8XlaPaths:
    def test_paged_decode_matches_dequantized_pool(self):
        """The XLA reference over an fp8 pool equals the same reference
        over the pre-dequantized pool — the cast happens on load, before
        any arithmetic."""
        S, n_heads, n_kv, d, page_size, pps = 3, 4, 2, 16, 8, 3
        q = _rand(jax.random.key(0), (S, n_heads, d))
        kp, vp, bt, cl = _fp8_paged_setup(
            jax.random.key(1), S=S, n_kv=n_kv, d=d, page_size=page_size,
            pages_per_seq=pps, ctx_lens=[1, 9, 24],
        )
        out = ref_ops.paged_decode_attention(
            q, kp, vp, bt, cl, scale=d**-0.5
        )
        ref = ref_ops.paged_decode_attention(
            q, kp.astype(jnp.float32), vp.astype(jnp.float32), bt, cl,
            scale=d**-0.5,
        )
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)

    def test_writes_cast_to_pool_dtype(self):
        """Both write paths store fp8 when the pool is fp8, matching an
        explicit host-side cast."""
        S, n_kv, d, page_size, pps, L = 2, 2, 16, 8, 2, 2
        P = 1 + S * pps
        kp = jnp.zeros((L, P, page_size, n_kv, d), FP8)
        vp = jnp.zeros((L, P, page_size, n_kv, d), FP8)
        bt = jnp.arange(1, 1 + S * pps, dtype=jnp.int32).reshape(S, -1)
        li = jnp.asarray(0, jnp.int32)

        kn = _rand(jax.random.key(2), (S, 1, n_kv, d))
        vn = _rand(jax.random.key(3), (S, 1, n_kv, d))
        positions = jnp.asarray([[3], [7]], jnp.int32)
        kp2, vp2 = ref_ops.write_kv_pages(kp, vp, kn, vn, bt, positions, li)
        assert kp2.dtype == FP8 and vp2.dtype == FP8
        got = kp2[0, bt[1, 0], 7].astype(jnp.float32)
        np.testing.assert_array_equal(
            got, kn[1, 0].astype(FP8).astype(jnp.float32)
        )

        T = page_size * pps
        kb = _rand(jax.random.key(4), (S, T, n_kv, d))
        vb = _rand(jax.random.key(5), (S, T, n_kv, d))
        kp3, vp3 = ref_ops.write_prompt_kv_pages(kp, vp, kb, vb, bt, li)
        assert kp3.dtype == FP8
        np.testing.assert_array_equal(
            kp3[0, bt[0, 0]].astype(jnp.float32),
            kb[0, :page_size].astype(FP8).astype(jnp.float32),
        )


class TestFp8PallasKernels:
    @pytest.mark.parametrize(
        "kernel,n_kv",
        [
            (pk.paged_decode_attention_pallas, 2),
            # four fp8 heads fill a word: the pool "live" reads
            (pk.paged_decode_attention_live, 4),
        ],
        ids=["v1", "live"],
    )
    def test_decode_kernels_match_reference_on_fp8_pool(self, kernel, n_kv):
        S, n_heads, d, page_size, pps = 4, 8, 16, 8, 4
        ctx = [1, 8, 19, 32]
        q = _rand(jax.random.key(6), (S, n_heads, d))
        kp, vp, bt, cl = _fp8_paged_setup(
            jax.random.key(7), S=S, n_kv=n_kv, d=d, page_size=page_size,
            pages_per_seq=pps, ctx_lens=ctx,
        )
        ref = ref_ops.paged_decode_attention(q, kp, vp, bt, cl, scale=d**-0.5)
        out = kernel(
            q, kp, vp, bt, cl,
            jnp.asarray([_WINDOW_DISABLED], jnp.int32),
            scale=d**-0.5, interpret=True,
        )
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16])
    def test_chunked_prefill_kernel_fp8_pool(self, q_dtype):
        """fp8 pages upcast to the query dtype inside the kernel — both
        the f32 (tests) and bf16 (production MXU full-rate) paths."""
        B, C, n_heads, n_kv, d, page_size, pps = 2, 8, 4, 2, 16, 8, 3
        q = _rand(jax.random.key(12), (B, C, n_heads, d)).astype(q_dtype)
        kp, vp, bt, _ = _fp8_paged_setup(
            jax.random.key(13), S=B, n_kv=n_kv, d=d, page_size=page_size,
            pages_per_seq=pps, ctx_lens=[0] * B,
        )
        # Row 0: positions 4..11; row 1: 0..5 then padding.
        q_positions = jnp.asarray(
            [[4, 5, 6, 7, 8, 9, 10, 11], [0, 1, 2, 3, 4, 5, -1, -1]],
            jnp.int32,
        )
        ref = ref_ops.paged_prefill_attention(
            q, kp, vp, bt, q_positions, scale=d**-0.5
        )
        num_valid = (q_positions >= 0).sum(axis=1).astype(jnp.int32)
        chunk_start = jnp.where(num_valid > 0, q_positions[:, 0], 0)
        out = pk.paged_prefill_attention_pallas(
            q, kp, vp, bt, chunk_start, num_valid,
            jnp.asarray([_WINDOW_DISABLED], jnp.int32),
            jnp.zeros((1,), jnp.int32),
            scale=d**-0.5, interpret=True,
        )
        valid = np.asarray(q_positions) >= 0
        tol = 2e-5 if q_dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[valid],
            np.asarray(ref, np.float32)[valid],
            rtol=tol, atol=tol,
        )


CFG = ModelConfig.tiny(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    intermediate_size=128,
    model_type="qwen2",
)


def _run_engine(kv_dtype, *, chunked=False):
    params = init_params(CFG, jax.random.key(0), dtype=jnp.float32)
    core = EngineCore(
        CFG,
        params,
        ByteTokenizer(),
        engine_config=EngineConfig(
            max_num_seqs=2,
            max_model_len=64,
            page_size=8,
            num_pages=32,
            kv_dtype=kv_dtype,
            min_prefill_bucket=16,
            prefill_chunk_size=8 if chunked else None,
        ),
    )
    for i in range(3):
        core.add_request(
            f"r{i}",
            prompt=f"fp8 kv cache request {i}",
            params=SamplingParams(temperature=0.0, max_tokens=8,
                                  ignore_eos=True),
        )
    finished = {}
    for _ in range(200):
        for out in core.step():
            finished[out.rid] = out
        if not core.has_work:
            break
    assert sorted(finished) == ["r0", "r1", "r2"]
    assert all(f.completion_tokens == 8 for f in finished.values())
    return {rid: f.token_ids for rid, f in finished.items()}


class TestFp8Engine:
    def test_config_resolves_strings(self):
        assert EngineConfig(kv_dtype="fp8").kv_dtype == FP8
        assert EngineConfig(kv_dtype="fp8_e5m2").kv_dtype == FP8
        assert EngineConfig(kv_dtype="bf16").kv_dtype == jnp.bfloat16
        assert EngineConfig(kv_dtype="float32").kv_dtype == jnp.float32
        with pytest.raises(ValueError, match="kv_dtype"):
            EngineConfig(kv_dtype="int4")

    def test_fp8_engine_deterministic_end_to_end(self):
        a = _run_engine("fp8")
        b = _run_engine("fp8")
        assert a == b  # fp8 rounding is deterministic

    def test_fp8_engine_chunked_prefill(self):
        a = _run_engine("fp8", chunked=True)
        assert all(len(t) == 8 for t in a.values())

    def test_fp8_pool_halves_bytes(self):
        params = init_params(CFG, jax.random.key(0), dtype=jnp.float32)
        cores = {
            name: EngineCore(
                CFG, params, ByteTokenizer(),
                engine_config=EngineConfig(
                    max_num_seqs=2, max_model_len=64, page_size=8,
                    num_pages=32, kv_dtype=name,
                ),
            )
            for name in ("bf16", "fp8")
        }
        nbytes = {
            name: core.k_pages.nbytes for name, core in cores.items()
        }
        assert nbytes["fp8"] * 2 == nbytes["bf16"]
