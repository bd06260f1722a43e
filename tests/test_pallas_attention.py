"""Pallas attention kernels vs the pure-XLA references (interpret mode).

Mirrors the reference's pattern of testing the inference backend with a
deterministic stand-in (SURVEY.md §4) — here the stand-in is the XLA
ground truth in ops/attention.py, and the subject is the compiled-path
kernels in ops/pallas_attention.py run through the Pallas interpreter on
the CPU backend.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmq_tpu.ops import attention as ref_ops
from llmq_tpu.ops import pallas_attention as pk
from llmq_tpu.ops.dispatch import _WINDOW_DISABLED

pytestmark = pytest.mark.unit

# The two decode schedules share one contract; every decode test runs
# against each. "live" is what ``dispatch.decode_kernel_plan`` names for a
# pool the chip does not pad, v1 what it names for one it does.
DECODE_KERNELS = {
    "v1": pk.paged_decode_attention_pallas,
    "live": pk.paged_decode_attention_live,
}


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.3


def _paged_setup(key, *, S, n_kv, d, page_size, pages_per_seq, ctx_lens):
    """Random pages + a block table that maps every live position."""
    P = 1 + S * pages_per_seq  # page 0 reserved (scratch)
    k1, k2 = jax.random.split(key)
    k_pages = _rand(k1, (P, page_size, n_kv, d))
    v_pages = _rand(k2, (P, page_size, n_kv, d))
    bt = np.arange(1, 1 + S * pages_per_seq, dtype=np.int32).reshape(
        S, pages_per_seq
    )
    return k_pages, v_pages, jnp.asarray(bt), jnp.asarray(ctx_lens, jnp.int32)


@pytest.mark.parametrize("kernel", DECODE_KERNELS.values(), ids=DECODE_KERNELS)
@pytest.mark.parametrize(
    "n_heads,n_kv,window,softcap",
    [
        (4, 4, None, None),  # MHA
        (8, 2, None, None),  # GQA
        (8, 2, 13, None),  # sliding window (ragged vs page grid)
        (4, 1, None, 30.0),  # softcap (gemma2-style)
        (6, 3, 7, 20.0),  # everything at once, odd group
    ],
)
def test_paged_decode_matches_reference(kernel, n_heads, n_kv, window, softcap):
    S, d, page_size, pages_per_seq = 5, 16, 8, 4
    ctx = [1, 7, 8, 19, 32]  # page-aligned and not, incl. full
    key = jax.random.key(0)
    kq, kp_ = jax.random.split(key)
    q = _rand(kq, (S, n_heads, d))
    k_pages, v_pages, bt, cl = _paged_setup(
        kp_, S=S, n_kv=n_kv, d=d, page_size=page_size,
        pages_per_seq=pages_per_seq, ctx_lens=ctx,
    )
    scale = d**-0.5
    win = jnp.asarray([window if window else _WINDOW_DISABLED], jnp.int32)
    ref = ref_ops.paged_decode_attention(
        q, k_pages, v_pages, bt, cl,
        scale=scale, sliding_window=window, softcap=softcap,
    )
    out = kernel(
        q, k_pages, v_pages, bt, cl, win,
        scale=scale, softcap=softcap, interpret=True,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", DECODE_KERNELS.values(), ids=DECODE_KERNELS)
def test_paged_decode_inactive_slot_no_nan(kernel):
    """ctx=0 slots must produce finite garbage, not NaN."""
    S, n_heads, n_kv, d, page_size, pages_per_seq = 2, 4, 2, 16, 8, 2
    key = jax.random.key(1)
    q = _rand(key, (S, n_heads, d))
    k_pages, v_pages, bt, cl = _paged_setup(
        key, S=S, n_kv=n_kv, d=d, page_size=page_size,
        pages_per_seq=pages_per_seq, ctx_lens=[0, 5],
    )
    out = kernel(
        q, k_pages, v_pages, bt, cl,
        jnp.asarray([_WINDOW_DISABLED], jnp.int32),
        scale=d**-0.5, interpret=True,
    )
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("kernel", DECODE_KERNELS.values(), ids=DECODE_KERNELS)
def test_paged_decode_stacked_layer_index(kernel):
    """Layer-stacked pool + traced layer index addresses the right layer."""
    S, n_heads, n_kv, d, page_size, pages_per_seq, L = 3, 4, 2, 16, 8, 3, 4
    key = jax.random.key(3)
    kq, kp_ = jax.random.split(key)
    q = _rand(kq, (S, n_heads, d))
    P = 1 + S * pages_per_seq
    k_pages = _rand(kp_, (L, P, page_size, n_kv, d))
    v_pages = _rand(jax.random.key(4), (L, P, page_size, n_kv, d))
    bt = jnp.arange(1, 1 + S * pages_per_seq, dtype=jnp.int32).reshape(S, -1)
    cl = jnp.asarray([5, 17, 24], jnp.int32)
    scale = d**-0.5
    win = jnp.asarray([_WINDOW_DISABLED], jnp.int32)
    for li in (0, 2, L - 1):
        ref = ref_ops.paged_decode_attention(
            q, k_pages, v_pages, bt, cl, scale=scale,
            layer=jnp.asarray(li, jnp.int32),
        )
        out = kernel(
            q, k_pages, v_pages, bt, cl, win,
            jnp.asarray(li, jnp.int32), scale=scale, interpret=True,
        )
        np.testing.assert_allclose(
            out, ref, rtol=2e-5, atol=2e-5, err_msg=f"layer {li}"
        )


# --- work that follows the live cache, and v1 on the same geometries ---------
#
# Pages here are 8 tokens x 2 kv heads x 16 x float32 = 1 KiB, so
# ``_decode_schedule`` gives chunks of C = 16 pages (128 tokens) folded in
# groups of G = 8 (64 tokens): the boundaries the cases below sit on.

LIVE_CASES = {
    "empty_slots_between_live_ones": dict(ctx=[0, 17, 0, 0, 300, 1, 0]),
    "ctx_on_a_page_a_group_and_a_chunk_boundary": dict(
        ctx=[8, 64, 128, 256, 129]
    ),
    "ctx_of_one_token": dict(ctx=[1, 1, 1]),
    "64_page_places_3_live_pages": dict(ctx=[20, 24, 17], pages_per_seq=64),
    "window_starts_inside_a_chunk": dict(ctx=[300, 140, 9], window=100),
    "window_and_softcap": dict(ctx=[300, 64, 0, 33], window=70, softcap=20.0),
    "softcap": dict(ctx=[200, 5], softcap=30.0),
    "fp8_pool_4_kv_heads": dict(
        ctx=[150, 0, 9], n_kv=4, pool=jnp.float8_e5m2
    ),
    "fp8_pool_2_kv_heads_padded_on_chip_goes_to_v1": dict(
        ctx=[150, 0, 9], pool=jnp.float8_e5m2
    ),
    "bf16_pool_and_queries": dict(
        ctx=[150, 0, 9], pool=jnp.bfloat16, q_dtype=jnp.bfloat16, tol=1e-2
    ),
    "mha_16_heads": dict(ctx=[70, 3], n_heads=16, n_kv=16),
    # Fewer page places than a group (8) or a chunk (16) holds.
    "1_page_place": dict(ctx=[8, 3, 0], pages_per_seq=1),
    "2_page_places": dict(ctx=[16, 9, 1], pages_per_seq=2),
    "3_page_places": dict(ctx=[24, 0, 17], pages_per_seq=3),
    "4_page_places": dict(ctx=[3, 8, 27, 32], pages_per_seq=4),
    # A narrow window: every leading page dead, the live span inside one
    # group (ctx 60), across two pages (ctx 20), or the whole context (9).
    "leading_pages_dead_then_live": dict(
        ctx=[60, 20, 9], window=10, pages_per_seq=8
    ),
}
PADDED = "fp8_pool_2_kv_heads_padded_on_chip_goes_to_v1"


def _live_setup(
    ctx, *, n_heads=8, n_kv=2, pages_per_seq=40, pool=jnp.float32,
    q_dtype=jnp.float32, layers=None, seed=30,
):
    """Queries, a pool whose pages are scattered, and the block table."""
    S, d, page = len(ctx), 16, 8
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    P = 1 + S * pages_per_seq
    lead = () if layers is None else (layers,)
    q = _rand(kq, (S, n_heads, d)).astype(q_dtype)
    k_pages = _rand(kk, lead + (P, page, n_kv, d)).astype(pool)
    v_pages = _rand(kv, lead + (P, page, n_kv, d)).astype(pool)
    bt = np.random.default_rng(seed).permutation(np.arange(1, P))
    bt = jnp.asarray(bt.reshape(S, pages_per_seq).astype(np.int32))
    return q, k_pages, v_pages, bt, jnp.asarray(ctx, jnp.int32)


def _assert_live_matches(out, ref, ctx, tol=2e-5, kernel="live"):
    live = np.asarray(ctx) > 0
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    if kernel == "live":
        assert (out[~live] == 0).all(), "an inactive slot must read zeros"
    else:  # v1 promises less: whatever it reads there is finite
        assert np.isfinite(out[~live]).all()


@pytest.mark.parametrize(
    "kernel,name",
    [
        (kernel, name)
        for kernel in ("live", "v1")
        for name in LIVE_CASES
        if (kernel, name) != ("live", PADDED)
    ],
)
def test_paged_decode_live_cases(kernel, name):
    """Both schedules on every geometry. The pool the chip pads is v1's
    alone, and reaches it the way the model's does: through the plan."""
    from llmq_tpu.ops import dispatch

    case = dict(LIVE_CASES[name])
    ctx, window = case.pop("ctx"), case.pop("window", None)
    softcap, tol = case.pop("softcap", None), case.pop("tol", 2e-5)
    q, k_pages, v_pages, bt, cl = _live_setup(ctx, **case)
    scale = q.shape[-1] ** -0.5
    ref = ref_ops.paged_decode_attention(
        q, k_pages, v_pages, bt, cl, scale=scale, sliding_window=window,
        softcap=softcap,
    )
    if name == PADDED:
        out = dispatch.decode_attention(
            q, k_pages, v_pages, bt, cl, scale=scale, sliding_window=window,
            softcap=softcap, backend="pallas",
        )
    else:
        out = DECODE_KERNELS[kernel](
            q, k_pages, v_pages, bt, cl,
            jnp.asarray([window if window else _WINDOW_DISABLED], jnp.int32),
            scale=scale, softcap=softcap, interpret=True,
        )
    _assert_live_matches(out, ref, ctx, tol, kernel)


def test_paged_decode_live_refuses_a_pool_padded_on_the_chip():
    q, k_pages, v_pages, bt, cl = _live_setup([150, 0, 9], pool=jnp.float8_e5m2)
    with pytest.raises(ValueError, match="padded on the chip"):
        pk.paged_decode_attention_live(
            q, k_pages, v_pages, bt, cl,
            jnp.asarray([_WINDOW_DISABLED], jnp.int32),
            scale=0.25, interpret=True,
        )


# The variable that once chose the decode kernel, in two pieces so that a
# search for the name finds the documents' history and nothing that runs.
RETIRED_VARIABLE = "LLMQ_DECODE_" + "KERNEL"


@pytest.mark.parametrize(
    "n_kv,pool,tp,backend,retired,plan",
    [
        (2, jnp.bfloat16, 1, "pallas", None, "live"),
        (1, jnp.bfloat16, 1, "pallas", None, "v1"),
        (4, jnp.float8_e5m2, 1, "pallas", None, "live"),
        (2, jnp.float8_e5m2, 1, "pallas", None, "v1"),
        (1, jnp.float8_e5m2, 1, "pallas", None, "v1"),
        (1, jnp.float32, 1, "pallas", None, "live"),
        (4, jnp.bfloat16, 4, "pallas", None, "xla"),  # one kv head a shard
        (2, jnp.bfloat16, 1, "xla", None, "xla"),
        (2, jnp.bfloat16, 1, "pallas", "v2", "live"),
    ],
    ids=[
        "bf16_2_kv", "bf16_1_kv", "fp8_4_kv", "fp8_2_kv", "fp8_1_kv",
        "f32_1_kv", "tp4_1_kv_a_shard", "backend_xla",
        "the_retired_variable_is_not_read",
    ],
)
def test_decode_kernel_plan_names_what_runs(
    monkeypatch, n_kv, pool, tp, backend, retired, plan
):
    """The plan is a function of the pool's shape and the backend, and
    what it names is the kernel ``decode_attention`` puts in the jaxpr."""
    from llmq_tpu.ops import dispatch
    from llmq_tpu.parallel.mesh import make_mesh

    monkeypatch.delenv(RETIRED_VARIABLE, raising=False)
    if retired:
        monkeypatch.setenv(RETIRED_VARIABLE, retired)
    mesh = (
        make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp])
        if tp > 1
        else None
    )
    n_heads = 8
    assert dispatch.decode_kernel_plan(n_heads, n_kv, pool, mesh, backend) == plan
    if plan == "xla":
        return
    ctx = [20, 0, 9]
    q, k_pages, v_pages, bt, cl = _live_setup(
        ctx, n_heads=n_heads, n_kv=n_kv, pool=pool, pages_per_seq=4
    )
    grids = _pallas_grids(
        dispatch.decode_attention, q, k_pages, v_pages, bt, cl,
        scale=0.25, backend=backend,
    )
    assert grids == [(len(ctx),) if plan == "live" else (len(ctx), 4)]


def test_paged_decode_live_stacked_pool_traced_layer_and_window():
    """The model's layer scan: the whole stacked pool, the layer and the
    window traced scalars of one jitted program."""
    ctx = [130, 0, 64, 7]
    q, k_pages, v_pages, bt, cl = _live_setup(ctx, layers=3)
    scale = q.shape[-1] ** -0.5

    @jax.jit
    def both_layers(k_pages, v_pages, windows):
        def layer(_, xs):
            li, window = xs
            return None, pk.paged_decode_attention_live(
                q, k_pages, v_pages, bt, cl, window, li,
                scale=scale, interpret=True,
            )

        return jax.lax.scan(layer, None, (jnp.arange(3), windows))[1]

    windows = [_WINDOW_DISABLED, 50, 9]
    outs = both_layers(k_pages, v_pages, jnp.asarray(windows, jnp.int32))
    for li, window in enumerate(windows):
        ref = ref_ops.paged_decode_attention(
            q, k_pages, v_pages, bt, cl, scale=scale, layer=li,
            sliding_window=None if window == _WINDOW_DISABLED else window,
        )
        _assert_live_matches(outs[li], ref, ctx)


def test_paged_decode_live_under_shard_over_heads():
    """tp=2 through the engine's dispatch: the kernel under ``shard_map``,
    queries and pool sharded over the heads (4 query / 2 kv a shard)."""
    from llmq_tpu.ops import dispatch
    from llmq_tpu.parallel.mesh import make_mesh

    ctx = [130, 0, 64, 7]
    q, k_pages, v_pages, bt, cl = _live_setup(ctx, n_kv=4, layers=2)
    mesh = make_mesh(tensor_parallel=2, devices=jax.devices()[:2])
    assert dispatch.decode_kernel_plan(8, 4, q.dtype, mesh, "pallas") == "live"
    scale = q.shape[-1] ** -0.5
    li = jnp.asarray(1, jnp.int32)
    out = jax.jit(
        lambda *a: dispatch.decode_attention(
            *a, scale=scale, mesh=mesh, backend="pallas", layer=li
        )
    )(q, k_pages, v_pages, bt, cl)
    ref = ref_ops.paged_decode_attention(
        q, k_pages, v_pages, bt, cl, scale=scale, layer=li
    )
    _assert_live_matches(out, ref, ctx)


def _pallas_grids(fn, *args, **kwargs):
    """The grid of every ``pallas_call`` in ``fn``'s jaxpr."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(functools.partial(fn, **kwargs))(*args).jaxpr)
    return grids


def _count_copy_starts(monkeypatch):
    """Every ``make_async_copy(...).start()`` of a kernel traced from here
    on appends to the returned list when it runs."""
    started = []
    real_copy = pk.pltpu.make_async_copy

    class Counted:
        def __init__(self, copy):
            self.copy = copy

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(
        pk.pltpu, "make_async_copy", lambda *a: Counted(real_copy(*a))
    )
    return started


def test_the_default_decode_schedule_follows_the_live_set(monkeypatch):
    """No grid axis spans the page places, and the copies a call starts
    are the live pages' (K and V), whatever ``pages_per_seq`` is: a later
    PR cannot bring the fixed grid back unnoticed."""
    from llmq_tpu.ops import dispatch

    assert dispatch.decode_kernel_plan(8, 2, jnp.float32, None, "pallas") == "live"

    started = _count_copy_starts(monkeypatch)
    ctx = [20, 0, 300, 64, 1]
    live_pages = sum(-(-c // 8) for c in ctx)
    window = jnp.asarray([_WINDOW_DISABLED], jnp.int32)
    copies, grids = {}, {}
    for pages_per_seq in (40, 64):
        q, k_pages, v_pages, bt, cl = _live_setup(
            ctx, pages_per_seq=pages_per_seq
        )
        # A softcap no other test uses: this kernel is traced afresh,
        # with the counting copies.
        call = dict(scale=0.25, softcap=29.5 + pages_per_seq, interpret=True)
        grids[pages_per_seq] = _pallas_grids(
            pk.paged_decode_attention_live,
            q, k_pages, v_pages, bt, cl, window, **call,
        )
        started.clear()
        jax.block_until_ready(
            pk.paged_decode_attention_live(
                q, k_pages, v_pages, bt, cl, window, **call
            )
        )
        jax.effects_barrier()
        copies[pages_per_seq] = len(started)
    assert grids[40] == grids[64] == [(len(ctx),)]
    assert copies[40] == copies[64] == 2 * live_pages
    # v1, for contrast, spans the places.
    q, k_pages, v_pages, bt, cl = _live_setup(ctx, pages_per_seq=64)
    assert _pallas_grids(
        pk.paged_decode_attention_pallas,
        q, k_pages, v_pages, bt, cl, window, scale=0.25, interpret=True,
    ) == [(len(ctx), 64)]


@pytest.mark.parametrize(
    "page_bytes,schedule",
    [
        (64 * 1024, (16, 8)),  # qwen2.5-3b: 128 tokens x 2 heads x 128 bf16
        (128 * 1024, (8, 4)),  # llama3.1-8b at tp=2, qwen2.5-7b
        (512 * 1024, (2, 1)),
        (1024 * 1024, (1, 1)),
        (4 * 1024 * 1024, (1, 1)),
        (1024, (16, 8)),  # the tiny pages of these tests
    ],
)
def test_decode_schedule_comes_from_the_page_bytes(page_bytes, schedule):
    C, G = pk._decode_schedule(page_bytes)
    assert (C, G) == schedule and C % G == 0
    assert 4 * C * page_bytes <= max(4 * page_bytes, 4 * 1024 * 1024)


def test_flash_prefill_bf16_matches_reference():
    """bf16 inputs: the kernel multiplies in bf16 (f32 softmax stats +
    accumulator) — the MXU full-rate path — and must track the XLA
    reference, whose einsums also multiply bf16 in bf16."""
    B, T, n_heads, n_kv, d = 2, 32, 4, 2, 16
    lengths = jnp.asarray([T, T // 2], jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.key(40), 3)
    q = _rand(kq, (B, T, n_heads, d)).astype(jnp.bfloat16)
    k = _rand(kk, (B, T, n_kv, d)).astype(jnp.bfloat16)
    v = _rand(kv, (B, T, n_kv, d)).astype(jnp.bfloat16)
    ref = ref_ops.full_prefill_attention(
        q, k, v, scale=d**-0.5, lengths=lengths
    )
    out = pk.flash_prefill_attention_pallas(
        q, k, v, lengths, jnp.asarray([_WINDOW_DISABLED], jnp.int32),
        scale=d**-0.5, block_q=16, block_kv=16, interpret=True,
    )
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(
            np.asarray(out[b, :n], np.float32),
            np.asarray(ref[b, :n], np.float32),
            rtol=3e-2, atol=3e-2,
        )


@pytest.mark.parametrize(
    "n_heads,n_kv,window,softcap,T,block",
    [
        (4, 4, None, None, 32, 16),  # MHA, multiple kv blocks
        (8, 2, None, None, 48, 16),  # GQA, T not multiple of 32
        (4, 2, 9, None, 64, 16),  # sliding window crossing blocks
        (4, 1, None, 25.0, 32, 32),  # softcap, single block
        (6, 3, 11, 15.0, 40, 16),  # all together, padded T
    ],
)
def test_flash_prefill_matches_reference(n_heads, n_kv, window, softcap, T, block):
    B, d = 3, 16
    lengths = jnp.asarray([T, T // 2, 3], jnp.int32)
    key = jax.random.key(2)
    kq, kk, kv = jax.random.split(key, 3)
    q = _rand(kq, (B, T, n_heads, d))
    k = _rand(kk, (B, T, n_kv, d))
    v = _rand(kv, (B, T, n_kv, d))
    scale = d**-0.5
    ref = ref_ops.full_prefill_attention(
        q, k, v, scale=scale, lengths=lengths,
        sliding_window=window, softcap=softcap,
    )
    out = pk.flash_prefill_attention_pallas(
        q, k, v, lengths,
        jnp.asarray([window if window else _WINDOW_DISABLED], jnp.int32),
        scale=scale, softcap=softcap,
        block_q=block, block_kv=block, interpret=True,
    )
    # Rows past a sequence's length are garbage in both impls: compare
    # only valid rows.
    for b in range(B):
        n = int(lengths[b])
        np.testing.assert_allclose(
            out[b, :n], ref[b, :n], rtol=2e-5, atol=2e-5,
            err_msg=f"batch row {b}",
        )


def test_dispatch_selects_xla_off_tpu(monkeypatch):
    from llmq_tpu.ops import dispatch

    monkeypatch.delenv("LLMQ_ATTN_BACKEND", raising=False)
    assert dispatch.resolve_backend() == "xla"
    monkeypatch.setenv("LLMQ_ATTN_BACKEND", "pallas")
    assert dispatch.resolve_backend() == "pallas"
    monkeypatch.setenv("LLMQ_ATTN_BACKEND", "bogus")
    with pytest.raises(ValueError):
        dispatch.resolve_backend()


def test_dispatch_pallas_path_through_model():
    """Full tiny-model decode parity: pallas backend vs xla backend."""
    from llmq_tpu.models.config import ModelConfig
    from llmq_tpu.models.transformer import (
        Transformer,
        init_params,
        make_kv_pages,
    )

    config = ModelConfig.tiny(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=64,
    )
    params = init_params(config, jax.random.key(0))
    S, page_size, num_pages, pages_per_seq = 3, 8, 16, 4
    k_pages, v_pages = make_kv_pages(config, num_pages, page_size, jnp.float32)
    tokens = jnp.asarray([1, 2, 3], jnp.int32)
    ctx = jnp.asarray([3, 5, 0], jnp.int32)
    bt = jnp.arange(1, 1 + S * pages_per_seq, dtype=jnp.int32).reshape(S, -1)
    active = jnp.asarray([True, True, False])

    outs = {}
    for backend in ("xla", "pallas"):
        model = Transformer(config, attn_backend=backend)
        logits, _, _ = model.decode(
            params, tokens, ctx, k_pages, v_pages, bt, active
        )
        outs[backend] = np.asarray(logits)
    np.testing.assert_allclose(
        outs["pallas"][:2], outs["xla"][:2], rtol=1e-4, atol=1e-4
    )


def test_write_prompt_kv_pages_matches_token_scatter():
    """Page-granular prefill write == token scatter on page-aligned buckets
    (positions 0..T-1 per row, zero-padded block tables → scratch page 0)."""
    L, Pp, page, n_kv, d = 3, 9, 8, 2, 16
    B, T = 2, 16  # two pages per row
    key = jax.random.key(1)
    k1, k2, k3 = jax.random.split(key, 3)
    k_new = _rand(k1, (B, T, n_kv, d))
    v_new = _rand(k2, (B, T, n_kv, d))
    base_k = _rand(k3, (L, Pp, page, n_kv, d))
    base_v = base_k + 1.0
    # row 0: full-length prompt; row 1: short (12 of 16) — garbage tail
    lengths = jnp.asarray([16, 12], jnp.int32)
    bt = jnp.zeros((B, 4), jnp.int32)
    bt = bt.at[0, :2].set(jnp.asarray([3, 5]))
    bt = bt.at[1, :2].set(jnp.asarray([7, 2]))
    pos_grid = jnp.arange(T)[None, :].astype(jnp.int32)
    positions = jnp.where(pos_grid < lengths[:, None], pos_grid, -1)
    li = jnp.asarray(1, jnp.int32)

    tok_k, tok_v = ref_ops.write_kv_pages(
        base_k, base_v, k_new, v_new, bt, positions, layer=li
    )
    pg_k, pg_v = ref_ops.write_prompt_kv_pages(
        base_k, base_v, k_new, v_new, bt, li
    )
    # Every position the token scatter wrote must match; the page path may
    # additionally fill the dead tail of row 1's last page (never read) and
    # the scratch page 0 — exclude both.
    np.testing.assert_allclose(pg_k[1, 3], tok_k[1, 3])
    np.testing.assert_allclose(pg_k[1, 5], tok_k[1, 5])
    np.testing.assert_allclose(pg_v[1, 7, :4], tok_v[1, 7, :4])
    np.testing.assert_allclose(pg_k[1, 7, :8], tok_k[1, 7, :8])
    np.testing.assert_allclose(pg_k[1, 2, :4], tok_k[1, 2, :4])
    # untouched layers and pages stay untouched
    np.testing.assert_allclose(pg_k[0], base_k[0])
    np.testing.assert_allclose(pg_k[2], base_k[2])
    np.testing.assert_allclose(pg_v[1, 4], base_v[1, 4])


@pytest.mark.parametrize(
    "n_heads,n_kv,window,softcap,block_q",
    [
        (4, 4, None, None, 8),  # MHA
        (8, 2, None, None, 8),  # GQA
        (8, 2, 13, None, 4),  # sliding window
        (4, 1, None, 30.0, 16),  # softcap, block > chunk
        (6, 3, 7, 20.0, 8),  # everything, odd group
    ],
)
def test_paged_prefill_chunk_matches_reference(
    n_heads, n_kv, window, softcap, block_q
):
    """Chunked-prefill kernel vs the XLA gather reference: a mid-prompt
    chunk whose queries attend earlier chunks' pages + their own."""
    S, d, page_size, pages_per_seq, C = 3, 16, 8, 4, 10
    key = jax.random.key(7)
    kq, kp_ = jax.random.split(key)
    q = _rand(kq, (S, C, n_heads, d))
    # cached context lens (pages already written up to these positions)
    starts = [0, 5, 17]  # chunk begins at these absolute positions
    valids = [10, 10, 7]  # row 2 has a ragged tail
    k_pages, v_pages, bt, _ = _paged_setup(
        kp_, S=S, n_kv=n_kv, d=d, page_size=page_size,
        pages_per_seq=pages_per_seq, ctx_lens=[0, 0, 0],
    )
    positions = np.full((S, C), -1, np.int32)
    for r in range(S):
        positions[r, : valids[r]] = np.arange(starts[r], starts[r] + valids[r])
    scale = d**-0.5
    ref = ref_ops.paged_prefill_attention(
        q, k_pages, v_pages, bt, jnp.asarray(positions),
        scale=scale, sliding_window=window, softcap=softcap,
    )
    out = pk.paged_prefill_attention_pallas(
        q, k_pages, v_pages, bt,
        jnp.asarray(starts, jnp.int32), jnp.asarray(valids, jnp.int32),
        jnp.asarray([window if window else _WINDOW_DISABLED], jnp.int32),
        scale=scale, softcap=softcap, block_q=block_q, interpret=True,
    )
    for r in range(S):
        np.testing.assert_allclose(
            out[r, : valids[r]], ref[r, : valids[r]],
            rtol=2e-5, atol=2e-5, err_msg=f"row {r}",
        )
    assert np.isfinite(np.asarray(out)).all()


def test_paged_prefill_chunk_stacked_layer():
    S, n_heads, n_kv, d, page_size, pages_per_seq, C, L = 2, 4, 2, 16, 8, 3, 6, 3
    key = jax.random.key(8)
    q = _rand(key, (S, C, n_heads, d))
    P_ = 1 + S * pages_per_seq
    k_pages = _rand(jax.random.key(9), (L, P_, page_size, n_kv, d))
    v_pages = _rand(jax.random.key(10), (L, P_, page_size, n_kv, d))
    bt = jnp.arange(1, 1 + S * pages_per_seq, dtype=jnp.int32).reshape(S, -1)
    positions = np.full((S, C), -1, np.int32)
    positions[0, :6] = np.arange(3, 9)
    positions[1, :4] = np.arange(0, 4)
    scale = d**-0.5
    for li in (0, 2):
        ref = ref_ops.paged_prefill_attention(
            q, k_pages, v_pages, bt, jnp.asarray(positions),
            scale=scale, layer=jnp.asarray(li, jnp.int32),
        )
        out = pk.paged_prefill_attention_pallas(
            q, k_pages, v_pages, bt,
            jnp.asarray([3, 0], jnp.int32), jnp.asarray([6, 4], jnp.int32),
            jnp.asarray([_WINDOW_DISABLED], jnp.int32),
            jnp.asarray(li, jnp.int32), scale=scale, block_q=4,
            interpret=True,
        )
        np.testing.assert_allclose(
            out[0, :6], ref[0, :6], rtol=2e-5, atol=2e-5, err_msg=f"l{li} r0"
        )
        np.testing.assert_allclose(
            out[1, :4], ref[1, :4], rtol=2e-5, atol=2e-5, err_msg=f"l{li} r1"
        )


def test_chunked_prefill_dispatch_pallas_matches_xla():
    """dispatch.chunked_prefill_attention: the pallas path's contiguous
    (start, num_valid) conversion must agree with the xla path."""
    from llmq_tpu.ops import dispatch

    S, C, n_heads, n_kv, d, page_size, pages_per_seq = 2, 6, 4, 2, 16, 8, 3
    key = jax.random.key(11)
    q = _rand(key, (S, C, n_heads, d))
    k_pages, v_pages, bt, _ = _paged_setup(
        jax.random.key(12), S=S, n_kv=n_kv, d=d, page_size=page_size,
        pages_per_seq=pages_per_seq, ctx_lens=[0, 0],
    )
    positions = np.full((S, C), -1, np.int32)
    positions[0, :6] = np.arange(4, 10)
    positions[1, :3] = np.arange(0, 3)
    outs = {}
    for backend in ("xla", "pallas"):
        outs[backend] = dispatch.chunked_prefill_attention(
            q, k_pages, v_pages, bt, jnp.asarray(positions),
            scale=d**-0.5, backend=backend,
        )
    np.testing.assert_allclose(
        outs["pallas"][0, :6], outs["xla"][0, :6], rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        outs["pallas"][1, :3], outs["xla"][1, :3], rtol=2e-5, atol=2e-5
    )


def test_decode_one_bf16_kv_head_through_model_runs_v1(monkeypatch):
    """A tiny model whose bf16 pool the chip would pad (one kv head),
    served by the engine on the pallas backend: token for token the XLA
    path's answer, and ``stats()`` names the schedule that ran."""
    from llmq_tpu.engine.engine import EngineConfig, EngineCore
    from llmq_tpu.engine.sampling import SamplingParams
    from llmq_tpu.engine.tokenizer import ByteTokenizer
    from llmq_tpu.models.config import ModelConfig
    from llmq_tpu.models.transformer import init_params
    from llmq_tpu.parallel import make_mesh

    config = ModelConfig.tiny(
        vocab_size=304, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=1, intermediate_size=64,
    )
    params = init_params(config, jax.random.key(0), dtype=jnp.float32)
    served = {}
    for backend, kernel in (("xla", "xla"), ("pallas", "v1")):
        monkeypatch.setenv("LLMQ_ATTN_BACKEND", backend)
        core = EngineCore(
            config, params, ByteTokenizer(),
            mesh=make_mesh(tensor_parallel=1),
            engine_config=EngineConfig(
                max_num_seqs=2, max_model_len=64, page_size=8, num_pages=20,
                kv_dtype=jnp.bfloat16, min_prefill_bucket=16,
            ),
        )
        seqs = [
            core.add_request(
                f"r{i}", prompt=prompt,
                params=SamplingParams(
                    temperature=0.0, max_tokens=12, ignore_eos=True
                ),
            )
            for i, prompt in enumerate(("one kv head", "a padded pool, v1"))
        ]
        while not all(seq.finish_reason for seq in seqs):
            core.step()
        assert core.stats()["decode_kernel"] == kernel
        served[backend] = [list(seq.output_ids) for seq in seqs]
    assert served["pallas"] == served["xla"]
    assert all(len(ids) == 12 for ids in served["xla"])


class TestMixedQueryGrid:
    """ops/attention.mixed_query_grid: the [S, C] grid one fused mixed
    (decode + piggybacked prefill) dispatch consumes. Every row must
    satisfy the chunked-prefill kernel contract — a leading contiguous
    run of valid positions, then -1 padding."""

    def _grid(self, **over):
        kw = dict(
            tokens=jnp.asarray([7, 8, 9, 10], jnp.int32),
            ctx=jnp.asarray([3, 0, 5, 2], jnp.int32),
            active=jnp.asarray([True, False, True, False]),
            chunk_tokens=jnp.asarray([21, 22, 23], jnp.int32),
            chunk_positions=jnp.asarray([4, 5, -1], jnp.int32),
            slot=jnp.asarray(1, jnp.int32),
            max_kv_pos=64,
        )
        kw.update(over)
        return ref_ops.mixed_query_grid(**kw)

    def test_decode_rows_are_single_position_runs(self):
        q_tok, q_pos, is_chunk = self._grid()
        np.testing.assert_array_equal(np.asarray(q_tok[0]), [7, 0, 0])
        np.testing.assert_array_equal(np.asarray(q_pos[0]), [3, -1, -1])
        np.testing.assert_array_equal(np.asarray(q_pos[2]), [5, -1, -1])

    def test_chunk_row_carries_segment(self):
        q_tok, q_pos, is_chunk = self._grid()
        np.testing.assert_array_equal(np.asarray(is_chunk),
                                      [False, True, False, False])
        np.testing.assert_array_equal(np.asarray(q_tok[1]), [21, 22, 23])
        np.testing.assert_array_equal(np.asarray(q_pos[1]), [4, 5, -1])

    def test_inactive_non_chunk_rows_are_all_padding(self):
        _, q_pos, is_chunk = self._grid()
        assert not bool(is_chunk[3])  # inactive but not the piggy slot
        np.testing.assert_array_equal(np.asarray(q_pos[3]), [-1, -1, -1])

    def test_active_piggy_slot_decodes_normally(self):
        # After activation (final segment scattered) the slot is active:
        # it must get its decode position, not the chunk segment.
        q_tok, q_pos, is_chunk = self._grid(
            active=jnp.asarray([True, True, True, False])
        )
        assert not bool(is_chunk[1])
        np.testing.assert_array_equal(np.asarray(q_tok[1]), [8, 0, 0])
        np.testing.assert_array_equal(np.asarray(q_pos[1]), [0, -1, -1])

    def test_past_page_map_routes_to_scratch(self):
        _, q_pos, _ = self._grid(
            ctx=jnp.asarray([3, 0, 64, 2], jnp.int32), max_kv_pos=64
        )
        np.testing.assert_array_equal(np.asarray(q_pos[2]), [-1, -1, -1])

    def test_rows_keep_leading_contiguous_contract(self):
        _, q_pos, _ = self._grid()
        pos = np.asarray(q_pos)
        for row in pos:
            valid = row >= 0
            n = int(valid.sum())
            assert valid[:n].all() and not valid[n:].any(), row
            if n > 1:
                np.testing.assert_array_equal(
                    row[:n], np.arange(row[0], row[0] + n)
                )


# ---------------------------------------------------------------------------
# Latent (MLA) paged decode: the kernel against the XLA loop
# ---------------------------------------------------------------------------

LATENT_PAGE = 16  # a chunk is 16 pages at these widths: 256 tokens
# (heads, rank, W, Wp): openpangu's 128 heads over rows of 576 kept in 640,
# and ling's 32.
LATENT_SHAPES = {"heads128": (128, 512, 576, 640), "heads32": (32, 512, 576, 640)}
# An empty slot, one token, exactly one page, one token past a chunk, a
# ragged row, and the block table's last place.
LATENT_PLACES = 18
LATENT_CTX = [0, 1, LATENT_PAGE, 16 * LATENT_PAGE + 1, 93, LATENT_PLACES * LATENT_PAGE]


def _latent_setup(shape, dtype, *, ctx=LATENT_CTX, layers=3, garbage=None):
    """A stacked latent pool whose rows hold zeros beyond ``W``, queries,
    and a block table that gives every live place a scattered page of its
    own; a dead place holds page 0, or ``garbage``."""
    n_heads, rank, W, Wp = LATENT_SHAPES[shape]
    rng = np.random.default_rng(7)
    need = [-(-c // LATENT_PAGE) for c in ctx]
    P = 1 + sum(need) + 3
    pages = np.zeros((layers, P, LATENT_PAGE, Wp), np.float32)
    pages[..., :W] = rng.normal(size=(layers, P, LATENT_PAGE, W)) * 0.3
    q = rng.normal(size=(len(ctx), n_heads, W)) * 0.3
    bt = np.full((len(ctx), LATENT_PLACES), 0 if garbage is None else garbage, np.int32)
    order, at = rng.permutation(np.arange(1, P)), 0
    for s, n in enumerate(need):
        bt[s, :n] = order[at : at + n]
        at += n
    return (
        jnp.asarray(q, dtype), jnp.asarray(pages, dtype), jnp.asarray(bt),
        jnp.asarray(ctx, jnp.int32), rank,
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2), (jnp.float32, 2e-5)], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", LATENT_SHAPES)
def test_latent_decode_live_matches_the_xla_loop(shape, dtype, tol):
    """Ragged contexts over a layer > 0 of a stacked pool: the kernel is
    the XLA loop's result in its precision (bf16 operands, float32
    softmax), an empty slot gives zeros and nothing is NaN."""
    q, pages, bt, cl, rank = _latent_setup(shape, dtype)
    layer = jnp.asarray(2, jnp.int32)
    ref = ref_ops.latent_paged_decode_attention(
        q, pages, bt, cl, scale=0.07, rank=rank, layer=layer
    )
    out = pk.latent_paged_decode_attention_live(
        q, pages, bt, cl, layer, scale=0.07, rank=rank, interpret=True
    )
    assert out.shape == ref.shape == (len(LATENT_CTX), q.shape[1], rank)
    assert out.dtype == q.dtype
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    live = np.asarray(LATENT_CTX) > 0
    assert np.isfinite(out).all()
    assert not out[~live].any()
    np.testing.assert_allclose(out[live], ref[live], rtol=0, atol=tol)
    # another layer's rows are other rows
    other = pk.latent_paged_decode_attention_live(
        q, pages, bt, cl, jnp.asarray(0, jnp.int32), scale=0.07, rank=rank, interpret=True
    )
    assert np.abs(np.asarray(other, np.float32)[live] - out[live]).max() > 10 * tol


@pytest.mark.parametrize("shape", LATENT_SHAPES)
def test_latent_decode_live_never_reads_a_dead_page_place(shape):
    """Dead places of the block table may hold anything, a page id far
    outside the pool included: the output is bit for bit what it is with
    the scratch page there."""
    outs = []
    for garbage in (None, 10**6):
        q, pages, bt, cl, rank = _latent_setup(shape, jnp.bfloat16, garbage=garbage)
        outs.append(np.asarray(
            pk.latent_paged_decode_attention_live(
                q, pages, bt, cl, jnp.asarray(1, jnp.int32),
                scale=0.07, rank=rank, interpret=True,
            ),
            np.float32,
        ))
    assert np.array_equal(*outs)


def test_latent_decode_live_under_the_layer_scan():
    """The model's layer scan: the whole stacked pool, the layer a traced
    index of one jitted program."""
    q, pages, bt, cl, rank = _latent_setup("heads32", jnp.float32, ctx=[40, 0, 17, 270])

    def attend(fn, **kw):
        def layer(_, li):
            return None, fn(q, pages, bt, cl, scale=0.07, rank=rank, layer=li, **kw)

        return jax.jit(lambda: jax.lax.scan(layer, None, jnp.arange(pages.shape[0]))[1])()

    def kernel(q, pages, bt, cl, *, layer, **kw):
        return pk.latent_paged_decode_attention_live(q, pages, bt, cl, layer, **kw)

    out = attend(kernel, interpret=True)
    ref = attend(ref_ops.latent_paged_decode_attention)
    live = np.asarray([40, 0, 17, 270]) > 0
    np.testing.assert_allclose(
        np.asarray(out)[:, live], np.asarray(ref)[:, live], rtol=0, atol=2e-5
    )


def test_latent_decode_live_refuses_a_pool_padded_on_the_chip():
    q, pages, bt, cl, rank = _latent_setup("heads32", jnp.bfloat16)
    with pytest.raises(ValueError, match="padded on the chip"):
        pk.latent_paged_decode_attention_live(
            q, pages[..., :576], bt, cl, jnp.asarray(0, jnp.int32),
            scale=0.07, rank=rank, interpret=True,
        )


@pytest.mark.parametrize(
    "page,width,pool,tp,backend,plan",
    [
        (128, 640, jnp.bfloat16, 1, "pallas", "latent_live"),
        (128, 640, jnp.float32, 1, "pallas", "latent_live"),
        (16, 640, jnp.bfloat16, 1, "pallas", "latent_live"),
        (128, 576, jnp.bfloat16, 1, "pallas", "xla"),  # rows not whole lane tiles
        (8, 640, jnp.bfloat16, 1, "pallas", "xla"),  # a page of half a packed tile
        (128, 640, jnp.float8_e5m2, 1, "pallas", "xla"),  # one-byte values
        (128, 640, jnp.bfloat16, 2, "pallas", "xla"),  # a mesh of several devices
        (128, 640, jnp.bfloat16, 1, "xla", "xla"),
    ],
    ids=[
        "bf16", "f32", "bf16_small_pages", "rows_of_576", "pages_of_8_bf16",
        "fp8", "tp2", "backend_xla",
    ],
)
def test_latent_decode_kernel_plan_names_what_runs(page, width, pool, tp, backend, plan):
    """The latent plan is a function of the pool's shape, the mesh and the
    backend, and what it names is what ``latent_decode_attention`` puts in
    the jaxpr: one grid step a slot, or no kernel."""
    from llmq_tpu.ops import dispatch
    from llmq_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp])
    assert dispatch.latent_decode_kernel_plan(
        512, page, width, pool, mesh, backend
    ) == plan
    assert dispatch.latent_decode_kernel_plan(
        512, page, width, pool, None, backend
    ) == (plan if tp == 1 else "latent_live")
    # a rank that is not whole lane tiles (the tiny test models') stays XLA
    assert dispatch.latent_decode_kernel_plan(
        32, page, width, pool, mesh, backend
    ) == "xla"
    S, places = 3, 4
    grids = _pallas_grids(
        dispatch.latent_decode_attention,
        jnp.zeros((S, 4, min(width, 576)), jnp.bfloat16),
        jnp.zeros((2, 9, page, width), pool),
        jnp.zeros((S, places), jnp.int32), jnp.ones((S,), jnp.int32),
        scale=0.07, rank=512, layer=jnp.asarray(1, jnp.int32), mesh=mesh, backend=backend,
    )
    assert grids == ([(S,)] if plan == "latent_live" else [])


def test_the_latent_schedule_follows_the_live_set(monkeypatch):
    """The copies a call starts are each row's own pages, one a page,
    whatever ``pages_per_seq`` and the longest row are, and that is the
    count ``latent_decode_pages_visited`` gives for the kernel's plan:
    at least what is live, at most what the XLA loop gathers."""
    started = _count_copy_starts(monkeypatch)
    ctx = [20, 0, 270, 64, 1]
    q, pages, bt, cl, rank = _latent_setup("heads32", jnp.float32, ctx=ctx)
    live_pages = sum(-(-c // LATENT_PAGE) for c in ctx)
    visited = ref_ops.latent_decode_pages_visited
    for places in (LATENT_PLACES, 40):
        table = jnp.pad(bt, ((0, 0), (0, places - LATENT_PLACES)))
        # A scale no other test uses: this kernel is traced afresh, with
        # the counting copies.
        call = dict(scale=0.05 + places / 1000, rank=rank, interpret=True)
        layer = jnp.asarray(1, jnp.int32)
        assert _pallas_grids(
            pk.latent_paged_decode_attention_live, q, pages, table, cl, layer, **call
        ) == [(len(ctx),)]
        started.clear()
        jax.block_until_ready(
            pk.latent_paged_decode_attention_live(q, pages, table, cl, layer, **call)
        )
        jax.effects_barrier()
        assert len(started) == live_pages
        assert (
            live_pages
            == visited("latent_live", ctx, len(ctx), places, LATENT_PAGE)
            <= visited("xla", ctx, len(ctx), places, LATENT_PAGE)
        )


@pytest.mark.parametrize(
    "n_heads,page_size,itemsize,pages",
    [
        (128, 128, 2, 8),  # openpangu: a score tile of [128, 1,024] float32
        (32, 128, 2, 16),  # ling: 2.5 MiB of pages an update
        (128, 128, 4, 8),
        (8, 128, 4, 8),  # a float32 pool: the bytes an update folds in
        (128, 16, 2, 16),  # the small pages of these tests
    ],
)
def test_latent_schedule_comes_from_the_page_bytes_and_the_heads(
    n_heads, page_size, itemsize, pages
):
    G = pk._latent_decode_schedule(page_size * 640 * itemsize, n_heads, page_size)
    assert G == pages
    # two chunks of pages, the score tile and the accumulator fit VMEM's default limit
    assert 2 * G * page_size * 640 * itemsize + n_heads * (G * page_size + 512) * 4 < 12 * 2**20


# ---------------------------------------------------------------------------
# Flash prefill of expanded latent attention
# ---------------------------------------------------------------------------


def _mla_rows(B, T, n, seed=0):
    """q_c, q_r, kv (a head's 128 content keys, then its 128 values), k_r."""
    keys = jax.random.split(jax.random.key(seed), 4)
    shapes = ((B, T, n, 128), (B, T, n, 64), (B, T, n, 256), (B, T, 64))
    return [
        jax.random.normal(k, s, jnp.float32).astype(jnp.bfloat16)
        for k, s in zip(keys, shapes)
    ]


@pytest.mark.parametrize(
    "n,T,lengths,blocks,by_row",
    [
        (32, 128, [128], (64, 64), False),  # ling's heads, a full prompt
        (128, 128, [100], (64, 64), False),  # openpangu's heads, a ragged one
        (4, 200, [200, 77, 1], (64, 64), False),  # T no multiple of the block
        (4, 192, [130, 192], (128, 64), False),  # two key blocks a query block
        (4, 192, [60, 192], (64, 128), False),  # two query blocks a key block
        (2, 96, [96, 50], (512, 512), False),  # one block holds the bucket
        (4, 128, [128, 3, 90, 0], (64, 64), True),  # B = 4, a row at a time
    ],
    ids=["heads32", "heads128", "ragged_T200", "bq128_bk64", "bq64_bk128",
         "one_block", "four_rows_row_at_a_time"],
)
def test_mla_flash_prefill_matches_the_blocked_reference(n, T, lengths, blocks, by_row):
    """The flash kernel of expanded latent attention (scores over 128 +
    64, values over 128, bf16 into the MXU, float32 softmax state) against
    ``blocked_prefill_attention`` over every row of each prompt; rows past
    a prompt's length are padding, and finite. Keys and values go in as
    ``W_kvb``'s product has them, side by side a head."""
    from llmq_tpu.ops import dispatch

    B = len(lengths)
    rows = _mla_rows(B, T, n, seed=n + T)
    lens = jnp.asarray(lengths, jnp.int32)
    scale = 192**-0.5
    want = dispatch.mla_prefill_attention(*rows, scale=scale, lengths=lens, plan="xla")
    flash = functools.partial(
        pk.mla_flash_prefill_attention, scale=scale,
        block_q=blocks[0], block_kv=blocks[1], interpret=True,
    )
    if by_row:  # as ``_mla_prefill`` takes a bucket above MLA_PREFILL_HEAD_TOKENS
        got = jax.lax.map(
            lambda args: flash(*(a[None] for a in args))[0], (*rows, lens)
        )
    else:
        got = flash(*rows, lens)
    assert got.shape == want.shape == (B, T, n, 128) and got.dtype == jnp.bfloat16
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    for b, length in enumerate(lengths):
        np.testing.assert_allclose(
            np.asarray(got[b, :length], np.float32),
            np.asarray(want[b, :length], np.float32),
            atol=2e-2, rtol=2e-2,
        )


@pytest.mark.parametrize("lengths", [[96], [72], [1], [0], [96, 72, 1, 0]],
                         ids=["full", "three_quarters", "one", "empty", "four_rows"])
def test_mla_flash_prefill_rows_past_a_length_hold_numbers(lengths):
    """Rows past a prompt's length are padding: whatever they hold goes
    through ``o_proj`` and the next layer's norm, so it has to be numbers
    (a block wholly past the prompt is never folded: its running sum is 0
    and it reads as zeros), for a full prompt, one of three quarters of the
    bucket, one token, and an empty row of a padded batch."""
    T = 96
    rows = _mla_rows(len(lengths), T, 4, seed=11)
    got = pk.mla_flash_prefill_attention(
        *rows, jnp.asarray(lengths, jnp.int32), scale=192**-0.5,
        block_q=32, block_kv=32, interpret=True,
    ).astype(jnp.float32)
    assert bool(jnp.isfinite(got).all())
    for b, length in enumerate(lengths):
        whole_blocks_past = -(-length // 32) * 32
        assert not np.asarray(got[b, whole_blocks_past:]).any()


def test_mla_flash_prefill_under_a_layer_scan_with_a_traced_layer_index():
    """As a scanned group of latent layers runs it: the keys and values
    raised inside the scan's body from the latent rows by the layer's own
    ``W_kvb``, taken from the stack at a traced index, the output carried
    to the next layer. The kernel's every layer is the blocked
    reference's."""
    from llmq_tpu.ops import dispatch

    L, T, n, rank = 3, 96, 2, 64
    keys = jax.random.split(jax.random.key(3), 3)
    q_c, q_r, _, k_r = _mla_rows(1, T, n, seed=5)
    c = jax.random.normal(keys[0], (1, T, rank), jnp.float32).astype(jnp.bfloat16)
    w_kvb = (jax.random.normal(keys[1], (L, rank, n * 256), jnp.float32) * rank**-0.5).astype(jnp.bfloat16)
    lens = jnp.asarray([80], jnp.int32)

    def stack(plan):
        def layer(carry, li):
            w = jax.lax.dynamic_index_in_dim(w_kvb, li, keepdims=False)
            kv = (c @ w).reshape(1, T, n, 256)
            o = dispatch.mla_prefill_attention(
                q_c + carry, q_r, kv, k_r, scale=192**-0.5, lengths=lens, plan=plan
            )
            return o, o

        return jax.jit(lambda: jax.lax.scan(layer, jnp.zeros_like(q_c), jnp.arange(L))[1])()

    want, got = stack("xla"), stack("flash")
    assert got.shape == (L, 1, T, n, 128)
    np.testing.assert_allclose(
        np.asarray(got[:, :, :80], np.float32), np.asarray(want[:, :, :80], np.float32),
        atol=4e-2, rtol=4e-2,
    )


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_mla_flash_prefill_copies_no_block_it_skips(bq, bk):
    """A skipped step's index map stays on the last key block its query
    block attends (some key of it at or under a query of the block AND
    inside the prompt), so the pipeline copies nothing for it: the block
    the maps clamp to is that one, for every query block and length."""
    T = 512
    for length in (0, 1, 63, 64, 65, 200, 511, 512):
        for iq in range(T // bq):
            attended = [
                ik for ik in range(T // bk)
                if ik * bk <= iq * bq + bq - 1 and ik * bk < length
            ]
            assert int(pk._mla_last_key_block(iq, length, bq, bk)) == max(attended, default=0)
    grids = _pallas_grids(
        pk.mla_flash_prefill_attention, *_mla_rows(3, T, 2), jnp.asarray([T, 100, 0], jnp.int32),
        scale=0.1, block_q=bq, block_kv=bk, interpret=True,
    )
    assert grids == [(3, 2, T // bq, T // bk)]  # heads are a grid axis


#: ling's cell warms 1 and 4 rows of 256 ... 2,048 at 32 heads, openpangu's 1
#: and 4 rows of 1,024 ... 4,096 at 128 (``benchmark/run_helpers.warm_shapes``).
_LING_WARMED = [(32, t) for t in (256, 512, 1024, 2048)]
_PANGU_WARMED = [(128, t) for t in (1024, 2048, 4096)]


@pytest.mark.parametrize(
    "heads,tokens,dtype,dims,tp,backend,plan",
    [(n, t, jnp.bfloat16, (128, 64, 128), 1, "pallas", "xla") for n, t in _LING_WARMED]
    + [(n, t, jnp.bfloat16, (128, 64, 128), 1, "pallas", "flash") for n, t in _PANGU_WARMED]
    + [
        (32, 4096, jnp.bfloat16, (128, 64, 128), 1, "pallas", "flash"),  # ling above 2**17
        (32, 8192, jnp.bfloat16, (128, 64, 128), 1, "pallas", "flash"),
        (128, 512, jnp.bfloat16, (128, 64, 128), 1, "pallas", "xla"),  # openpangu under it
        (128, 2048, jnp.float32, (128, 64, 128), 1, "pallas", "xla"),  # another precision
        (128, 2048, jnp.bfloat16, (128, 64, 128), 2, "pallas", "xla"),  # a mesh
        (128, 2048, jnp.bfloat16, (128, 64, 128), 1, "xla", "xla"),  # the CPU
        (128, 2048, jnp.bfloat16, (16, 8, 16), 1, "pallas", "xla"),  # the tiny models' heads
        (128, 2048, jnp.bfloat16, (128, 32, 128), 1, "pallas", "xla"),
        (128, 2048, jnp.bfloat16, (128, 64, 64), 1, "pallas", "xla"),
    ],
)
def test_mla_prefill_plan_names_what_runs(heads, tokens, dtype, dims, tp, backend, plan):
    """The plan is a function of the backend, the mesh, the rows' dtype,
    the head sizes and ``num_heads x T`` of the bucket, whatever the rows
    of the batch: every shape ling's cell warms keeps the XLA form, every
    shape openpangu's warms takes the kernel."""
    from llmq_tpu.ops import dispatch
    from llmq_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp])
    assert dispatch.mla_prefill_plan(heads, tokens, dtype, *dims, mesh, backend) == plan
    assert dispatch.MLA_FLASH_HEAD_TOKENS == 2**17
    rows = [jnp.zeros(s, dtype) for s in (
        (1, 64, 2, dims[0]), (1, 64, 2, dims[1]), (1, 64, 2, dims[0] + dims[2]), (1, 64, dims[1]),
    )]
    grids = _pallas_grids(
        dispatch.mla_prefill_attention, *rows,
        scale=0.07, lengths=jnp.asarray([64], jnp.int32), plan=plan,
    )
    assert grids == ([(1, 2, 1, 1)] if plan == "flash" else [])


def test_mla_prefill_plan_follows_the_one_backend_variable(monkeypatch):
    from llmq_tpu.ops import dispatch

    monkeypatch.setenv("LLMQ_ATTN_BACKEND", "xla")
    assert dispatch.mla_prefill_plan(128, 2048, jnp.bfloat16, 128, 64, 128) == "xla"
    monkeypatch.setenv("LLMQ_ATTN_BACKEND", "pallas")
    assert dispatch.mla_prefill_plan(128, 2048, jnp.bfloat16, 128, 64, 128) == "flash"
    assert dispatch.mla_prefill_plan(32, 2048, jnp.bfloat16, 128, 64, 128) == "xla"
