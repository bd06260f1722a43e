"""The main path, compiled for a TPU v5e that is described, not attached.

libtpu's compiler is installed wherever the tests run, and it compiles
for a topology given by name (``on-chip-measurement`` guide, section 2).
Interpret-mode tests cannot see what it refuses: a block shape that
breaks the tiling rules, a kernel over its VMEM budget, a step program
that does not fit HBM. Every case here is a program the worker really
runs at qwen2.5-3b widths (192 slots, 16/2 heads, d=128, 128-token
pages), or the per-shard shapes of tp=4. Nothing executes,
so nothing here is a time or a result — a pass means "the chip's
compiler accepts it", no more.

``ops/dispatch`` asks ``on_tpu()`` whether to interpret; the process is
a CPU run, so the cases that go through it steer ``_interpret`` here, in
the test, rather than through an option of the program.
"""

import dataclasses
import os
import re
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from llmq_tpu.core.faults import classify_failure, is_compile_failure
from llmq_tpu.models import cache, hybrid
from llmq_tpu.models.presets import get_preset
from llmq_tpu.models.transformer import Transformer, init_params
from llmq_tpu.ops import dispatch
from llmq_tpu.ops import pallas_attention as pk
from llmq_tpu.ops import pallas_matmul as pm
from llmq_tpu.parallel.mesh import TP_AXIS, make_mesh

Q3B = get_preset("qwen2.5-3b")
Q7B = get_preset("qwen2.5-7b")
L8B = get_preset("llama3.1-8b")
SLOTS, PAGE, POOL_PAGES, PAGES_PER_SEQ = 192, 128, 1024, 65
H, NKV, D = Q3B.num_heads, Q3B.num_kv_heads, Q3B.head_dim_
SCALE = D**-0.5


@pytest.fixture(scope="module")
def topo():
    """The described chip; the whole file skips where it cannot be
    described. The persistent compile cache is off around the cases: an
    entry written by a compile-only client cannot be read back without a
    chip, and the next run would warn instead of staying silent."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


class _Shapes:
    """ShapeDtypeStructs placed on the described devices."""

    def __init__(self, topo, mesh=None):
        self.mesh = mesh
        self.one_chip = SingleDeviceSharding(topo.devices[0])

    def __call__(self, shape, dtype, spec=None):
        sharding = (
            self.one_chip
            if self.mesh is None
            else NamedSharding(self.mesh, spec if spec is not None else P())
        )
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def pool(self, layers=Q3B.num_layers, dtype=jnp.bfloat16, n_kv=NKV):
        return self((layers, POOL_PAGES, PAGE, n_kv, D), dtype)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in it"
    return compiled


def _decode(kernel, pool_dtype=jnp.bfloat16):
    def case(topo, monkeypatch):
        s = _Shapes(topo)
        pool = s.pool(dtype=pool_dtype)
        _compile(
            partial(kernel, scale=SCALE),
            s((SLOTS, H, D), jnp.bfloat16), pool, pool,
            s((SLOTS, PAGES_PER_SEQ), jnp.int32), s((SLOTS,), jnp.int32),
            s((1,), jnp.int32), s((1,), jnp.int32),
        )

    return case


def _decode_default_128_slots_64_places(topo, monkeypatch):
    """What the benchmark's 3B cells run: the dispatch's default decode
    kernel at 128 slots x 64 page places over the whole 36-layer pool,
    the layer traced. It is the live-pages schedule, under the name the
    benchmark's trace readers look for, and the pool reaches it as it
    lies (the launcher's 2-D view of a page is a bitcast, not a copy)."""
    monkeypatch.setattr(dispatch, "_interpret", lambda: False)
    s = _Shapes(topo)
    pool = s((Q3B.num_layers, 1915, PAGE, NKV, D), jnp.bfloat16)
    kv = Format(Layout(tuple(range(5))), pool.sharding)

    def step(q, kp, vp, bt, cl, layer):
        return dispatch.decode_attention(
            q, kp, vp, bt, cl, scale=SCALE, backend="pallas", layer=layer
        )

    compiled = (
        jax.jit(step, in_shardings=(None, kv, kv, None, None, None))
        .lower(
            s((128, H, D), jnp.bfloat16), pool, pool,
            s((128, 64), jnp.int32), s((128,), jnp.int32), s((), jnp.int32),
        )
        .compile()
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%paged_decode_attention_live" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def _decode_fp8_pool_2_kv_heads_takes_v1(topo, monkeypatch):
    """qwen2.5-3b's pool in fp8: two 1-byte heads a token do not fill a
    word, the chip pads the pages, and the dispatch compiles v1 for it."""
    monkeypatch.setattr(dispatch, "_interpret", lambda: False)
    s = _Shapes(topo)
    pool = s.pool(dtype=jnp.float8_e5m2)

    def step(q, kp, vp, bt, cl, layer):
        return dispatch.decode_attention(
            q, kp, vp, bt, cl, scale=SCALE, backend="pallas", layer=layer
        )

    text = _compile(
        step, s((SLOTS, H, D), jnp.bfloat16), pool, pool,
        s((SLOTS, PAGES_PER_SEQ), jnp.int32), s((SLOTS,), jnp.int32),
        s((), jnp.int32),
    ).as_text()
    assert "%paged_decode_attention_pallas" in text
    assert "paged_decode_attention_live" not in text


def _latent_decode_live(n_heads, layers, pool_pages, places):
    """The latent decode kernel alone at a layer-pattern cell's shapes: 128
    slots, a stacked pool of 128-token pages of 640 bf16 values (576 kept
    in whole lane tiles), rank 512, the layer traced. The plan names it for
    these shapes, and the pool reaches it as it lies."""

    def case(topo, monkeypatch):
        s = _Shapes(topo)
        pool = s((layers, pool_pages, PAGE, 640), jnp.bfloat16)
        assert dispatch.latent_decode_kernel_plan(
            512, PAGE, 640, pool.dtype, None, "pallas"
        ) == "latent_live"
        compiled = _compile(
            partial(pk.latent_paged_decode_attention_live, scale=192**-0.5, rank=512),
            s((128, n_heads, 576), jnp.bfloat16), pool,
            s((128, places), jnp.int32), s((128,), jnp.int32), s((), jnp.int32),
        )
        assert " copy(" not in "".join(
            l for l in compiled.as_text().splitlines() if f"bf16[{layers},{pool_pages}," in l
        )
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20

    return case


def _kda_step_inplace(topo, monkeypatch):
    """The one-pass KDA state update alone at ling's shape: a pool of 6
    layers x 129 rows x 32 heads of 128 x 128 float32 (1.62 GB), a run of
    128 rows from row 1, the layer traced. The pool is updated where it
    lies (aliased, no copy of it, next to no temporaries), and the
    kernel's blocks fit the scoped VMEM at the heads a block in the tree."""
    from llmq_tpu.ops.pallas_delta_rule import kda_step_inplace

    s = _Shapes(topo)
    pool = s((6, 129, 32, 128, 128), jnp.float32)
    vec = s((128, 32, 128), jnp.float32)
    assert dispatch.kda_decode_plan(1, pool.dtype, 128, 128, None, "pallas") == "inplace"
    compiled = jax.jit(partial(kda_step_inplace, first_row=1), donate_argnums=0).lower(
        pool, s((), jnp.int32), vec, vec, vec, vec,
        s((128, 32), jnp.float32), s((128,), jnp.bool_),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * 129 * 32 * 128 * 128 * 4
    assert mem.temp_size_in_bytes < 2**20
    text = compiled.as_text()
    assert "%kda_step_inplace" in text and " copy(" not in "".join(
        l for l in text.splitlines() if "f32[6,129,32,128,128]" in l
    )


def _mla_flash_prefill(n_heads, T):
    """The flash prefill of expanded latent attention alone at openpangu's
    heads (128 + 64 and 128, bf16) over one row of a bucket: the plan names
    it for the shape, it asks for no more fast memory than a kernel has by
    default (PR 51: one granted 18 MB hung two prefill programs), and no
    float32 score matrix leaves it (the XLA form's is ``n_heads x 512 x T``
    a block, 1 GB here)."""

    def case(topo, monkeypatch):
        s = _Shapes(topo)
        assert dispatch.mla_prefill_plan(
            n_heads, T, jnp.bfloat16, 128, 64, 128, None, "pallas"
        ) == "flash"
        compiled = _compile(
            partial(pk.mla_flash_prefill_attention, scale=192**-0.5),
            s((1, T, n_heads, 128), jnp.bfloat16), s((1, T, n_heads, 64), jnp.bfloat16),
            s((1, T, n_heads, 256), jnp.bfloat16), s((1, T, 64), jnp.bfloat16),
            s((1,), jnp.int32),
        )
        text = compiled.as_text()
        calls = [l for l in text.splitlines() if "custom-call(" in l and "mla_flash_prefill_attention" in l]
        assert len(calls) == 1 and "vmem_limit_bytes" not in calls[0]
        # the queries heads first and the output: nothing the size of a score block
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * T * n_heads * 192 * 2

    return case


def _flash_prefill(T):
    def case(topo, monkeypatch):
        s = _Shapes(topo)
        kv = s((4, T, NKV, D), jnp.bfloat16)
        _compile(
            partial(pk.flash_prefill_attention_pallas, scale=SCALE),
            s((4, T, H, D), jnp.bfloat16), kv, kv,
            s((4,), jnp.int32), s((1,), jnp.int32),
        )

    return case


def _paged_prefill(topo, monkeypatch):
    s = _Shapes(topo)
    _compile(
        partial(pk.paged_prefill_attention_pallas, scale=SCALE),
        s((4, 512, H, D), jnp.bfloat16), s.pool(), s.pool(),
        s((4, PAGES_PER_SEQ), jnp.int32), s((4,), jnp.int32),
        s((4,), jnp.int32), s((1,), jnp.int32), s((1,), jnp.int32),
    )


def _int8_matmul(topo, monkeypatch):
    s = _Shapes(topo)
    K, N = Q3B.hidden_size, Q3B.intermediate_size
    _compile(
        pm.int8_matmul_pallas,
        s((SLOTS, K), jnp.bfloat16), s((K, N), jnp.int8), s((N,), jnp.bfloat16),
    )


def _int4_matmul(K, N):
    """Both MLP shapes: up (K=2048, whole 1024-row K tiles do not exist
    for the group axis) and down (K=11008 = 86 groups, no multiple of 8
    divides it). The group tile was never legal before PR 24."""

    def case(topo, monkeypatch):
        s = _Shapes(topo)
        G = K // 128
        _compile(
            pm.int4_matmul_pallas,
            s((SLOTS, K), jnp.bfloat16), s((K // 2, N), jnp.uint8),
            s((G, N), jnp.bfloat16), s((G, N), jnp.bfloat16),
        )

    return case


def _decode_tp4_shard_map(topo, monkeypatch):
    """tp=4 through the dispatch the engine uses: the default (live)
    kernel under ``shard_map`` on a 4-device mesh at llama3.1-8b widths (8 query / 2 kv
    heads a shard), the pool sharded on its kv-head axis."""
    monkeypatch.setattr(dispatch, "_interpret", lambda: False)
    cfg = L8B
    mesh = make_mesh(tensor_parallel=4, devices=topo.devices)
    s = _Shapes(topo, mesh)
    heads = P(None, TP_AXIS, None)
    pool = s(
        (cfg.num_layers, POOL_PAGES, PAGE, cfg.num_kv_heads, D),
        jnp.bfloat16,
        P(None, None, None, TP_AXIS, None),
    )

    def step(q, kp, vp, bt, cl, layer):
        return dispatch.decode_attention(
            q, kp, vp, bt, cl, scale=SCALE, mesh=mesh, backend="pallas",
            layer=layer,
        )

    # The engine pins the pool row-major at every jit boundary
    # (EngineCore._kv_formats); so does the test, or the compiler picks a
    # parameter layout of its own and copies both pools into the kernel's.
    kv = Format(Layout(tuple(range(5))), pool.sharding)
    compiled = (
        jax.jit(step, in_shardings=(None, kv, kv, None, None, None))
        .lower(
            s((SLOTS, cfg.num_heads, D), jnp.bfloat16, heads), pool, pool,
            s((SLOTS, PAGES_PER_SEQ), jnp.int32), s((SLOTS,), jnp.int32),
            s((), jnp.int32),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    # Per device: a quarter of both pools as arguments, no copy of either.
    pool_bytes = 2 * cfg.num_layers * POOL_PAGES * PAGE * cfg.num_kv_heads * D * 2
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 0.3 * pool_bytes
    assert mem.temp_size_in_bytes < 0.01 * pool_bytes


def _one_kv_head_a_shard_takes_the_xla_path(topo, monkeypatch):
    """qwen2.5-7b at tp=4 (28/4 heads) leaves a shard one kv head. The
    kernels' pool layout cannot hold that without padding and a per-layer
    pool copy (the page-bytes case below), so the dispatch gives way —
    and says so, once."""
    mesh = make_mesh(tensor_parallel=4, devices=topo.devices)
    for plan in (dispatch.verify_kernel_plan, dispatch.mixed_kernel_plan):
        assert plan(Q7B.num_heads, Q7B.num_kv_heads, mesh, "pallas") == "xla"
    assert dispatch.decode_kernel_plan(
        Q7B.num_heads, Q7B.num_kv_heads, jnp.bfloat16, mesh, "pallas"
    ) == "xla"
    assert dispatch.decode_kernel_plan(
        L8B.num_heads, L8B.num_kv_heads, jnp.bfloat16, mesh, "pallas"
    ) == "live"


def _pool_page_bytes_come_from_the_compiler(topo, monkeypatch):
    """The pool is sized in pages of what a page really takes on a
    device. In the kernels' row-major layout one bf16 kv head a shard
    (qwen2.5-7b at tp=4) is padded to a packed pair — twice the bytes the
    shape says — which is why that model's pool is left in the compiler's
    own layout (unpadded) and its attention to XLA. qwen2.5-3b on one chip
    (two heads) is unpadded; its fp8 pool is padded back to the bf16
    size."""
    from llmq_tpu.models.cache import cache_layout
    from llmq_tpu.parallel.sharding import kv_page_pspec

    def page_bytes(cfg, tp, dtype, pinned=True):
        mesh = make_mesh(tensor_parallel=tp, devices=topo.devices[:tp])
        sharding = NamedSharding(mesh, kv_page_pspec(cfg, tp))
        fmt = Format(Layout(tuple(range(5))), sharding) if pinned else sharding
        layout = cache_layout(
            cfg, page_size=PAGE, max_model_len=2048, max_num_seqs=128, kv_dtype=dtype
        )
        return layout.page_bytes(fmt)

    def by_shape(cfg, tp, itemsize):
        return (
            2 * cfg.num_layers * PAGE * cfg.num_kv_heads * D * itemsize // tp
        )

    assert page_bytes(Q3B, 1, jnp.bfloat16) == by_shape(Q3B, 1, 2)
    assert page_bytes(Q7B, 4, jnp.bfloat16) == 2 * by_shape(Q7B, 4, 2)
    assert page_bytes(Q7B, 4, jnp.bfloat16, pinned=False) == by_shape(Q7B, 4, 2)
    assert page_bytes(Q3B, 1, jnp.float8_e5m2) == 2 * by_shape(Q3B, 1, 1)


def _prefill_step(topo, monkeypatch, *, layers=2, pool_pages=POOL_PAGES):
    monkeypatch.setattr(dispatch, "_interpret", lambda: False)
    cfg = dataclasses.replace(Q3B, num_layers=layers)
    s = _Shapes(topo)
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(
            partial(init_params, cfg, dtype=jnp.bfloat16), jax.random.key(0)
        ),
    )
    pool = s((layers, pool_pages, PAGE, NKV, D), jnp.bfloat16)
    step = jax.jit(
        Transformer(cfg, attn_backend="pallas").prefill, donate_argnums=(3, 4)
    )
    return step.lower(
        params, s((4, 256), jnp.int32), s((4,), jnp.int32), pool, pool,
        s((4, PAGES_PER_SEQ), jnp.int32),
    ).compile()


def _aligned_prefill_writes_pages_in_place(topo, monkeypatch):
    """The regression guard for the pool copy: a page-aligned bucket
    (256 = 2 pages) must not reserve a temporary the size of the KV
    pools. As one block scatter the page write made the compiler
    re-tile both pools around the layer scan — temp == both pools, and
    at the worker's 90%-of-HBM pool the step did not fit the chip."""
    layers = 2
    compiled = _prefill_step(topo, monkeypatch, layers=layers)
    pool_bytes = 2 * layers * POOL_PAGES * PAGE * NKV * D * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


def _refusal_is_a_compile_failure_not_a_device_fault(topo, monkeypatch):
    """What the compiler says when a step does not fit HBM (a pool of
    2 x 16 GiB on a 16 GiB chip) must never be classified as a device
    fault: the fault plane would rebuild the engine and retry a program
    that can never compile."""
    with pytest.raises(Exception) as refused:  # noqa: PT011 — jaxlib's type
        _prefill_step(topo, monkeypatch, layers=1, pool_pages=2**18)
    assert "RESOURCE_EXHAUSTED" in str(refused.value)
    assert is_compile_failure(refused.value)
    assert classify_failure(refused.value) is None


def _hybrid_step(
    which, preset="ling-3.0-flash-ep4", pages=2700, places=64, page_bytes=None, slots=128
):
    """The decode step and a prefill bucket of the Ling-3.0-flash cut
    at its published widths (``preset://ling-3.0-flash-ep4``: 10.46 GB of
    bf16 weights), over the pools the benchmark's cell runs with: 128
    slots and their 129 state rows, 2,700 latent pages of 128 tokens, 64
    page places a row (``max_model_len`` 8,192). What has to hold: the
    chip's compiler accepts the scans over the layer groups, the grouped
    expert matmuls and the state update, the pools are updated in place,
    and weights, pools and temporaries fit the chip's 16.9 GB with room
    for the benchmark's correctness scratch (0.6 GB); and a decode step
    copies the latent pool nowhere and holds little besides the pools,
    whatever ``max_model_len`` is. (Ling's largest buckets, 4 x 4,096 and 4
    x 8,192, which take rows and blocks one at a time in
    ``models/hybrid.py``, compile too, ``_hybrid_step((4, 8192))``: 25 s
    of every core each, and left out of ``CASES`` for the suite's sake;
    the harness warms 4 x 2,048 at most there.)

    The same for the openPangu-Ultra-MoE cut
    (``preset://openpangu-ultra-moe-718b-ep16``: 9.84 GB of weights, latent
    attention at 128 heads on all five layers, NO state layer: the state
    pool's leaves are empty), over its cell's pools: 3,200 latent pages, 32
    page places a row (``max_model_len`` 4,096). Its largest bucket, 4 x
    4,096, which expands a row at a time, compiles in 12 s to 3.7 GB of
    temporaries (3.2 with ``lax.ragged_dot`` and its copies).

    And for the LFM2-24B-A2B stage (``preset://lfm2-24b-a2b-pp5``: 10.36 GB
    of weights, gated short-convolution layers with tails alone in the
    state pool, two GQA layers of 32 / 8 heads of 64), over its cell's
    pools: 6,700 pages, 64 page places a row. Heads of 64: the paged
    pool's row is a token's V then K, 1,024 bf16 values, so a page is the
    512 KB the widths say (``page_bytes``: nothing padded), the decode step
    reads it with the latent pool's kernel and copies it nowhere, and the
    1 x 4,096 and 4 x 2,048 prefills (the Pallas flash kernel at heads of
    64 in them) hold no temporary the size of the pool.

    And for the EvaByte stage (``preset://evabyte-6.5b-pp4``: 3.24 GB of
    weights, eight EVA layers of 32 / 32 heads of 128), over its cell's
    pools: 24 slots, 512 pages, 96 page places a row (``max_model_len``
    12,288). The pool's row is a token's V then K, 8,192 bf16 values: a
    page is 2 MB a layer, 16.8 MB over the eight, where lfm2's is 512 KB
    and openpangu's 160 KB. The decode step reads it with the latent
    pool's kernel at ``rank`` 4,096 (one page a chunk: the kernel's two
    page buffers are 4 MB of VMEM) and, with the window's compaction
    inside it (a loop of as many turns as sequences close a window),
    copies the pool nowhere; the 1 x 8,192 prefill, the bucket of the
    cell's every prompt, holds 0.73 GB of temporaries.

    And for the Laguna-S-2.1 cut (``preset://laguna-s-2.1-ep4``: 6.00 GB of
    weights, two full-attention layers of 48 heads over the paged pool
    beside three sliding-window layers of 72 heads over a ring, 8 kv
    heads of 128 on both), over its cell's pools: 6,200 pages of 128 tokens
    x 2 layers x 2,048 values (1 MB a page), 129 rings of 3 x 512 rows
    (0.81 GB), 48 page places a row (``max_model_len`` 6,144). The decode
    step reads pool AND ring with the latent pool's kernel (five Mosaic
    calls, one a layer) and copies neither; the largest prefill the harness
    warms, 4 x 6,144, holds 2.72 GB of temporaries (the lead layer's dense
    MLP of 12,288 is most of it: 4 x 8,192 would hold 3.54 GB, 16.85 GB in
    all, which is why the configuration stops at 6,144).

    A prefill of the three patterns with routed experts (more rows than
    the dense form takes, bf16, whole lane tiles) takes the grouped
    matmul that reads a group's stack where it lies
    (``dispatch.grouped_experts_plan``: ``stacked``): the kernel is in the
    program and NO instruction but a parameter makes a buffer the size
    of a layer's expert matrix: the three copies a scanned layer made in
    front of ``lax.ragged_dot`` (3 x 503 MB a layer for ling) are gone,
    checked here where no chip is needed. The largest bucket the harness
    warms compiles beside the pool too (ling 4 x 2,048, openpangu 4 x
    4,096, lfm2 4 x 8,192: a block of rows at a time around the
    kernel)."""

    def case(topo, monkeypatch):
        from llmq_tpu.models.transformer import build_model, make_kv_pages

        monkeypatch.setattr(dispatch, "_interpret", lambda: False)
        cfg = get_preset(preset)
        model = build_model(cfg, attn_backend="pallas")
        s = _Shapes(topo)
        shaped = partial(jax.tree.map, lambda a: s(a.shape, a.dtype))
        params = shaped(jax.eval_shape(
            partial(init_params, cfg, dtype=jnp.bfloat16), jax.random.key(0)
        ))
        latent, state = shaped(jax.eval_shape(
            lambda: make_kv_pages(cfg, pages, PAGE, jnp.bfloat16, state_rows=slots + 1)
        ))
        if which == "decode":
            rows = slots
            compiled = jax.jit(
                partial(model.decode, counters=True, state_rows=1),
                donate_argnums=(3, 4),
            ).lower(
                params, s((rows,), jnp.int32), s((rows,), jnp.int32), latent, state,
                s((rows, places), jnp.int32), s((rows,), jnp.bool_),
            ).compile()
        else:
            rows, bucket = which
            compiled = jax.jit(model.prefill, donate_argnums=(3, 4)).lower(
                params, s((rows, bucket), jnp.int32), s((rows,), jnp.int32), latent,
                state, s((rows, places), jnp.int32), s((rows,), jnp.int32),
            ).compile()
        mem = compiled.memory_analysis()
        pools = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves((latent, state)))
        assert mem.alias_size_in_bytes >= pools  # both pools in place
        if page_bytes is not None:
            assert latent.size * latent.dtype.itemsize == pages * page_bytes
            # (what the compiler reserves grows with what the pools leave
            # free: 0.56 GB beside 7,500 pages, 1.29 GB beside 6,700)
            assert mem.temp_size_in_bytes < pools // 2
            text = compiled.as_text()
            # flash prefill, or the decode kernel (an EVA prefill is XLA's:
            # no kernel for window + summary keys yet)
            assert "tpu_custom_call" in text or (which != "decode" and "eva" in preset)
            pool = f"bf16[{latent.shape[0]},{pages},"
            assert not [l for l in text.splitlines() if " copy(" in l and pool in l]
        if "ring" in state:  # the window layers' rings, in place like the pool
            ring = "bf16[" + ",".join(map(str, state["ring"].shape[:2])) + ","
            assert not [l for l in compiled.as_text().splitlines() if " copy(" in l and ring in l]
            assert state["ring"].size * 2 == cache.state_bytes(cfg, slots + 1, jnp.bfloat16)["ring"]
        if cfg.num_experts:
            held, H, I = cfg.experts_held_[1], cfg.hidden_size, cfg.moe_intermediate_size
            plan = dispatch.grouped_experts_plan(
                hybrid.expert_rows(rows if which == "decode" else rows * bucket, H),
                hybrid.DENSE_EXPERT_ROWS, jnp.bfloat16, jnp.bfloat16, H, I, None, "pallas",
            )
            assert plan == ("xla" if which == "decode" else "stacked")
            text = compiled.as_text()
            assert ("%grouped_matmul_stacked" in text) == (plan == "stacked")
            # a layer's expert matrix, or a stack of one layer: made by
            # nothing (a loop's own arguments are views, not buffers)
            matrix = re.compile(rf"= bf16\[(1,)?{held},({H},{I}|{I},{H})\]")
            made = [
                l.strip()[:200] for l in text.splitlines()
                if matrix.search(l) and " parameter(" not in l and " get-tuple-element(" not in l
            ]
            assert bool(made) == (plan == "xla"), made[:3]
        if which != "decode" and hybrid.count_layers(cfg, "mla"):
            # Expanded latent attention from ``num_heads x T`` of 2**17 is
            # the flash kernel (``dispatch.mla_prefill_plan``), once a
            # group of latent layers; then no ``[B, heads, block, T]``
            # float32 score matrix is made, and the kernel's call asks for
            # no more fast memory than a kernel has (PR 51: one granted 18
            # MB hung two of ling's programs on the chip and compiled
            # here). Under it XLA's blocked form stays.
            plan = model.mla_prefill_plan(bucket, jnp.bfloat16)
            assert plan == ("flash" if cfg.num_heads * bucket >= 2**17 else "xla")
            text = compiled.as_text()
            calls = [l for l in text.splitlines() if "mla_flash_prefill_attention" in l and "custom-call(" in l]
            groups = sum(1 for g in hybrid.layer_groups(cfg) if g.attn == "mla")
            assert len(calls) == (groups if plan == "flash" else 0), calls[:2]
            assert not [l for l in calls if "vmem_limit_bytes" in l]
            scores = re.compile(rf"f32\[\d+,{cfg.num_heads},\d+,{bucket}\]")
            assert plan == "xla" or not scores.search(text)
        if which == "decode":
            # The latent kernel is in the step (a Mosaic call named for
            # it), reads the pool where it lies, and with the XLA loop's
            # gathered rows and float32 scores gone the step's temporaries
            # are no larger than they were (85.6 and 94.9 MB with the loop).
            text = compiled.as_text()
            assert "tpu_custom_call" in text
            assert "%latent_paged_decode_attention_live" in text
            kda = "%kda_step_inplace" in text
            assert mem.temp_size_in_bytes <= (
                (86_831_104 if kda else 85_604_352) if places == 64 else 94_859_264
            )
            # A pattern with KDA layers of 128 x 128 float32 states (ling
            # alone) updates them by the one-pass kernel, on the pool where
            # it lies: no run of states gathered beside it (the XLA form
            # had 14 values of that shape; the step's other temporaries
            # are 1.5 MB more than with it, 86.8 MB: the kernel's four
            # vector operands and its output are whole at once where
            # fusions made and used them)
            assert kda == (preset == "ling-3.0-flash-ep4")
            S = state["S"]
            assert kda == (S.shape[0] > 0 and "inplace" == dispatch.kda_decode_plan(
                1, S.dtype, *S.shape[-2:], None, "pallas"
            ))
            if kda:
                assert f"f32[{rows},{','.join(map(str, S.shape[2:]))}]" not in text
            pool = f"bf16[{latent.shape[0]},{pages},"
            copies = [l for l in text.splitlines() if " copy(" in l and pool in l]
            assert not copies, copies[:2]
        # (in the two largest buckets the compiler's own accounting decides:
        # it refuses what does not fit the chip beside these pools)
        if which not in ((4, 8192), (4, 4096), (4, 6144)):
            assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9

    return case


CASES = {
    "hybrid_decode_128_slots": _hybrid_step("decode"),
    "hybrid_prefill_1x512": _hybrid_step((1, 512)),
    "hybrid_prefill_4x2048": _hybrid_step((4, 2048)),
    "hybrid_prefill_1x8192_above_the_flash_threshold": _hybrid_step((1, 8192)),
    "latent_only_decode_128_slots_128_heads": _hybrid_step(
        "decode", "openpangu-ultra-moe-718b-ep16", pages=3200, places=32
    ),
    "latent_only_prefill_1x2048": _hybrid_step(
        (1, 2048), "openpangu-ultra-moe-718b-ep16", pages=3200, places=32
    ),
    "latent_only_prefill_4x4096": _hybrid_step(
        (4, 4096), "openpangu-ultra-moe-718b-ep16", pages=3200, places=32
    ),
    "conv_gqa_decode_128_slots_heads_of_64": _hybrid_step(
        "decode", "lfm2-24b-a2b-pp5", pages=6700, page_bytes=2 * 2 * 8 * 64 * 128 * 2
    ),
    "conv_gqa_prefill_1x4096": _hybrid_step(
        (1, 4096), "lfm2-24b-a2b-pp5", pages=6700, page_bytes=2 * 2 * 8 * 64 * 128 * 2
    ),
    "conv_gqa_prefill_4x2048": _hybrid_step(
        (4, 2048), "lfm2-24b-a2b-pp5", pages=6700, page_bytes=2 * 2 * 8 * 64 * 128 * 2
    ),
    "conv_gqa_prefill_4x8192": _hybrid_step(
        (4, 8192), "lfm2-24b-a2b-pp5", pages=6700, page_bytes=2 * 2 * 8 * 64 * 128 * 2
    ),
    "eva_decode_24_slots_32_heads_of_128": _hybrid_step(
        "decode", "evabyte-6.5b-pp4", pages=512, places=96, slots=24,
        page_bytes=8 * 2 * 32 * 128 * 128 * 2,
    ),
    "eva_prefill_1x8192": _hybrid_step(
        (1, 8192), "evabyte-6.5b-pp4", pages=512, places=96, slots=24,
        page_bytes=8 * 2 * 32 * 128 * 128 * 2,
    ),
    "swa_gqa_decode_128_slots_72_and_48_heads": _hybrid_step(
        "decode", "laguna-s-2.1-ep4", pages=6200, places=48,
        page_bytes=2 * 2 * 8 * 128 * 128 * 2,
    ),
    "swa_gqa_prefill_4x6144": _hybrid_step(
        (4, 6144), "laguna-s-2.1-ep4", pages=6200, places=48,
        page_bytes=2 * 2 * 8 * 128 * 128 * 2,
    ),
    "latent_decode_live_128_heads": _latent_decode_live(128, 5, 3200, 32),
    "latent_decode_live_32_heads": _latent_decode_live(32, 1, 2305, 64),
    "kda_step_inplace_128_rows_32_heads": _kda_step_inplace,
    "mla_flash_prefill_128_heads_T4096": _mla_flash_prefill(128, 4096),
    "decode_live": _decode(pk.paged_decode_attention_live),
    "decode_fp8_pool_2_kv_heads_takes_v1": (
        _decode_fp8_pool_2_kv_heads_takes_v1
    ),
    "decode_default_128_slots_64_places": _decode_default_128_slots_64_places,
    "decode_v1": _decode(pk.paged_decode_attention_pallas),
    "decode_v1_fp8_pool": _decode(
        pk.paged_decode_attention_pallas, jnp.float8_e5m2
    ),
    "flash_prefill_T256": _flash_prefill(256),
    "flash_prefill_T2048": _flash_prefill(2048),
    "paged_prefill_C512": _paged_prefill,
    "int8_matmul_192x2048x11008": _int8_matmul,
    "int4_matmul_up_192x2048x11008": _int4_matmul(2048, 11008),
    "int4_matmul_down_192x11008x2048": _int4_matmul(11008, 2048),
    "decode_v1_tp4_shard_map_llama8b": _decode_tp4_shard_map,
    "qwen7b_tp4_one_kv_head_a_shard_is_xla": (
        _one_kv_head_a_shard_takes_the_xla_path
    ),
    "kv_page_bytes_padded_as_compiled": _pool_page_bytes_come_from_the_compiler,
    "aligned_prefill_step_temp_far_below_pool": (
        _aligned_prefill_writes_pages_in_place
    ),
    "hbm_refusal_is_compile_failure": (
        _refusal_is_a_compile_failure_not_a_device_fault
    ),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_compiles_for_v5e(case, topo, monkeypatch):
    case(topo, monkeypatch)
