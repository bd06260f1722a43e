"""The grouped expert matmul that reads a group's stack where it lies
(``ops/pallas_grouped_matmul.py``), interpreted on the CPU, against the
XLA form it stands in for (``lax.ragged_dot`` on the layer's own
matrices, as ``models/hybrid._experts_grouped`` has it) and against a
float32 product; the same inside a layer scan, inside ``moe_held``'s
blocks of rows and inside a whole prefill; and the plan that says where
it runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmq_tpu.models import hybrid
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.transformer import build_model, init_params, make_kv_pages
from llmq_tpu.ops import dispatch
from llmq_tpu.ops import pallas_grouped_matmul as pgm

LAYERS, G, K, N = 3, 6, 256, 384

#: name: (rows, group sizes, layers of the stack, the layer, rows of a tile)
CASES = {
    "uneven_groups": (1024, [100, 17, 300, 5, 250, 200], LAYERS, 0, 128),
    "empty_groups": (1024, [0, 300, 0, 0, 400, 0], LAYERS, 0, 128),
    "no_rows_at_all": (512, [0, 0, 0, 0, 0, 0], LAYERS, 1, 128),
    "one_expert_takes_every_row": (1024, [0, 0, 1024, 0, 0, 0], LAYERS, 0, 128),
    "the_last_expert_takes_every_row": (1024, [0, 0, 0, 0, 0, 1024], LAYERS, 0, 256),
    "rows_held_elsewhere_sort_last": (2048, [40, 0, 130, 9, 0, 77], LAYERS, 0, 128),
    "a_layer_above_0": (1024, [100, 17, 300, 5, 250, 200], LAYERS, 2, 128),
    "a_stack_of_one_layer": (1024, [100, 17, 300, 5, 250, 200], 1, 0, 128),
    "rows_not_a_multiple_of_the_tile": (1000, [100, 17, 300, 5, 250, 200], LAYERS, 1, 128),
    "groups_that_end_on_a_tile": (1024, [128, 256, 0, 128, 384, 128], LAYERS, 1, 128),
    "tiles_of_512": (2048, [700, 17, 300, 512, 250, 200], LAYERS, 1, 512),
}


def _operands(rows, layers, seed=0):
    keys = jax.random.split(jax.random.key(seed), 2)
    lhs = jax.random.normal(keys[0], (rows, K), jnp.bfloat16)
    stack = jax.random.normal(keys[1], (layers, G, K, N), jnp.bfloat16) * K**-0.5
    return lhs, stack


@pytest.mark.parametrize("reference", ["ragged_dot", "float32"])
@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_the_kernel_is_the_grouped_product_of_the_layers_own_matrices(case, reference):
    rows, sizes, layers, layer, tile_rows = case
    lhs, stack = _operands(rows, layers, seed=layer)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = pgm.grouped_matmul_stacked(
        lhs, stack, jnp.int32(layer), sizes, tile_rows=tile_rows, interpret=True
    )
    assert got.shape == (rows, N) and got.dtype == lhs.dtype
    meant = int(sizes.sum())  # rows past it hold nothing meant
    if reference == "ragged_dot":
        want = jax.lax.ragged_dot(lhs, stack[layer], sizes)
        tol = dict(rtol=2**-7, atol=2**-7)  # a last bit of bf16: the sum's order
    else:
        group_of = np.repeat(np.arange(G), np.asarray(sizes))
        want = jnp.einsum(
            "mk,mkn->mn", lhs[:meant].astype(jnp.float32),
            stack[layer].astype(jnp.float32)[group_of],
        )
        tol = dict(rtol=2**-7, atol=2**-6)
    np.testing.assert_allclose(
        np.asarray(got[:meant], np.float32), np.asarray(want[:meant], np.float32), **tol
    )


@pytest.mark.parametrize("rows,tile", [(1024, 128), (1000, 128), (2048, 512)])
def test_the_visits_cover_each_groups_rows_once_and_no_others(rows, tile):
    """The grid's table: visits run by expert and an expert's by tile, a
    tile's visits are successive, an empty expert has none, tiles past the
    last row that is meant have none, the rows a visit stores (its tile's
    rows inside its expert's run) partition the rows meant, and the table's
    entries past the visits repeat the last visit."""
    rng = np.random.default_rng(rows)
    for _ in range(20):
        sizes = rng.multinomial(rng.integers(0, rows + 1), rng.dirichlet(np.ones(G) * 0.3))
        offsets, group_of, tile_of, visits = (
            np.asarray(a) for a in pgm.tile_visits(jnp.asarray(sizes, jnp.int32), rows, tile)
        )
        visits = int(visits)
        assert offsets.tolist() == [0, *np.cumsum(sizes)]
        assert group_of.shape == tile_of.shape == (-(-rows // tile) + G - 1,)
        assert (0 <= group_of).all() and (group_of < G).all()
        assert (0 <= tile_of).all() and (tile_of < -(-rows // tile)).all()
        stored = np.zeros(rows, int)
        for v in range(visits):
            g, first = group_of[v], tile_of[v] * tile
            lo, hi = max(first, offsets[g]), min(first + tile, offsets[g + 1])
            assert lo < hi, "a visit that stores nothing"
            stored[lo:hi] += 1
        assert (stored[: sizes.sum()] == 1).all() and not stored[sizes.sum() :].any()
        assert (np.diff(group_of[:visits]) >= 0).all() and (np.diff(tile_of[:visits]) >= 0).all()
        assert visits == sum(
            -(-offsets[g + 1] // tile) - offsets[g] // tile for g in range(G) if sizes[g]
        )
        # a step past the last visit names the last visit's blocks again
        last = max(visits - 1, 0)
        assert (group_of[visits:] == group_of[last]).all()
        assert (tile_of[visits:] == tile_of[last]).all()


def test_the_call_inside_a_layer_scan_reads_each_layers_own_experts():
    """The stack closed over whole, the layer's index the scan's: what
    ``HybridTransformer._run_groups`` does for a prefill."""
    lhs, stack = _operands(512, LAYERS)
    sizes = jnp.asarray([60, 0, 200, 3, 100, 80], jnp.int32)

    @jax.jit
    def scanned(lhs, stack):
        def layer(_, li):
            return None, pgm.grouped_matmul_stacked(lhs, stack, li, sizes, interpret=True)

        return jax.lax.scan(layer, None, jnp.arange(LAYERS, dtype=jnp.int32))[1]

    got = scanned(lhs, stack)
    meant = int(sizes.sum())
    for li in range(LAYERS):
        want = jax.lax.ragged_dot(lhs, stack[li], sizes)
        np.testing.assert_allclose(
            np.asarray(got[li, :meant], np.float32), np.asarray(want[:meant], np.float32),
            rtol=2**-7, atol=2**-7,
        )


# --- inside the model ---------------------------------------------------------

#: ``bailing_hybrid``'s keys at the least widths the kernel takes (hidden
#: and expert width whole lane tiles): KDA, KDA, MLA with a dense lead
#: layer and one whole period, 16 experts of which 8 are held here.
HF = dict(
    model_type="bailing_hybrid", vocab_size=304, hidden_size=128,
    num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=128, rope_theta=10000.0, rms_norm_eps=1e-6,
    layer_group_size=3, first_k_dense_replace=2, short_conv_kernel_size=4,
    kda_lower_bound=-5, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=128, moe_shared_expert_intermediate_size=32,
    num_shared_experts=1, n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, tie_word_embeddings=False,
    kept_layers=[0, 3, 4, 5], experts_held=[4, 8],
)
MC = ModelConfig.from_hf_config(HF)


def _layer(seed=3, held=8):
    """One routed layer's leaves, and a stack of three whose middle it is."""
    keys = jax.random.split(jax.random.key(seed), 8)
    H, I, E = MC.hidden_size, MC.moe_intermediate_size, MC.num_experts
    w = lambda key, *shape: jax.random.normal(key, shape, jnp.bfloat16) * shape[-2] ** -0.5  # noqa: E731
    stack = {
        "expert_gate_proj": w(keys[0], 3, held, H, I),
        "expert_up_proj": w(keys[1], 3, held, H, I),
        "expert_down_proj": w(keys[2], 3, held, I, H),
    }
    lp = {
        "router": w(keys[3], H, E).astype(jnp.float32),
        "router_bias": jnp.zeros((E,), jnp.float32),
        "shared_gate_proj": w(keys[4], H, 32), "shared_up_proj": w(keys[5], H, 32),
        "shared_down_proj": w(keys[6], 32, H),
        **{name: leaf[1] for name, leaf in stack.items()},
    }
    return lp, stack


@pytest.mark.parametrize("blocks", [False, True], ids=["all_rows_at_once", "lax_map_over_blocks"])
def test_moe_held_gives_the_same_sum_from_the_stack(blocks, monkeypatch):
    """``moe_held`` with the group's stack and the layer's place in it
    against the same layer's own leaves through ``ragged_dot``: the same
    counters, the same output to bf16 rounding; also a block of rows at a
    time (``lax.map``; blocks of 512 rows here)."""
    if blocks:
        monkeypatch.setattr(hybrid, "MOE_BLOCK_ROWS", 512)
        monkeypatch.setattr(hybrid, "_MOE_BLOCK_HIDDEN", MC.hidden_size)
    rows = 1536
    assert hybrid.expert_rows(rows, MC.hidden_size) == (512 if blocks else rows)
    lp, stack = _layer()
    x = jax.random.normal(jax.random.key(9), (2, rows // 2, MC.hidden_size), jnp.bfloat16)
    want, want_counts = jax.jit(lambda x: hybrid.moe_held(x, lp, MC))(x)
    others = {k: v for k, v in lp.items() if k not in hybrid.EXPERT_LEAVES}
    run = jax.jit(lambda x, li: hybrid.moe_held(x, others, MC, (stack, li)))
    assert "pallas_call" in str(jax.make_jaxpr(run)(x, jnp.int32(1)))
    got, got_counts = run(x, jnp.int32(1))
    assert got_counts.tolist() == want_counts.tolist() and int(got_counts[0]) > rows
    spread = float(jnp.std(want.astype(jnp.float32)))
    assert float(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()) < 0.05 * spread
    other, _ = run(x, jnp.int32(0))  # another layer's experts: another sum
    assert float(jnp.abs(other.astype(jnp.float32) - want.astype(jnp.float32)).max()) > spread


def test_a_prefill_takes_the_kernel_on_the_whole_stack_and_agrees_with_xla(monkeypatch):
    """A 1 x 384 prefill of a tiny ``bailing_hybrid`` model in bf16 by both
    backends: with ``pallas`` the scanned group's expert leaves stay out of
    the scan's slices (the kernel is in the scan's body, and in the
    group of one layer) and the logits are the XLA form's to bf16
    rounding; a decode step of either backend holds no such kernel."""
    params = init_params(MC, jax.random.key(1), dtype=jnp.bfloat16)
    rng = np.random.default_rng(4)
    tokens = np.zeros((1, 384), np.int32)
    tokens[0, :370] = rng.integers(1, 300, size=370)
    lengths = np.asarray([370], np.int32)
    bt = np.asarray([[1, 2, 3, 0]], np.int32)
    logits = {}
    for backend in ("xla", "pallas"):
        model = build_model(MC, attn_backend=backend)
        k, v = make_kv_pages(MC, 6, 128, jnp.bfloat16)
        prefill = jax.jit(model.prefill)
        text = str(jax.make_jaxpr(model.prefill)(params, tokens, lengths, k, v, bt))
        assert (text.count("grouped_matmul_stacked") > 0) == (backend == "pallas")
        logits[backend] = np.asarray(prefill(params, tokens, lengths, k, v, bt)[0], np.float32)
        decode = str(jax.make_jaxpr(model.decode)(
            params, tokens[:, 0], lengths, k, v, bt, np.asarray([True])
        ))
        assert "grouped_matmul_stacked" not in decode
    spread = logits["xla"].std()
    assert np.abs(logits["pallas"] - logits["xla"]).max() < 0.1 * spread


@pytest.mark.parametrize(
    "rows,x_dtype,w_dtype,H,I,tp,backend,plan",
    [
        (512, jnp.bfloat16, jnp.bfloat16, 2560, 768, 1, "pallas", "stacked"),
        (4096, jnp.bfloat16, jnp.bfloat16, 2048, 1536, 1, "pallas", "stacked"),
        (257, jnp.bfloat16, jnp.bfloat16, 128, 128, 1, "pallas", "stacked"),
        (256, jnp.bfloat16, jnp.bfloat16, 2560, 768, 1, "pallas", "xla"),
        (128, jnp.bfloat16, jnp.bfloat16, 2560, 768, 1, "pallas", "xla"),
        (512, jnp.bfloat16, jnp.bfloat16, 2560, 768, 1, "xla", "xla"),
        (512, jnp.bfloat16, jnp.bfloat16, 2560, 768, 2, "pallas", "xla"),
        (512, jnp.float32, jnp.float32, 2560, 768, 1, "pallas", "xla"),
        (512, jnp.bfloat16, jnp.float32, 2560, 768, 1, "pallas", "xla"),
        (512, jnp.bfloat16, jnp.int8, 2560, 768, 1, "pallas", "xla"),
        (512, jnp.bfloat16, None, 2560, 768, 1, "pallas", "xla"),
        (512, jnp.bfloat16, jnp.bfloat16, 64, 32, 1, "pallas", "xla"),
        (512, jnp.bfloat16, jnp.bfloat16, 2560, 96, 1, "pallas", "xla"),
        (512, jnp.bfloat16, jnp.bfloat16, 192, 768, 1, "pallas", "xla"),
    ],
    ids=[
        "lings_prefill", "lfm2s_prefill", "one_row_above_the_dense_form", "the_dense_forms_most_rows",
        "a_decode_step", "backend_xla", "tp2", "float32", "float32_weights", "int8_weights",
        "a_quantised_leaf", "a_tiny_models_widths", "expert_width_no_lane_tile",
        "hidden_size_no_lane_tile",
    ],
)
def test_grouped_experts_plan_names_what_runs(rows, x_dtype, w_dtype, H, I, tp, backend, plan):
    from llmq_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp])
    dense_rows = hybrid.DENSE_EXPERT_ROWS
    assert dense_rows == 256
    assert dispatch.grouped_experts_plan(
        rows, dense_rows, x_dtype, w_dtype, H, I, mesh=mesh, backend=backend
    ) == plan
    assert dispatch.grouped_experts_plan(
        rows, dense_rows, x_dtype, w_dtype, H, I, mesh=None, backend=backend
    ) == (plan if tp == 1 else "stacked")


def test_grouped_experts_plan_follows_the_one_backend_variable(monkeypatch):
    """``auto`` on a CPU run is the XLA form; ``LLMQ_ATTN_BACKEND`` is the
    only switch."""
    args = (512, 256, jnp.bfloat16, jnp.bfloat16, 256, 128)
    assert dispatch.grouped_experts_plan(*args) == "xla"
    monkeypatch.setenv("LLMQ_ATTN_BACKEND", "pallas")
    assert dispatch.grouped_experts_plan(*args) == "stacked"


@pytest.mark.parametrize(
    "k,n,tile",
    [
        (2560, 768, (128, 768)), (768, 2560, (128, 2560)),  # ling
        (2048, 1536, (128, 768)), (1536, 2048, (128, 1024)),  # lfm2
        (7680, 2048, (128, 256)), (2048, 7680, (128, 768)),  # openpangu
        (128, 128, (128, 128)),
    ],
)
def test_tiles_follow_from_the_shapes(k, n, tile):
    assert pgm.tiles(k, n) == tile
    tm, tn = tile
    assert n % tn == 0 and tn % 128 == 0 and k * tn <= pgm.RHS_BLOCK_VALUES
