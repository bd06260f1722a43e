"""``models/cache.CacheLayout``: what a sequence keeps on the device, asked
of one object. Every expected value below is a LITERAL read at the parent
of PR 58 from the engine's own helpers (``_live_pages``, ``_contexts``,
``_eva_rows``, ``_window_rows``, ``_latent_pages_visited``, ``_table_pages``,
``_layer_pattern_refusal``) and from ``make_kv_pages``, at 4 slots, 96
positions, pages of 8, 60 pages, a float32 cache: none is recomputed by
the code under test."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.scheduler import Scheduler, SchedulerConfig, Sequence
from llmq_tpu.models import cache
from llmq_tpu.models.config import ModelConfig
from llmq_tpu.models.presets import get_preset
from llmq_tpu.models.transformer import make_kv_pages
from llmq_tpu.ops.attention import eva_table_pages

# ling's kinds (KDA state + latent pool), as tests/test_hybrid_engine.py builds them
LING = ModelConfig(
    vocab_size=304, hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=4,
    head_dim=16, intermediate_size=128, eos_token_ids=(0,),
    model_type="bailing_hybrid",
    layer_pattern=(("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe")),
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, norm_topk_prob=True, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, experts_held=(4, 8),
)
TINY = get_preset("tiny")
SIZES = dict(page_size=8, max_model_len=96, max_num_seqs=4, kv_dtype=jnp.float32)
LENGTHS = (1, 8, 9, 40, 70, 96)
SPANS = ((0, 1), (0, 8), (0, 9), (40, 41), (0, 96), (69, 75), (95, 97))
POSITIONS = dict(table_pages=[1, 1, 2, 6, 12, 10, 13], contexts=[1, 8, 9, 40, 70, 96])
NO_STATE = dict(S=((0, 5, 4, 16, 16), "float32"), conv=((0, 5, 3, 192), "float32"))
NO_STATE_A_PAGE = dict(S=((0, 7, 4, 16, 16), "float32"), conv=((0, 7, 3, 192), "bfloat16"))
REFUSED = "spec_tokens=2 is not supported for a model with a layer pattern "
HELD = ", and its experts are held whole on one device"

CASES = {
    "tiny": dict(
        config=TINY,
        pools=(((2, 60, 8, 2, 16), "float32"), ((2, 60, 8, 2, 16), "float32")),
        bare=(((2, 7, 8, 2, 16), "bfloat16"), ((2, 7, 8, 2, 16), "bfloat16")),
        **POSITIONS, span=dict(live_pages=30), max_useful=53,
        state_rows=None, state_kind=None, stats={},
        decode_plan=("decode_kernel_plan", (4, 2, jnp.float32)), kda=None,
        sig=dict(num_layers=2, num_kv_heads=2, head_dim=16, kv_dtype="float32"),
    ),
    # ctx 40, window 10: positions 30..39, pages 3 and 4 of 0..4
    "tiny-slides": dict(
        config=dataclasses.replace(TINY, sliding_window=10),
        **POSITIONS, span=dict(live_pages=10), max_useful=53,
    ),
    # every second layer sees the whole context: count those
    "tiny-slides-every-second": dict(
        config=dataclasses.replace(TINY, sliding_window=10, sliding_window_pattern=2),
        **POSITIONS, span=dict(live_pages=30), max_useful=53,
    ),
    "ling": dict(
        config=LING,
        pools=(
            ((1, 60, 8, 128), "float32"),
            dict(S=((3, 5, 4, 16, 16), "float32"), conv=((3, 5, 3, 192), "float32")),
        ),
        bare=(
            ((1, 7, 8, 128), "bfloat16"),
            dict(S=((3, 7, 4, 16, 16), "float32"), conv=((3, 7, 3, 192), "bfloat16")),
        ),
        **POSITIONS, max_useful=53,
        span=dict(live_pages=30, state_rows=6, latent_pages_visited=48),
        refusal=REFUSED + "(per-sequence KDA state beside a latent cache): the state "
        "cannot be shared by a prefix, cut at a chunk, rewound by a length or moved "
        "between pools" + HELD,
        state_rows=5, state_kind="kda", stats={},
        decode_plan=("latent_decode_kernel_plan", (32, 8, 128, jnp.float32)),
        kda=(1, jnp.float32, 16, 16),
        sig=dict(num_layers=4, num_kv_heads=4, head_dim=16, kv_dtype="float32"),
    ),
    "openpangu-ultra-moe-tiny": dict(
        pools=(((3, 60, 8, 128), "float32"), NO_STATE),
        bare=(((3, 7, 8, 128), "bfloat16"), NO_STATE_A_PAGE),
        **POSITIONS, max_useful=53,
        span=dict(live_pages=30, latent_pages_visited=48),
        refusal=REFUSED + "(a latent cache alone, no per-sequence state): chunked "
        "prefill, verify, the mixed step and moving a latent pool are not built for a "
        "layer pattern (HybridTransformer has whole-prompt prefill and decode)" + HELD,
        state_rows=5, state_kind=None, stats={},
        decode_plan=("latent_decode_kernel_plan", (32, 8, 128, jnp.float32)), kda=None,
        sig=dict(num_layers=3, num_kv_heads=4, head_dim=16, kv_dtype="float32"),
    ),
    "lfm2-moe-tiny": dict(
        pools=(
            ((2, 60, 8, 128), "float32"),
            dict(S=((0, 5, 4, 16, 16), "float32"), conv=((7, 5, 2, 64), "float32")),
        ),
        bare=(
            ((2, 7, 8, 128), "bfloat16"),
            dict(S=((0, 7, 4, 16, 16), "float32"), conv=((7, 7, 2, 64), "bfloat16")),
        ),
        **POSITIONS, max_useful=53,
        span=dict(live_pages=30, state_rows=6, latent_pages_visited=48),
        refusal=REFUSED + "(gated short-convolution layers beside a K/V paged cache): "
        "a per-sequence convolution tail cannot be shared by a prefix, cut at a chunk "
        "or rewound, moving it between pools is not built" + HELD,
        state_rows=5, state_kind="conv", stats={},
        decode_plan=("latent_decode_kernel_plan", (32, 8, 128, jnp.float32)), kda=None,
        sig=dict(num_layers=9, num_kv_heads=2, head_dim=16, kv_dtype="float32"),
    ),
    "evabyte-tiny": dict(  # windows of 32 positions, a summary a chunk of 4
        pools=(((3, 60, 8, 128), "float32"), NO_STATE),
        bare=(((3, 7, 8, 128), "bfloat16"), NO_STATE_A_PAGE),
        table_pages=[1, 1, 2, 3, 6, 4, 6], contexts=[1, 8, 9, 16, 22, 48], max_useful=29,
        span=dict(live_pages=15, summary_rows=40, window_rows=64, latent_pages_visited=32),
        refusal=REFUSED + "(EVA layers over a compressed paged cache whose rows are not "
        "positions): a closed window's rows are overwritten by its summaries, so the "
        "cache cannot be shared by a prefix, cut at a chunk, rewound by a length or "
        "moved between pools; a step yields one token (the extra prediction heads are "
        "not served)" + HELD,
        state_rows=5, state_kind=None, stats={}, closing_window=32,
        decode_plan=("latent_decode_kernel_plan", (64, 8, 128, jnp.float32)), kda=None,
        sig=dict(num_layers=3, num_kv_heads=4, head_dim=16, kv_dtype="float32"),
    ),
    "laguna-tiny": dict(  # a ring of 8 rows a window layer
        pools=(
            ((2, 60, 8, 128), "float32"),
            dict(
                S=((0, 5, 6, 16, 16), "float32"), conv=((0, 5, 3, 288), "float32"),
                ring=((3, 5, 8, 128), "float32"),
            ),
        ),
        bare=(
            ((2, 7, 8, 128), "bfloat16"),
            dict(
                S=((0, 7, 6, 16, 16), "float32"), conv=((0, 7, 3, 288), "bfloat16"),
                ring=((3, 7, 8, 128), "bfloat16"),
            ),
        ),
        **POSITIONS, max_useful=53,
        span=dict(live_pages=30, state_rows=6, window_rows=41, latent_pages_visited=48),
        refusal=REFUSED + "(sliding-window layers over a per-sequence ring beside "
        "full-attention layers over a K/V paged cache): a ring of the last window's "
        "rows cannot be shared by a prefix, cut at a chunk or rewound by a length "
        "without storing it, moving it between pools is not built" + HELD,
        state_rows=5, state_kind="swa",
        stats=dict(kv_pool_layers=2, swa_ring_bytes=61440),
        decode_plan=("latent_decode_kernel_plan", (32, 8, 128, jnp.float32)), kda=None,
        sig=dict(num_layers=5, num_kv_heads=2, head_dim=16, kv_dtype="float32"),
    ),
}
MODELS = [name for name in CASES if "pools" in CASES[name]]
PATTERNS = [name for name in CASES if "refusal" in CASES[name]]


def layout_of(name):
    config = CASES[name].get("config") or get_preset(name)
    return cache.cache_layout(config, **SIZES)


def shapes(pools):
    return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), pools)


@pytest.mark.parametrize("name", MODELS)
def test_the_pools_are_the_parents(name):
    """Shapes, dtypes and leaves, as the engine allocates them (a state row
    a slot and one of scratch) and as a caller of ``make_kv_pages`` that
    gives no ``state_rows`` gets them (a state row a page)."""
    want, layout = CASES[name], layout_of(name)
    assert shapes(layout.allocate(60, None)) == want["pools"]
    assert shapes(make_kv_pages(layout.config, 7, 8, jnp.bfloat16)) == want["bare"]
    assert (layout.state_rows, layout.state_kind) == (want["state_rows"], want["state_kind"])
    is_pattern = layout.config.layer_pattern is not None
    assert layout.fixed_bytes == is_pattern * sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(layout.allocate(60, None)[1])
    )


@pytest.mark.parametrize("name", list(CASES))
def test_the_row_map_and_the_decode_spans_fields_are_the_parents(name):
    want, layout = CASES[name], layout_of(name)
    assert [layout.table_pages(a, b) for a, b in SPANS] == want["table_pages"]
    assert layout.contexts(LENGTHS) == want["contexts"]
    assert layout.live_pages(LENGTHS) == want["span"]["live_pages"]
    assert layout.max_useful_pages == want["max_useful"]
    span = layout.decode_span(LENGTHS, lambda: "xla")
    assert span == want["span"] and list(span) == list(want["span"])
    if "latent_pages_visited" in span:  # a schedule that follows the live cache reads what is live
        live = layout.decode_span(LENGTHS, lambda: "latent_live")
        assert live["latent_pages_visited"] == span["live_pages"]


@pytest.mark.parametrize("name", PATTERNS)
def test_a_patterns_refusal_is_the_parents_string(name):
    assert layout_of(name).refusal("spec_tokens=2") == CASES[name]["refusal"]


@pytest.mark.parametrize("name", MODELS)
def test_plans_stats_and_signature_are_the_parents(name):
    want, layout = CASES[name], layout_of(name)
    plan, args = layout.decode_plan
    assert (plan, tuple(args)) == want["decode_plan"]
    assert layout.kda_plan_args == want["kda"]
    assert layout.stats() == want["stats"]
    assert layout.snapshot_sig() == want["sig"]
    assert layout.closing_window == want.get("closing_window")


@pytest.mark.parametrize("name", ["tiny", "evabyte-tiny"])
def test_a_scheduler_counts_pages_by_the_layout_it_is_handed(name):
    layout = layout_of(name)
    sched = Scheduler(
        SchedulerConfig(max_num_seqs=4, num_pages=60, page_size=8, max_model_len=96),
        layout,
    )
    for n in (1, 31, 32, 33, 70):
        seq = Sequence(rid=f"s{n}", prompt_ids=[1] * n, params=SamplingParams())
        for reach in (n, n + 1, n + 8, 96):
            if name == "tiny":
                assert sched.pages_for(seq, reach) == -(-reach // 8)
            else:
                assert sched.pages_for(seq, reach) == eva_table_pages(n - 1, reach, 32, 4, 8)
    seq = Sequence(rid="long", prompt_ids=[1] * 70, params=SamplingParams())
    sched.add(seq)
    assert sched.admit() == [seq]
    # the next position's row (70): 9 places a row a position, 3 under EVA's map
    assert len(seq.pages) == (9 if name == "tiny" else 3)
