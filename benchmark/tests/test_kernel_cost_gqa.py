"""The bytes and operations of grouped-query decode attention in a
pattern, against shapes worked by hand."""

import json
from pathlib import Path

import pytest

from benchmark import kernel_cost, kernel_cost_gqa

LFM2 = dict(hidden=2048, heads=32, kv_heads=8, head_dim=64)
CONFIGS = Path(kernel_cost_gqa.__file__).parent / "configs"


def test_one_token_one_row_by_hand():
    # One layer, one live token, one row:
    # cache: K and V, 8 heads x 64 values x 2 B each = 2,048 B
    # W_q 2,048 x 2,048 + W_k, W_v 2 x 2,048 x 512 = 6,291,456 values x 2 B
    # the row's hidden in and heads' outputs out: (2,048 + 2,048) x 2 B
    kw = dict(live_tokens=1, rows=1, layers=1, **LFM2)
    assert kernel_cost_gqa.gqa_decode_bytes(**kw) == 2_048 + 12_582_912 + 8_192
    # attention: 4 x 32 heads x 64 = 8,192; the row through the matrices
    assert kernel_cost_gqa.gqa_decode_flops(**kw) == 8_192 + 12_582_912


def test_at_the_cell_size_the_bytes_are_the_bound():
    # 128 rows, 524,000 live tokens, 2 attention layers: 2.146 GB of keys
    # and values + 0.025 GB of matrices: 2.65 ms at 819 GB/s; 8.6 + 3.2
    # GFLOP: 0.06 ms at 197 TFLOP/s.
    kw = dict(live_tokens=524_000, rows=128, layers=2, **LFM2)
    peaks = kernel_cost.peaks_for("TPU v5 lite")
    nbytes = kernel_cost_gqa.gqa_decode_bytes(**kw)
    flops = kernel_cost_gqa.gqa_decode_flops(**kw)
    assert nbytes == 2 * (524_000 * 2_048 + 12_582_912 + 128 * 8_192)
    assert flops == 2 * (524_000 * 8_192 + 128 * 12_582_912)
    assert 1e3 * nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(2.654, rel=1e-3)
    assert 1e3 * flops / peaks["bf16_flops_per_s"] < 0.07
    assert kernel_cost.roofline_ms(flops, nbytes, peaks) == pytest.approx(2.654, rel=1e-3)


@pytest.mark.parametrize(
    "config, layers",
    [("lfm2-24b-a2b-pp5", 2), ("qwen2.5-3b-bf16", 36), ("openpangu-ultra-moe-718b-ep16", 5)],
)
def test_attention_layers_are_counted_from_layer_types(config, layers):
    # lfm2: published layers 2 and 6 of the nine kept; a file without
    # layer_types: every layer (no cell lists such a configuration).
    cfg = json.loads((CONFIGS / f"{config}.json").read_text())
    assert kernel_cost_gqa.attention_layers(cfg) == layers


def test_the_whole_published_model_has_ten_attention_layers():
    cfg = json.loads((CONFIGS / "lfm2-24b-a2b-pp5.json").read_text())
    whole = {k: v for k, v in cfg.items() if k != "kept_layers"}
    whole["num_hidden_layers"] = 40
    assert kernel_cost_gqa.attention_layers(whole) == 10


def test_query_heads_share_a_kv_heads_keys():
    # Four times the query heads read the same cache: the bytes grow by
    # the queries' projection and outputs alone.
    kw = dict(live_tokens=1000, rows=0, layers=1, hidden=2048, kv_heads=8, head_dim=64)
    more = kernel_cost_gqa.gqa_decode_bytes(heads=128, **kw)
    less = kernel_cost_gqa.gqa_decode_bytes(heads=32, **kw)
    assert more - less == 2048 * 96 * 64 * 2
