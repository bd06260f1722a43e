"""The bytes and operations of latent decode attention, against shapes
worked by hand."""

import pytest

from benchmark import kernel_cost, kernel_cost_mla

PANGU = dict(hidden=7680, heads=128, kv_rank=512, nope=128, rope=64, v_dim=128)


def test_one_token_one_row_by_hand():
    # One layer, one live token, one row, a query LoRA (its matrices are
    # another scope's):
    # cache: 576 values * 2 B = 1,152 B
    # W_kva 7,680 * 576 + W_uk, W_uv 512 * 128 * 256 = 21,200,896 values * 2 B
    # the row's hidden in and heads' values out: (7,680 + 16,384) * 2 B
    kw = dict(live_tokens=1, rows=1, layers=1, q_lora=True, **PANGU)
    assert kernel_cost_mla.mla_decode_bytes(**kw) == 1_152 + 42_401_792 + 48_128
    # attention: 2 * 128 heads * (576 + 512) = 278,528; the row through the
    # scope's matrices: 2 * 21,200,896
    assert kernel_cost_mla.mla_decode_flops(**kw) == 278_528 + 42_401_792


def test_without_a_query_lora_the_query_projection_is_the_scope_s():
    kw = dict(live_tokens=0, rows=0, layers=1, **PANGU)
    extra = 7680 * 128 * 192 * 2
    assert (
        kernel_cost_mla.mla_decode_bytes(q_lora=False, **kw)
        - kernel_cost_mla.mla_decode_bytes(q_lora=True, **kw)
    ) == extra


def test_at_the_cell_size_the_two_bounds_are_a_tenth_apart():
    # 128 rows, 295,000 live tokens, 5 layers: 1.70 GB of rows + 0.21 GB of
    # matrices + 0.03 GB of rows in and out: 2.37 ms at 819 GB/s; 0.41 +
    # 0.03 TFLOP: 2.22 ms at 197 TFLOP/s. Both at once: the ridge.
    kw = dict(live_tokens=295_000, rows=128, layers=5, q_lora=True, **PANGU)
    peaks = kernel_cost.peaks_for("TPU v5 lite")
    nbytes = kernel_cost_mla.mla_decode_bytes(**kw)
    flops = kernel_cost_mla.mla_decode_flops(**kw)
    assert nbytes == 5 * (295_000 * 1152 + 42_401_792 + 128 * 48_128)
    assert flops == 5 * (295_000 * 278_528 + 128 * 42_401_792)
    assert 1e3 * nbytes / peaks["hbm_bytes_per_s"] == pytest.approx(2.371, rel=1e-3)
    assert 1e3 * flops / peaks["bf16_flops_per_s"] == pytest.approx(2.223, rel=1e-3)
    assert kernel_cost.roofline_ms(flops, nbytes, peaks) == pytest.approx(2.371, rel=1e-3)


@pytest.mark.parametrize(
    "config, layers",
    [("openpangu-ultra-moe-718b-ep16", 5), ("ling-3.0-flash-ep4", 1), ("qwen2.5-3b-bf16", 36)],
)
def test_latent_layers_are_counted_from_the_pattern(config, layers):
    # openpangu: every kept layer; ling: the one layer in its seven whose
    # published index closes a group of six (kept_layers); a file without
    # a pattern: every layer (no cell lists such a configuration).
    import json
    from pathlib import Path

    cfg = json.loads((Path(kernel_cost_mla.__file__).parent / "configs" / f"{config}.json").read_text())
    assert kernel_cost_mla.latent_layers(cfg) == layers


def test_a_pool_that_stores_another_row_is_counted_as_stored():
    kw = dict(live_tokens=1000, rows=0, layers=1, q_lora=True, **PANGU)
    assert (
        kernel_cost_mla.mla_decode_bytes(row_values=640, **kw)
        - kernel_cost_mla.mla_decode_bytes(**kw)
    ) == 1000 * 64 * 2
