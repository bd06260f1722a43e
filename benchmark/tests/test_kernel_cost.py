"""The bytes and operations of the decode attention kernel, against
shapes worked by hand."""

import pytest

from benchmark import kernel_cost


def test_decode_attention_bytes_by_hand():
    # One layer, one sequence of 1,000 live tokens, 2 kv heads of 128, bf16:
    # K and V: 2 * 1000 * 2 * 128 * 2 B = 1,024,000 B
    # q in and out: 2 * 1 * 16 * 128 * 2 B = 8,192 B
    got = kernel_cost.decode_attention_bytes(
        live_tokens=1000, sequences=1, layers=1, q_heads=16, kv_heads=2, head_dim=128
    )
    assert got == 1_024_000 + 8_192


def test_decode_attention_bytes_at_the_cell_size():
    # qwen2.5-3b, 128 sequences of 1,536 live tokens, 36 layers:
    # K and V per layer: 2 * 196,608 * 2 * 128 * 2 = 201,326,592 B
    # q and out per layer: 2 * 128 * 16 * 128 * 2 = 1,048,576 B
    got = kernel_cost.decode_attention_bytes(
        live_tokens=128 * 1536, sequences=128, layers=36, q_heads=16, kv_heads=2,
        head_dim=128,
    )
    assert got == 36 * (201_326_592 + 1_048_576)
    # at 819 GB/s: 7,285,506,048 B -> 8.8956 ms: the floor of the 70 ms PR 26 read
    peaks = kernel_cost.peaks_for("TPU v5 lite")
    flops = kernel_cost.decode_attention_flops(
        live_tokens=128 * 1536, layers=36, q_heads=16, head_dim=128
    )
    assert flops == 36 * 4 * 196_608 * 16 * 128
    assert kernel_cost.roofline_ms(flops, got, peaks) == pytest.approx(8.8956, rel=1e-4)


def test_fp8_pool_halves_the_cache_bytes():
    kw = dict(live_tokens=1000, sequences=1, layers=1, q_heads=16, kv_heads=2, head_dim=128)
    assert (
        kernel_cost.decode_attention_bytes(kv_bytes=1, **kw)
        == 512_000 + 8_192
    )


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        kernel_cost.peaks_for("TPU v99")
