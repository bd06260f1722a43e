"""What a kernel has to move and compute, from its shapes alone. Kept
with the benchmark so that no later PR can make a kernel look better by
counting more for it."""

from __future__ import annotations

import json
from pathlib import Path


def peaks_for(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json: "
            "add its published peaks with their source"
        )
    return table[device_kind]


def decode_attention_bytes(
    *,
    live_tokens: float,
    sequences: float,
    layers: int,
    q_heads: int,
    kv_heads: int,
    head_dim: int,
    kv_bytes: int = 2,
    act_bytes: int = 2,
) -> float:
    """Bytes one decode step's attention must move over all layers: every
    live token's key and value once (``live_tokens`` summed over the
    sequences, new token included), each sequence's queries in and
    attention output out. Pages are not rounded up, masks and block
    tables not counted: this is the least the algorithm needs, so a share
    of the roofline computed from it cannot be flattered."""
    kv = 2.0 * live_tokens * kv_heads * head_dim * kv_bytes
    qo = 2.0 * sequences * q_heads * head_dim * act_bytes
    return layers * (kv + qo)


def decode_attention_flops(
    *, live_tokens: float, layers: int, q_heads: int, head_dim: int
) -> float:
    """QK^T and PV: 2 multiply-adds per (query head, live token, channel)."""
    return layers * 4.0 * live_tokens * q_heads * head_dim


def roofline_ms(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return 1e3 * max(
        flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"]
    )
