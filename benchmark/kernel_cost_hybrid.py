"""What one decode step of a ``bailing_hybrid`` configuration has to move
and compute, from its shapes and the step's own counters alone (see
``kernel_cost.py``: kept with the benchmark so that no later PR can make
a layer look better by counting more for it). Everything is the least
the algorithm needs: nothing is rounded up to pages, tiles or slots."""

from __future__ import annotations


def moe_expert_bytes(
    *, experts_hit: float, assignments: float, hidden: int, width: int,
    weight_bytes: int = 2, act_bytes: int = 2,
) -> float:
    """Bytes of the routed experts of one step, summed over its expert
    layers: the three matrices of every held expert that got a token
    (``experts_hit``, the program's counter), once, and each assignment's
    row in and out."""
    weights = experts_hit * 3.0 * hidden * width * weight_bytes
    rows = assignments * 2.0 * hidden * act_bytes
    return weights + rows


def moe_expert_flops(*, assignments: float, hidden: int, width: int) -> float:
    """Gate, up and down projections of each assignment."""
    return assignments * 3.0 * 2.0 * hidden * width


def kda_decode_bytes(
    *, rows: float, layers: int, hidden: int, heads: int, head_dim: int,
    weight_bytes: int = 2, state_bytes: int = 4,
) -> float:
    """Bytes of what runs under ``llmq.attn.kda`` in one step, over the
    KDA layers: each live row's state read and written once, and the five
    full-rank projections (q, k, v, the gate's W_f, the output gate's
    W_g) and the beta projection streamed once. The output projection and
    the convolution have scopes of their own and are not counted."""
    D = heads * head_dim
    state = 2.0 * rows * heads * head_dim * head_dim * state_bytes
    weights = (5.0 * hidden * D + hidden * heads) * weight_bytes
    return layers * (state + weights)


def kda_decode_flops(
    *, rows: float, layers: int, hidden: int, heads: int, head_dim: int
) -> float:
    """The projections (2 a multiply-add) and the delta rule's four passes
    over the state (decay, two reductions, the rank-one update)."""
    D = heads * head_dim
    proj = 2.0 * rows * (5.0 * hidden * D + hidden * heads)
    rule = 8.0 * rows * heads * head_dim * head_dim
    return layers * (proj + rule)


def latent_cache_bytes(
    *, live_tokens: float, layers: int, width: int, cache_bytes: int = 2
) -> float:
    """Bytes of the latent cache one step's MLA layers read: every live
    token's row of ``width`` values once a layer (all heads share it)."""
    return float(layers) * live_tokens * width * cache_bytes
