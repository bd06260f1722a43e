"""What one decode step's EVA attention has to move and compute, from its
shapes and the live cache alone (see ``kernel_cost.py``: kept with the
benchmark so that no later PR can make a layer look better by counting
more for it). It counts what runs under the program's scope
``llmq.attn.eva_decode`` and nothing beside it: the q, k and v
projections and attention over the rows a query ATTENDS, which for EVA
are not its positions: the exact keys and values of its own window and one
summary a chunk of every earlier window. Never the page write, the
summaries' making (``llmq.attn.eva_summarize``) nor ``o_proj``.
Everything is the least the algorithm needs: nothing is rounded up to
pages, lane tiles or slots, a window's dead rows are not counted, and a
kernel that multiplies more (zeros beside a head's own keys, say) is
credited with none of it.

The attended rows of a traced step are what the program's
``decode_dispatch`` spans say (``summary_rows + window_rows``: the run's
record holds only the live sequences' tokens in ALL, from which the rows
cannot be told to better than a tenth, and a share read a tenth high
would pass 100). The row arithmetic below is this file's own copy, on
purpose: tier-1 holds the program's fields to it
(``tests/test_eva.py``), so the program's own
``llmq_tpu/ops/attention.eva_context`` does not decide alone what the
program is credited with."""

from __future__ import annotations


def attended_rows(n: int, *, window: int, chunk: int) -> int:
    """Rows the query that follows ``n - 1`` cached positions attends, its
    own included (a sequence of ``n`` tokens in a decode step): ``window /
    chunk`` summaries for each complete earlier window, and its own
    window's positions up to itself."""
    if n <= 0:
        return 0
    last = n - 1
    return (window // chunk) * (last // window) + last % window + 1


def _scope_weights(*, hidden: int, heads: int, head_dim: int) -> float:
    """Values of the matrices the scope streams a layer: W_q, W_k, W_v."""
    return float(3 * hidden * heads * head_dim)


def eva_decode_bytes(
    *, attended: float, rows: float, layers: int, hidden: int, heads: int,
    head_dim: int, weight_bytes: int = 2, cache_bytes: int = 2, act_bytes: int = 2,
) -> float:
    """Bytes over the layers of one step: every attended row's keys and
    values once a layer (all heads), the scope's matrices once, each
    sequence's hidden input in and its heads' outputs out."""
    cache = 2.0 * attended * heads * head_dim * cache_bytes
    weights = _scope_weights(hidden=hidden, heads=heads, head_dim=head_dim) * weight_bytes
    acts = rows * (hidden + heads * head_dim) * act_bytes
    return layers * (cache + weights + acts)


def eva_decode_flops(
    *, attended: float, rows: float, layers: int, hidden: int, heads: int,
    head_dim: int,
) -> float:
    """Scores and the weighted sum, ``head_dim`` values each, for every
    (head, attended row), and each sequence through the scope's matrices
    (2 a multiply-add)."""
    attention = 4.0 * attended * heads * head_dim
    projections = 2.0 * rows * _scope_weights(
        hidden=hidden, heads=heads, head_dim=head_dim
    )
    return layers * (attention + projections)
