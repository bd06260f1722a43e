"""A percentile of TTFT, over the same samples as ``ttft_p50_ms``."""

from ..stats import percentile


def read(ctx, *, q):
    return percentile(ctx.records.ttft_ms(), q)
