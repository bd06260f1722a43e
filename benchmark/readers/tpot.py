"""A percentile of per-request TPOT, over the same samples as ``tpot_p50_ms``."""

from ..stats import percentile


def read(ctx, *, q):
    return percentile(ctx.records.tpot_ms(), q)
