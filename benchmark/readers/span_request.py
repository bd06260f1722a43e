"""A percentile of the time between two stamps of a request, where either
may be one the program's span ring kept (``claimed``, ``engine_submit``,
``result_published``; ``llmq_tpu/obs/spans.py``) or one of the run's own
records (``due``, ``sent``, ``received``). Over the requests of the run
whose stamps the ring holds: those that finished while it was on."""

from .. import span_join
from ..stats import percentile


def read(ctx, *, start, end, q):
    j = span_join.load(ctx)
    if j is None:
        return None
    out = []
    for row in ctx.records.rows:
        kept = j.requests.get(row["rid"])
        if not kept:
            continue
        a = kept.get(start) or row.get(start)
        b = kept.get(end) or row.get(end)
        if a and b:
            out.append((b - a) * 1e3)
    return percentile(out, q)
