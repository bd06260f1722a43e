"""A percentile of the time between two stamps of a request.

Stamps: ``due``, ``sent`` (just before the publish), ``received`` (result
at the client), and the engine's own ``enqueued``, ``admitted``,
``prefill_start``, ``first_token``, ``last_token``, ``finished``.
"""

from ..stats import percentile


def read(ctx, *, start, end, q, population="due_in_window"):
    rows = getattr(ctx.records, population)() if population != "all" else ctx.records.rows
    spans = [
        (r[end] - r[start]) * 1e3
        for r in rows
        if r.get(start) and r.get(end)
    ]
    return percentile(spans, q)
