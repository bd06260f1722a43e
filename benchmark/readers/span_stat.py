"""The mean of one field (``rows``) over the spans of one name that
start inside the window."""

from .. import span_join


def read(ctx, *, name, field):
    j = span_join.load(ctx)
    if j is None:
        return None
    values = [
        float(s[field])
        for s in j.spans
        if s["name"] == name and field in s and span_join.in_window(ctx, s["t0_ns"])
    ]
    return sum(values) / len(values) if values else None
