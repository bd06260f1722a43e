"""The share (%) of the time the engine's ring covered, inside the
window, during which a span of the given name was open: the union of
those spans over the covered time."""

from .. import span_join, trace_reduce


def read(ctx, *, name):
    j = span_join.load(ctx)
    if j is None or not j.covered:
        return None
    lo = max(j.covered[0], int(ctx.records.t0 * 1e9))
    hi = min(j.covered[1], int(ctx.records.t1 * 1e9))
    if hi <= lo:
        return None
    held = trace_reduce.union_ns(
        (max(lo, s["t0_ns"]), min(hi, s["t1_ns"]))
        for s in j.spans
        if s["name"] == name
    )
    return 100.0 * sum(b - a for a, b in held) / (hi - lo)
