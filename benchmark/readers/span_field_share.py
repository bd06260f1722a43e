"""One field's share (%) of the sum of several fields, each summed over
the spans of one name that start inside the window and carry them all;
nothing where no span does."""

from .. import span_join


def read(ctx, *, name, field, of):
    j = span_join.load(ctx)
    if j is None:
        return None
    spans = [
        s for s in j.spans
        if s["name"] == name and all(f in s for f in of)
        and span_join.in_window(ctx, s["t0_ns"])
    ]
    whole = sum(float(s[f]) for s in spans for f in of)
    return 100.0 * sum(float(s[field]) for s in spans) / whole if whole else None
