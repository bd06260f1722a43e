"""The quotient of two program counters' changes over the window (keys of
``engine.stats()``); nothing where either is absent or the second stood
still."""


def read(ctx, *, over, under):
    d = ctx.records.drive
    if any(k not in s for k in (over, under) for s in (d.stats0, d.stats1)):
        return None
    below = d.stats1[under] - d.stats0[under]
    return float(d.stats1[over] - d.stats0[over]) / below if below else None
