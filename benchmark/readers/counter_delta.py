"""A program counter's change over the window: a key of ``engine.stats()``
or, with ``source: "compile"``, of the ``CompileMeter``."""


def read(ctx, *, key, source="stats"):
    d = ctx.records.drive
    a, b = (d.compile0, d.compile1) if source == "compile" else (d.stats0, d.stats1)
    if key not in a or key not in b:
        return None
    return float(b[key] - a[key])
