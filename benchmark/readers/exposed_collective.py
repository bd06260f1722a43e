"""Collective time during which nothing else runs on the chip, over busy time (%)."""

from .. import trace_reduce


def read(ctx):
    if ctx.trace is None:
        return None
    return trace_reduce.exposed_collective_pct(ctx.trace)
