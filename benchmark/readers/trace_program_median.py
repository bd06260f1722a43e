"""Median device duration (ms) of the runs of a compiled program."""

from .. import trace_reduce


def read(ctx, *, program):
    if ctx.trace is None:
        return None
    return trace_reduce.median_run_ms(ctx.trace, program)
