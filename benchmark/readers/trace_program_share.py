"""Device time of the programs matching a pattern, over busy time (%)."""

from .. import trace_reduce


def read(ctx, *, program):
    if ctx.trace is None:
        return None
    return trace_reduce.program_share_pct(ctx.trace, program)
