"""Own device time (ms) of an operation inside a program, summed over the
run's layers, per run of the program."""

from .. import trace_reduce


def read(ctx, *, program, op):
    if ctx.trace is None:
        return None
    return trace_reduce.op_ms_per_run(ctx.trace, program, op)
