"""Own device time (ms) of the operations traced under one ``llmq.*``
scope, summed over a run's layers, per run of a program. Which
instruction belongs to which scope comes from the program's own dump
(``scopes``), not from the names the compiler happened to give."""

from .. import span_join


def read(ctx, *, program, scope):
    j = span_join.load(ctx)
    if j is None:
        return None
    return span_join.scope_ms_per_run(j, program, scope)
