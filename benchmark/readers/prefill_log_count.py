"""How many prefill programs the engine dispatched inside the window,
from the harness's own log of them (``System.prefill_log``, what the
``window`` line prints as ``prefill_dispatches``): for a fixed job, whose
window is first submit to last result, the whole job's count, where
the span readers see the traced stretch alone. Nothing where the run kept
no log or no dispatch fell inside the window."""


def read(ctx):
    log = getattr(ctx, "prefill_log", None)
    if not log:
        return None
    t0, t1 = ctx.records.t0, ctx.records.t1
    count = sum(1 for t, *_ in log if t0 <= t < t1)
    return float(count) if count else None
