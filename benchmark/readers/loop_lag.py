"""The latest tick of the worker's loop-lag mark inside the window (ms):
a 100 ms timer on its event loop, on for the worker's whole life
(``llmq_tpu/obs/spans.py`` ``LoopLag``), keeps the ticks that ran more
than 20 ms late. 0 where none did; nothing on a program without the
mark."""

from .. import span_join


def read(ctx):
    j = span_join.load(ctx)
    late = span_join.late_ticks(j, ctx) if j is not None else None
    if late is None:
        return None
    return max((ms for _, ms in late), default=0.0)
