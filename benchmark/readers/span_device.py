"""A percentile of a time between a dispatch span and the device run it
launched, host and device on one clock (``span_join``): ``queue`` is the
span's start to the run's start on the device, ``fetch_lag`` the run's
end on the device to the end of its ``fetch`` span, ``device`` the run's
own time on the device."""

from .. import span_join
from ..stats import percentile


def read(ctx, *, which, name, q):
    j = span_join.load(ctx)
    if j is None or ctx.trace is None:
        return None
    values = {
        "queue": span_join.queue_ms,
        "fetch_lag": span_join.fetch_lag_ms,
        "device": span_join.device_ms,
    }[which](j, name)
    return percentile(values, q)
