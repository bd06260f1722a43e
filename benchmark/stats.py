"""Percentile arithmetic of the benchmark. One definition, used by every
metric: linear interpolation between order statistics (numpy's default,
Hyndman-Fan type 7), on the whole population handed in."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) of ``values``; None when there are none."""
    xs: List[float] = sorted(float(v) for v in values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def summary(values: Sequence[float], qs=(50, 90, 95, 99)) -> dict:
    """Percentiles with their sample count, for the earlier lines of a run."""
    out = {"n": len(values)}
    for q in qs:
        out[f"p{q}"] = percentile(values, q)
    out["max"] = max(values) if values else None
    return out

