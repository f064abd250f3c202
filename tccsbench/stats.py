"""Order statistics shared by the metric readers."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear interpolation between order statistics (numpy's "linear")."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no values")
    pos = q / 100.0 * (len(s) - 1)
    lo = int(math.floor(pos))
    if lo + 1 >= len(s):
        return s[lo]
    return s[lo] + (pos - lo) * (s[lo + 1] - s[lo])


def latencies_s(run) -> list[float]:
    """Send-to-answer seconds of every query sent in the window. A query
    never answered counts as the whole wait its run gave it, which is past
    every limit."""
    return [(r.t_done if r.t_done is not None else run.t_gave_up) - r.t_send
            for r in run.records]
