"""Arithmetic the metric readers share: percentiles and span overlap."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least ``p``% of
    the values at or below it. ``inf`` stands for a request that never
    returned, so it counts against every percentile it reaches."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[max(0, math.ceil(p / 100 * len(vals)) - 1)]


def window_spans(run, *names):
    """(start, end) of the spans of these names inside the window."""
    t0, t1 = run.served.t0, run.served.t_close
    return [(a, b) for n, a, b in run.spans
            if n in names and a >= t0 and b <= t1]


def union(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def overlap_s(a, b) -> float:
    """Seconds that two sets of intervals overlap, each counted once."""
    ua, ub = union(a), union(b)
    total, i, j = 0.0, 0, 0
    while i < len(ua) and j < len(ub):
        total += max(0.0, min(ua[i][1], ub[j][1]) - max(ua[i][0], ub[j][0]))
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return total


def query_latencies(run) -> list[float]:
    """Latency of every query due in the window; ``inf`` for one that was
    never answered."""
    lat = [q.done - q.due for q in run.served.queries]
    return lat + [math.inf] * (run.served.n_due - len(lat))
