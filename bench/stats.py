"""Arithmetic of the end-to-end metrics, from the harness's request
records alone.

Every latency is timed from the request's DUE time in the schedule, not
from when it was submitted, so a stall counts against every request that
was due behind it. A request due in the window that never produced a
first token counts with the time it had waited when the run gave up on
it: never less than it would have been. A closed loop's requests that
were preloaded during set-up are due before the window (negative times):
they count for their tokens and gaps inside the window only.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def ttfts(records, seconds: float, gave_up: float) -> list:
    """Time to first token of every request due in [0, seconds)."""
    out = []
    for r in records:
        if not 0.0 <= r.due < seconds:
            continue
        first = r.times[0] if r.times else gave_up
        out.append(first - r.due)
    return out


def gaps(records, seconds: float) -> list:
    """Every gap between successive tokens of a request, as the client
    saw them, whose two tokens arrived inside the window."""
    out = []
    for r in records:
        for a, b in zip(r.times, r.times[1:]):
            if 0.0 <= a and b < seconds:
                out.append(b - a)
    return out


def tokens_in_window(records, seconds: float) -> int:
    return sum(1 for r in records for t in r.times if 0.0 <= t < seconds)
