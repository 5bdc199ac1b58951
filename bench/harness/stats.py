"""Percentiles and per-request latencies from the harness's records.

Every time is the host's ``perf_counter`` in seconds. A request's tokens
arrive at the syncs that return them: ``syncs`` is a list of
``(time, tokens delivered so far)``.
"""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default), for
    ``p`` in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    h = (len(xs) - 1) * p / 100.0
    lo = math.floor(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def ttft_s(due: float, syncs, t_end: float) -> float:
    """Due time to the sync that returned the first token; a request with
    no token by ``t_end`` enters with the time it has waited."""
    for t, n in syncs:
        if n >= 1 and t <= t_end:
            return t - due
    return t_end - due


def tpot_s(syncs, t_end: float):
    """(last token's time - first token's time) / (tokens - 1), over the
    tokens returned by ``t_end``; None below two tokens."""
    seen = [(t, n) for t, n in syncs if t <= t_end and n >= 1]
    if not seen or seen[-1][1] < 2:
        return None
    return (seen[-1][0] - seen[0][0]) / (seen[-1][1] - 1)
