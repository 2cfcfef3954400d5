"""Percentiles and window arithmetic over the stamps of one run.

All times are seconds on one clock (the harness's ``perf_counter``
origin).  A run measures the window [t0, t1).  Percentiles are numpy's
default linear interpolation between order statistics.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """q-th percentile (0..100) of ``values``; None when there are none."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else None


def censored_waits(due, done, t0: float, t1: float) -> list:
    """For every request due in [t0, t1): the time from its due time to
    ``done`` (its first token, its admission, ...), or to t1 where that
    came later or never (``done`` None)."""
    out = []
    for d, x in zip(due, done):
        if t0 <= d < t1:
            end = t1 if x is None else min(x, t1)
            out.append(end - d)
    return out


def gaps_ending_in(stamps_per_request, t0: float, t1: float) -> list:
    """Every gap between consecutive token stamps of one request whose
    later stamp lies in [t0, t1), over all requests."""
    out = []
    for st in stamps_per_request:
        for a, b in zip(st, st[1:]):
            if t0 <= b < t1:
                out.append(b - a)
    return out


def count_in(stamps_per_request, t0: float, t1: float) -> int:
    """Token stamps in [t0, t1), over all requests."""
    return sum(1 for st in stamps_per_request for s in st if t0 <= s < t1)
