"""Arithmetic of the end-to-end metrics, over every request of a window.

A rate is taken over all the work and all the time of the window; a
tail over all samples of the window.  Percentiles interpolate linearly
between order statistics (numpy's default).
"""

from __future__ import annotations

from typing import Iterable

import numpy as np


def percentile(values: Iterable[float], q: float) -> float | None:
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        return None
    return float(np.percentile(v, q))


def tokens_in(stamps_per_request: Iterable[list[float]], t0: float,
              t1: float) -> int:
    """Output tokens whose time falls in ``[t0, t1)``; tokens of requests
    that began before the window count."""

    return sum(sum(1 for t in s if t0 <= t < t1) for s in stamps_per_request)


def token_gaps(stamps_per_request: Iterable[list[float]], t0: float,
               t1: float) -> list[float]:
    """Every gap between consecutive output tokens of a request, both
    tokens inside ``[t0, t1)``."""

    out = []
    for s in stamps_per_request:
        inside = [t for t in s if t0 <= t < t1]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def first_token_latencies(recs, t0: float, t1: float) -> list[float]:
    """First-token time minus ``due``, for every request whose first
    token falls in ``[t0, t1)``."""

    return [r.stamps[0] - r.due for r in recs
            if r.stamps and t0 <= r.stamps[0] < t1]
