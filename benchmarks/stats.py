"""The arithmetic from timestamps to the end-to-end metrics. Times are
seconds on one host clock; results are milliseconds or per second."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile; None of nothing."""
    values = np.asarray(list(values), np.float64)
    return float(np.percentile(values, q)) if values.size else None


def gaps_ending_in(token_times, w0: float, w1: float) -> list[float]:
    """Gaps between successive tokens of one request that END inside
    [w0, w1] — the first token ends no gap."""
    t = np.asarray(token_times, np.float64)
    if t.size < 2:
        return []
    d, end = np.diff(t), t[1:]
    return d[(end >= w0) & (end <= w1)].tolist()


def tokens_in(token_times, w0: float, w1: float) -> int:
    t = np.asarray(token_times, np.float64)
    return int(((t >= w0) & (t <= w1)).sum())
