"""A quantile (or the mean) of one of the host-clock series the adapter
collects: `args` name the series, `q` (0-100, or "mean") and a scale."""

import numpy as np


def read(ctx, series: str, q, scale: float = 1.0):
    values = ctx["series"].get(series)
    if not values:
        return None
    x = np.mean(values) if q == "mean" else np.percentile(values, q)
    return float(x) * scale
