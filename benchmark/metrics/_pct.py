"""Exact percentiles of a sample (a sort, linear between ranks)."""
import numpy as np


def pct(values, q):
    if not values:
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
