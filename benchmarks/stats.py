"""Order statistics the end-to-end metrics are made of."""
from __future__ import annotations

import math

import numpy as np


def harrell_davis_weights(n, q=0.5):
    """Weights w_i = I(i/n) - I((i-1)/n) over the order statistics, I the
    regularized incomplete beta function with a=(n+1)q, b=(n+1)(1-q)."""
    from scipy.special import betainc
    a, b = (n + 1) * q, (n + 1) * (1 - q)
    edges = betainc(a, b, np.arange(n + 1) / n)
    return np.diff(edges)


def harrell_davis(values, q=0.5):
    """Harrell-Davis estimate of the q-quantile: every order statistic
    weighted, so the estimate moves smoothly when one sample moves (the
    sample median of nine jumps by a whole sample)."""
    x = np.sort(np.asarray(values, np.float64))
    if x.size == 0:
        raise ValueError("harrell_davis: no samples")
    return float(np.dot(harrell_davis_weights(x.size, q), x))


def percentile(values, q):
    """Plain sample quantile (linear interpolation), q in [0, 1]."""
    x = np.asarray(values, np.float64)
    if x.size == 0:
        raise ValueError("percentile: no samples")
    return float(np.quantile(x, q))


def quartile_spread(values):
    """(Q3 - Q1) / median by `statistics.quantiles(n=4)`: the spread the
    bounds in BENCHMARK.json are set from."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else math.inf
