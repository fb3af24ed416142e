"""Metric arithmetic the benchmark owns (a program PR cannot move it)."""

import statistics


def percentile(values, q):
    """Nearest-rank percentile of ``values`` (None when empty).  The same
    convention as ``flexflow_tpu/obs/metrics.percentile``, kept here as the
    benchmark's own copy."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[min(int(q * len(xs)), len(xs) - 1)]


def iqr_share(values):
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``): the spread a bound is
    set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
