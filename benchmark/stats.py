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


def decode_spans(records):
    """Per ``ok`` record with two tokens or more: ``(seconds from its first
    token's stamp to its last one's, tokens after the first)``.  A request
    with one token has no pace, and one that failed no ``finish_s``."""
    return [(r["finish_s"] - r["first_token_s"], len(r["tokens"]) - 1)
            for r in records
            if r["outcome"] == "ok" and len(r["tokens"]) >= 2]


def pooled_pace(spans):
    """Seconds per token over ALL tokens after a first one: the sum of the
    spans over the sum of their tokens (None when there is none) — each
    request's pace weighted by its tokens.  Stamps are the ends of decode
    stretches, so a short answer's own pace is 0 or a whole stretch over a
    few tokens; a ratio of sums holds no such point mass, a percentile of
    the per-request paces sits on its edge (PERF.md section 2)."""
    tokens = sum(n for _, n in spans)
    return sum(s for s, _ in spans) / tokens if tokens else None


def zero_pace_share(spans):
    """Share of those requests whose first and last token were stamped at
    the same step boundary (None when there is none)."""
    return sum(1 for s, _ in spans if s == 0) / len(spans) if spans else None
