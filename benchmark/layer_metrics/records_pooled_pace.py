"""The pooled decode pace of the requests a traced run finished BEFORE its
traced span: milliseconds per token over all their tokens after a first one
(``stats.pooled_pace``: the sum of ``finish_s - first_token_s`` over the sum
of ``tokens - 1``).

Read from the serving records, not from the trace: the tracer's start and
stop delay the loop, so what finished inside or after the span is left out.
None where no such request has a second token.
"""

from benchmark import stats


def read(ctx):
    span = ctx["clock"].trace_at
    if span is None:
        return None
    t_span = span[0].t - ctx["clock"].t0
    done = [r for r in ctx["records"].values()
            if r.get("finish_s", t_span) < t_span]
    pace = stats.pooled_pace(stats.decode_spans(done))
    return None if pace is None else 1e3 * pace
