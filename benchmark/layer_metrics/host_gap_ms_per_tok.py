"""Device-idle milliseconds that lie under a span of the scheduler, per
token generated in the traced span.

Idle is the complement of the union of chip 0's ``XLA Ops`` intervals
between the first scheduler span's start and the last one's end; each idle
stretch is charged to the innermost span open over it (xplane_spans).  What
lies under no span at all is left out of the numerator and logged.  The
tokens are those the ``commit`` spans say they appended.  None where the
trace holds no scheduler span (a program without them).
"""

from benchmark import xplane_spans as xs


def read(ctx):
    trace = xs.for_run(ctx)
    xs.log_run(trace, ctx)
    idle = xs.idle_by_span(trace)
    made = xs.committed_tokens(trace)
    if idle is None or not made:
        return None
    under = sum(v for k, v in idle.items() if k != xs.NO_SPAN)
    return 1e3 * under / made
