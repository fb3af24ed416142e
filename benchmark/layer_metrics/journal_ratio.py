"""A ratio of sums over the scheduler's tick journal (flexflow_tpu
``obs/journal.py``: one record per tick of the serving loop, always on):
``scale`` x sum(``num``) / sum(``den``) over the records of the window
that the profiler session did not touch.

Kept: records that lie inside the window (``clock.opened.t`` to
``clock.closed.t``) and do not overlap the session (``clock.trace_at[0].t``
to ``clock.tracer.t_stop``).  The record that CONTAINS ``t_stop`` overlaps
it and goes too: ``Tracer.stop`` stamps ``t_stop`` before ``stop_trace()``,
from inside the clock hook, so the stop's stall of seconds lies in that
record (under ``loop_clock``).  In an open cell that leaves the window's
first 49 s, in a closed cell its first second and what the stall left.

``num`` / ``den``: the journal's fields to add up, and two made here:
``extent_ns`` (``t1_ns - t0_ns``) and ``slow_excess_ns`` (how far a slow
tick lies over its class's median, by the journal's own rule for its
slow-tick report, applied to the kept records).  ``where``: ``[field,
"zero" | "positive"]`` keeps the records whose field is 0 / above 0.
None where the program keeps no journal (an older one), no record is kept
or the denominator is 0.  The first reading of a run logs what was kept.
"""

import numpy as np


def kept_records(ctx):
    """``(the journal, its module, the kept rows)`` or None."""
    rm = getattr(ctx["llm"], "rm", None)
    jr = getattr(rm, "journal", None)
    clock = ctx["clock"]
    if jr is None or clock.opened is None or clock.closed is None:
        return None
    from flexflow_tpu.obs import journal as J

    rows = jr.array()
    if not len(rows):
        return None
    t0, t1 = rows[:, J.FIELDS.index("t0_ns")], rows[:, J.FIELDS.index("t1_ns")]
    keep = (t0 >= int(clock.opened.t * 1e9)) & (t1 <= int(clock.closed.t * 1e9))
    tracer = getattr(clock, "tracer", None)
    if clock.trace_at is not None and tracer is not None \
            and tracer.t_stop is not None:
        s0, s1 = int(clock.trace_at[0].t * 1e9), int(tracer.t_stop * 1e9)
        keep &= (t1 <= s0) | (t0 >= s1)
    return jr, J, rows[keep]


def column(J, rows, name):
    if name == "extent_ns":
        return J.extent_ns(rows)
    if name == "slow_excess_ns":
        return J.slow_excess_ns(rows)
    return rows[:, J.FIELDS.index(name)]


def read(ctx, num, den, where=None, scale=1.0):
    got = kept_records(ctx)
    if got is None:
        return None
    jr, J, rows = got
    if not ctx.get("_journal_logged"):
        ctx["_journal_logged"] = True
        ext = J.extent_ns(rows)
        deep = rows[column(J, rows, "ctx_rows") > 0]
        depth = (column(J, deep, "ctx_sum") / column(J, deep, "ctx_rows")
                 ).mean() if len(deep) else float("nan")
        ctx["log"](
            f"journal: read {ext.sum() / 1e9:.3f}s in {len(rows)} records of "
            f"the window outside the profiler session (ring: {jr.emitted} "
            f"written, {jr.dropped} dropped); mean ctx_sum / rows over them "
            f"{depth:.1f}")
    cols = {name: column(J, rows, name) for name in set(num) | set(den)}
    mask = np.ones(len(rows), bool)
    if where is not None:
        field, how = where
        value = column(J, rows, field)
        mask = value == 0 if how == "zero" else value > 0
    bottom = sum(int(cols[name][mask].sum()) for name in den)
    if bottom <= 0:
        return None
    top = sum(int(cols[name][mask].sum()) for name in num)
    return scale * top / bottom

