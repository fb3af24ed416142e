"""A sum over the program's build log (flexflow_tpu ``obs/journal.py``
``builds``: every trace, lowering and backend compile JAX reported in this
process, outermost builds only, by program name): the seconds — with
``count`` the number — of the events of the kinds ``what`` whose
``fun_name`` is one of ``programs``, stamped before the measured loop's own
zero (``clock.t0``): what set-up spent building the deployment's programs.

``what``: of ``trace``, ``lower``, ``compile`` (a ``compile`` the
persistent cache answered is one too, and holds the cache read).  None
where the program keeps no log (an older one) or the loop never started.
The first reading of a run logs the table by program, and what the log
dropped: it is a ring, and a sum over a log that dropped is a lower bound.
"""


def read(ctx, what, programs, count=False):
    from flexflow_tpu.obs import journal as J

    t0 = ctx["clock"].t0
    if not hasattr(J, "builds") or t0 is None:
        return None
    built = J.builds(programs=programs, before_ns=int(t0 * 1e9))
    events = [b for b in built if b.what in what]
    if not ctx.get("_build_log_logged"):
        ctx["_build_log_logged"] = True
        log = J.build_log()
        table = {}
        for b in built:
            row = table.setdefault(b.fun_name, {})
            n, s, hits = row.get(b.what, (0, 0.0, 0))
            row[b.what] = (n + 1, s + b.dur_ns / 1e9, hits + b.cached)
        by_program = "; ".join(
            name + " " + ", ".join(
                f"{k} {n} in {s:.2f}s" + (f" ({hits} cached)" if hits else "")
                for k, (n, s, hits) in sorted(row.items()))
            for name, row in sorted(table.items()))
        ctx["log"](
            f"build log: {log.emitted} events in the process, {log.dropped} "
            f"dropped; before the window, by program: {by_program or 'none'}")
    if count:
        return len(events)
    return sum(b.dur_ns for b in events) / 1e9
