"""Device milliseconds of some jitted programs in the traced span, per unit
of work the host saw done in the same span.

The span starts and stops at boundaries where a tick has just returned
(serve_loop.py): nothing is in flight, so the device's work in the span is
the work the host counts over it.  ``per``: ``generated_token`` or
``prompt_ktoken`` (thousands of prompt tokens fed to the device).
"""

from benchmark.trace_reduce import program_seconds

_COLUMN = {"generated_token": ("generated", 1.0), "prompt_ktoken": ("fed", 1e3)}


def read(ctx, programs, per):
    span = ctx["clock"].trace_at
    chips = ctx["reduced"]["chips"]
    if span is None or not chips:
        return None
    col, scale = _COLUMN[per]
    units = (getattr(span[1], col) - getattr(span[0], col)) / scale
    secs = sum(program_seconds(c, set(programs))[0] for c in chips) / len(chips)
    if units <= 0 or secs <= 0:
        return None
    return 1e3 * secs / units
