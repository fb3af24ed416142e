"""From the profiler's ``.xplane.pb`` to what the per-layer readers take.

One device plane per chip (``/device:TPU:<n>``).  On it the line
``XLA Modules`` holds one event per execution of a jitted program (named
``jit_<function>(<fingerprint>)``) and the line ``XLA Ops`` one event per
HLO operation, both with a start and a duration on the device's clock.  The
reduction keeps, per chip:

  programs   [(program name, start_s, seconds)]    from ``XLA Modules``
  ops        {op name: (count, seconds)}           from ``XLA Ops``, by the
             instruction's name without its number (``op_name``)
  op_events  [(op name, start_s, seconds)]         the same, one by one
  busy_s     union of the op intervals (operations overlap across lines)
  gaps       [(seconds, program before, program after)] idle stretches
             between op intervals, longest first

A program's name is its jitted function's (``_step_impl``,
``_decode_scan_impl``, ``_prefill_scan_impl``, ``_join_impl``) and a Pallas
kernel's event carries the kernel function's name (the calls have no
``name=``).  This reduction goes by those names alone; what lies below
them — each operation's ``jax.named_scope`` path by graph node and stage,
and the scheduler's spans on the host plane, both there since PR 27 — is
read by ``xplane_spans.py``.

    python -m benchmark.trace_reduce <file.xplane.pb>     # what a trace holds
"""

import glob
import json
import os
import re
import sys

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_PROGRAM = re.compile(r"^jit_(.*?)(\(\d+\))?$")
_OP = re.compile(r"^%?([^\s=]+?)(\.\d+)?(\s*=.*)?$", re.S)
# operations that only contain others (their time is their bodies')
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def program_name(event_name):
    m = _PROGRAM.match(event_name)
    return m.group(1) if m else event_name


def op_name(event_name):
    """``%decode_attention.36 = bf16[...] custom-call(...)`` ->
    ``decode_attention``: an ``XLA Ops`` event is named by the whole HLO
    instruction; a Pallas call's instruction carries the kernel function's
    name, a fusion only XLA's (``fusion``, ``copy``, ...)."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name


def read_planes(path):
    """``{plane name: {line name: [(name, start_ns, dur_ns)]}}`` for the
    device planes."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if not _DEVICE.match(plane.name):
            continue
        planes[plane.name] = {
            line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
            for line in plane.lines}
    return planes


def _union(intervals):
    """Merged ``[start, end]`` list of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce_plane(lines):
    modules = sorted(lines.get(MODULE_LINE, []), key=lambda e: e[1])
    ops = lines.get(OP_LINE, [])
    programs = [(program_name(n), s / 1e9, d / 1e9) for n, s, d in modules]
    op_events = [(op_name(n), s / 1e9, d / 1e9) for n, s, d in ops]
    by_op = {}
    for n, _, d in op_events:
        c, t = by_op.get(n, (0, 0.0))
        by_op[n] = (c + 1, t + d)
    busy = _union([(s, s + d) for _, s, d in (ops or modules)])
    busy_s = sum(e - s for s, e in busy) / 1e9

    # the scheduler's scalar conversions run as programs of microseconds:
    # a gap is named by the real programs on either side
    named = [m for m in modules if m[2] >= 1e5] or modules

    def program_at(t_ns, after):
        pick = None
        for n, s, d in named:
            if after and s >= t_ns:
                return program_name(n)
            if not after and s + d <= t_ns:
                pick = program_name(n)
        return pick

    gaps = sorted(((b[0] - a[1]) / 1e9, a[1], b[0])
                  for a, b in zip(busy, busy[1:]))[::-1][:50]
    gaps = [(g, program_at(a, False), program_at(b, True))
            for g, a, b in gaps]
    span_s = (busy[-1][1] - busy[0][0]) / 1e9 if busy else 0.0
    return {"programs": programs, "ops": by_op, "op_events": op_events,
            "busy_s": busy_s,
            "device_span_s": span_s, "gaps": gaps}


def reduce_trace(path):
    """``{"chips": [per-chip reduction, ...]}`` in device order."""
    planes = read_planes(path)
    order = sorted(planes, key=lambda n: int(_DEVICE.match(n).group(1)))
    return {"chips": [reduce_plane(planes[n]) for n in order]}


def op_seconds(chip, name):
    """Device seconds and calls of the operations called ``name``."""
    count, secs = chip["ops"].get(name, (0, 0.0))
    return secs, count


def op_seconds_inside(chip, name, programs):
    """Device seconds and calls of the operations called ``name`` that
    started while one of ``programs`` was executing."""
    import bisect

    spans = sorted((s, s + d) for n, s, d in chip["programs"]
                   if n in programs)
    starts = [s for s, _ in spans]
    secs, count = 0.0, 0
    for n, s, d in chip["op_events"]:
        if n != name:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            secs, count = secs + d, count + 1
    return secs, count


def program_seconds(chip, names):
    """Device seconds and executions of the programs called ``names``."""
    hits = [d for n, _, d in chip["programs"] if n in names]
    return sum(hits), len(hits)


def breakdown(reduced, top=10):
    """The contract's ``breakdown``: the device operations that took most
    time, and the longest idle gaps by the programs on either side (what
    the host was dispatching), both on chip 0."""
    chip = reduced["chips"][0]
    ops = sorted(((n, t) for n, (_, t) in chip["ops"].items()
                  if n not in CONTAINERS), key=lambda x: -x[1])[:top]
    by_edge = {}
    for g, before, after in chip["gaps"]:
        key = f"after {before or 'span edge'} before {after or 'span edge'}"
        by_edge[key] = by_edge.get(key, 0.0) + g
    gaps = sorted(by_edge.items(), key=lambda x: -x[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps]}


def main(argv):
    path = argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            names = {}
            for e in line.events:
                c, t = names.get(e.name, (0, 0.0))
                names[e.name] = (c + 1, t + e.duration_ns / 1e9)
            print(f"  LINE {line.name!r}: {sum(c for c, _ in names.values())}"
                  f" events, {len(names)} names")
            for n, (c, t) in sorted(names.items(),
                                    key=lambda x: -x[1][1])[:25]:
                print(f"      {t:10.6f}s {c:7d}x  {n[:150]}")
    red = reduce_trace(path)
    for i, chip in enumerate(red["chips"]):
        print(f"chip {i}: busy {chip['busy_s']:.4f}s of device span "
              f"{chip['device_span_s']:.4f}s; longest gaps "
              f"{[(round(g, 5), a, b) for g, a, b in chip['gaps'][:8]]}")
    print(json.dumps(breakdown(red)))


if __name__ == "__main__":
    main(sys.argv)
