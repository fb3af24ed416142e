"""The roofline share, in percent, of what some graph-node classes do inside
some programs, the least work computed from sums the program's OWN spans
carry.

  measured = summed device time of the ``XLA Ops`` events that started while
             one of the programs ``inside`` was executing and whose innermost
             graph-node scope (xplane_spans) is of one of the operator
             classes ``nodes`` — with ``stages`` given, only those whose
             innermost stage scope is one of them;
  least    = the larger of operations / peak FLOP/s and bytes / peak bytes/s
             from ``cost`` (``"<module of benchmark/>:<function>"``), called
             with the sums of the arguments ``sums`` over the spans named
             ``spans`` in the trace, in order, and the configuration's
             published fields.

A closed cell's traced span starts and stops where a tick has just returned
(serve_loop.py): nothing is in flight, so the launches the spans speak of
are the device work the span holds.  None where no such span carries the
first of the arguments (a program without it), the trace holds no such
operation, the cost module is not there or the configuration lacks a field
the cost reads.
"""

import importlib

from benchmark import xplane_spans as xs
from benchmark.costs import roofline_seconds


def read(ctx, nodes, cost, inside, spans, sums, stages=()):
    module, _, function = cost.partition(":")
    try:
        cost_fn = getattr(importlib.import_module("benchmark." + module),
                          function)
    except (ImportError, AttributeError):
        return None
    trace = xs.for_run(ctx)
    held = [h for h in xs.program_spans(trace) if h.name in spans]
    totals = [sum(int(h.args.get(k) or 0) for h in held) for k in sums]
    if not totals or not totals[0]:
        return None
    ns = 0.0
    chips = range(len(trace.device_planes()))
    for c in chips:
        for o in xs.ops_in_programs(trace, set(inside), c):
            node, stage = xs.scope_of(o)
            if node and node.split(".", 1)[0] in nodes and (
                    not stages or stage in stages):
                ns += o.dur_ns
    if ns <= 0:
        return None
    measured = ns / len(chips) / 1e9
    try:
        ops, nbytes = cost_fn(*totals, ctx["hf"])
    except KeyError:    # a configuration without the fields this cost reads
        return None
    least, bound = roofline_seconds(ops, nbytes, ctx["peak"])
    ctx["log"](f"roofline: {'+'.join(nodes)} inside {inside}: "
               f"{dict(zip(sums, totals))} over {len(held)} "
               f"{'/'.join(spans)} spans, least {least:.6f}s "
               f"({bound}-bound), measured {measured:.6f}s")
    return 100.0 * least / measured
