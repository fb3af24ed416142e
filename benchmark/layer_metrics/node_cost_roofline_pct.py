"""The roofline share, in percent, of what some graph-node classes do inside
the decode scan — ``node_roofline_pct.py``'s reading with the cost function
named by its module too (``cost``: ``"<module of benchmark/>:<function>"``,
called with the decode rows' contexts and the configuration's published
fields), for a configuration whose costs are not ``costs_hybrid``'s.

  measured = summed device time of the ``XLA Ops`` events that started while
             one of the programs ``inside`` was executing and whose innermost
             graph-node scope (xplane_spans) is of one of the operator
             classes ``nodes`` — with ``stages`` given, only those whose
             innermost stage scope is one of them;
  least    = the larger of operations / peak FLOP/s and bytes / peak bytes/s
             for the decode rows those programs computed.  The rows are
             counted as ``kernel_roofline_pct.py`` counts them: every
             generated token of the traced span but a request's first is one
             row, and the rows the span's flat steps (``flat``) may have made
             — their count x the slots, the longest contexts first — are
             left out.
So the share is a LOWER bound.  None where the trace holds no such operation
(a program without these node classes), the cost module is not there, or the
window's clock kept no lengths.
"""

import importlib

from benchmark import xplane_spans as xs
from benchmark.costs import roofline_seconds
from benchmark.trace_reduce import program_seconds


def read(ctx, nodes, cost, inside, flat, stages=()):
    lens, chips = ctx["clock"].trace_lens, ctx["reduced"]["chips"]
    if lens is None or not chips:
        return None
    module, _, function = cost.partition(":")
    try:
        cost_fn = getattr(importlib.import_module("benchmark." + module),
                          function)
    except (ImportError, AttributeError):
        return None
    trace = xs.for_run(ctx)
    ns = 0.0
    for c in range(len(trace.device_planes())):
        for o in xs.ops_in_programs(trace, set(inside), c):
            node, stage = xs.scope_of(o)
            if node and node.split(".", 1)[0] in nodes and (
                    not stages or stage in stages):
                ns += o.dur_ns
    measured = ns / len(chips) / 1e9
    before, after = lens
    contexts = []
    for rid, (prompt, gen1) in after.items():
        gen0 = before.get(rid, (prompt, 0))[1]
        contexts += [prompt + g for g in range(max(gen0, 1), gen1)]
    flat_steps = program_seconds(chips[0], set(flat))[1]
    slots = ctx["dep"]["compile"]["max_requests"]
    contexts = sorted(contexts)[:max(len(contexts) - flat_steps * slots, 0)]
    if not contexts or measured <= 0:
        return None
    try:
        ops, nbytes = cost_fn(contexts, ctx["hf"])
    except KeyError:    # a configuration without the fields this cost reads
        return None
    least, bound = roofline_seconds(ops, nbytes, ctx["peak"])
    ctx["log"](f"roofline: {'+'.join(nodes)} inside {inside}: "
               f"{len(contexts)} decode rows ({flat_steps} flat steps' worth "
               f"left out), least {least:.6f}s ({bound}-bound), measured "
               f"{measured:.6f}s")
    return 100.0 * least / measured
