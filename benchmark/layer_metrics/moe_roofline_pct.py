"""The roofline share, in percent, of the routed experts' grouped GEMMs
inside the decode scan, from the routing's OWN counts.

  measured = summed device time of the ``XLA Ops`` events that started while
             one of the programs ``inside`` was executing and whose innermost
             graph-node scope (xplane_spans) is of one of the operator
             classes ``nodes``;
  least    = the larger of operations / peak FLOP/s and bytes / peak bytes/s
             (``cost``: ``"<module of benchmark/>:<function>"``, called with
             the visited (expert, layer, step) triples, the pairs and the
             configuration's published fields) for the decode rows of the
             traced span.

The counts are the program's: every ``commit`` span of the trace carries
``experts_visited``, ``expert_pairs`` and ``expert_steps`` (decode-scan steps
x routed layers), counted on the device in the scan and read back with its
tokens, beside ``scan_tokens``.  A commit speaks of whole stretches, the
span may hold a part of one at either end; so the commits' sums are scaled
by the share of their ``scan_tokens`` that the span's decode rows are — the
rows counted as ``node_cost_roofline_pct.py`` counts them: every generated
token of the traced span but a request's first is one row (the window's
clock keeps the lengths).  None where no commit carries the counts (a
program without them), the clock kept no lengths, the trace holds no such
operation, or the cost module is not there.
"""

import importlib

from benchmark import xplane_spans as xs
from benchmark.costs import roofline_seconds


def read(ctx, nodes, cost, inside):
    lens = ctx["clock"].trace_lens
    if lens is None:
        return None
    module, _, function = cost.partition(":")
    try:
        cost_fn = getattr(importlib.import_module("benchmark." + module),
                          function)
    except (ImportError, AttributeError):
        return None
    trace = xs.for_run(ctx)
    commits = [h for h in xs.program_spans(trace) if h.name == "commit"]
    total = lambda k: sum(int(h.args.get(k) or 0) for h in commits)
    steps, tokens = total("expert_steps"), total("scan_tokens")
    if not steps or not tokens:
        return None
    ns = 0.0
    chips = range(len(trace.device_planes()))
    for c in chips:
        for o in xs.ops_in_programs(trace, set(inside), c):
            node, _ = xs.scope_of(o)
            if node and node.split(".", 1)[0] in nodes:
                ns += o.dur_ns
    before, after = lens
    rows = sum(max(gen1 - max(before.get(rid, (prompt, 0))[1], 1), 0)
               for rid, (prompt, gen1) in after.items())
    if ns <= 0 or not rows:
        return None
    measured = ns / len(chips) / 1e9
    share = rows / tokens
    try:
        ops, nbytes = cost_fn(total("experts_visited") * share,
                              total("expert_pairs") * share, ctx["hf"])
    except KeyError:    # a configuration without the fields this cost reads
        return None
    least, bound = roofline_seconds(ops, nbytes, ctx["peak"])
    ctx["log"](f"roofline: {'+'.join(nodes)} inside {inside}: {rows} decode "
               f"rows = {share:.4f} of the commits' {tokens} scan tokens "
               f"({steps} routed layer-steps, "
               f"{total('experts_visited') / steps:.2f} experts and "
               f"{total('expert_pairs') / steps:.1f} pairs each), least "
               f"{least:.6f}s ({bound}-bound), measured {measured:.6f}s")
    return 100.0 * least / measured
