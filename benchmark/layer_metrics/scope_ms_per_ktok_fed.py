"""Device milliseconds of the operations under some scopes, per 1000 PROMPT
tokens that the launches of those programs say they fed.

numerator    ``scope_ms_per_tok.py``'s: device time of the ``XLA Ops`` events
             that started while one of ``programs`` was executing and whose
             scope path (xplane_spans) holds one of ``stages`` as its
             innermost stage, or a graph node of one of the operator classes
             ``nodes``
denominator  the ``prompt_tokens`` of the dispatch spans ``launches``
             (``prefill_scan_dispatch``, ``step_dispatch``) in the trace: the
             prompt rows those launches fed, as the scheduler counted them at
             the launch (a pad scan carries none), in thousands

None where the trace has no such dispatch span or they fed nothing, where no
operation of the trace carries a graph-node scope at all (a stale
executable), and where no such operation ran.
"""

from benchmark import xplane_spans as xs


def read(ctx, programs, launches, stages=(), nodes=()):
    trace = xs.for_run(ctx)
    xs.log_run(trace, ctx)
    if not xs.has_node_scopes(trace):
        ctx["log"]("no graph-node scope in the trace: stale executable from "
                   "the compile cache?")
        return None
    fed = sum(int(h.args.get("prompt_tokens") or 0)
              for h in xs.program_spans(trace) if h.name in launches)
    if not fed:
        return None
    chips = range(len(trace.device_planes()))
    ns = 0.0
    for c in chips:
        for o in xs.ops_in_programs(trace, set(programs), c):
            node, stage = xs.scope_of(o)
            if stage in stages or (node and node.split(".", 1)[0] in nodes):
                ns += o.dur_ns
    if ns <= 0:
        return None
    return ns / len(chips) / 1e6 / (fed / 1e3)
