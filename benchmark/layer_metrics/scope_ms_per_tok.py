"""Device milliseconds of the operations under some scopes, per token that
the program itself says those programs made.

numerator    device time of the ``XLA Ops`` events that started while one of
             ``programs`` was executing and whose scope path (xplane_spans)
             holds one of ``stages`` (``kv_write``, ``qkv_proj``, ...) as its
             innermost stage, or a graph node of one of the operator classes
             ``nodes`` (``Linear``); with neither given, the whole device
             time of ``programs`` (the ``XLA Modules`` line)
denominator  the tokens of kinds ``tokens`` (``scan_tokens``, ...) that the
             scheduler's ``commit`` spans in the trace say they appended:
             which program made a token is known where it is committed, not
             from the counters at step boundaries

None where the trace has no ``commit`` span (a program without the spans),
and — for a reading by scope — where no operation of the trace carries a
graph-node scope at all: then the executable is older than the scopes, and
0 would be a lie.
"""

from benchmark import xplane_spans as xs


def read(ctx, programs, tokens, stages=(), nodes=()):
    trace = xs.for_run(ctx)
    xs.log_run(trace, ctx)
    if (stages or nodes) and not xs.has_node_scopes(trace):
        ctx["log"]("no graph-node scope in the trace: stale executable from "
                   "the compile cache?")
        return None
    made = xs.committed_tokens(trace, tokens)
    if not made:
        return None
    chips = range(len(trace.device_planes()))
    if stages or nodes:
        ns = 0.0
        for c in chips:
            for o in xs.ops_in_programs(trace, set(programs), c):
                node, stage = xs.scope_of(o)
                if stage in stages or (
                        node and node.split(".", 1)[0] in nodes):
                    ns += o.dur_ns
    else:
        ns = sum(d for c in chips for n, _, d in trace.programs(c)
                 if n in programs)
    if ns <= 0:
        return None
    return ns / len(chips) / 1e6 / made
