"""A decode kernel's share of its roofline inside the decode scan, in percent.

least seconds / measured seconds, where
  measured = summed device time of the kernel's events (``op``: the Pallas
             function's name, which its custom-call instruction carries)
             that started while one of the programs ``inside`` was
             executing.  The same kernel also runs in flat steps, where each
             prompt token is a row: those events are another matter and are
             left out;
  least    = the larger of operations / peak FLOP/s and bytes / peak
             bytes/s (costs.py) for the decode rows those programs
             computed.  The host sees which tokens were generated in the
             span (every token but a request's first is one kernel row per
             layer, reading its request's live prefix) but not which
             program made each: a flat step (``flat``) advances every
             decoding row by one token too.  So the rows with the longest
             contexts, as many as the flat steps of the span can have made
             (their count x the slots), are left out of the least work.
The share is therefore a LOWER bound, close where flat steps are few.  It
cannot pass 100 by construction of the counts; a reading above it is a bug
here or in costs.py.
"""

from benchmark.costs import decode_attention_cost, roofline_seconds
from benchmark.trace_reduce import op_seconds_inside, program_seconds


def read(ctx, op, inside, flat):
    lens, chips = ctx["clock"].trace_lens, ctx["reduced"]["chips"]
    if lens is None or not chips:
        return None
    before, after = lens
    contexts = []
    for rid, (prompt, gen1) in after.items():
        gen0 = before.get(rid, (prompt, 0))[1]
        # token number g (0-based) attends prompt + g positions, itself
        # included; g = 0 is the prefill's
        contexts += [prompt + g for g in range(max(gen0, 1), gen1)]
    measured = sum(op_seconds_inside(c, op, set(inside))[0]
                   for c in chips) / len(chips)
    flat_steps = program_seconds(chips[0], set(flat))[1]
    cfg = ctx["llm"].config
    slots = ctx["dep"]["compile"]["max_requests"]
    contexts = sorted(contexts)[:max(len(contexts) - flat_steps * slots, 0)]
    if not contexts or measured <= 0:
        return None
    tp = len(chips)
    ops, nbytes = decode_attention_cost(
        contexts, cfg.num_attention_heads // tp, max(cfg.kv_heads // tp, 1),
        cfg.hdim)
    least, bound = roofline_seconds(cfg.num_hidden_layers * ops,
                                    cfg.num_hidden_layers * nbytes,
                                    ctx["peak"])
    ctx["log"](f"roofline: {op} inside {inside}: {len(contexts)} decode rows "
               f"({flat_steps} flat steps' worth left out), least "
               f"{least:.6f}s ({bound}-bound), measured {measured:.6f}s")
    return 100.0 * least / measured
