#!/usr/bin/env python3
"""Hold a cache that COMPACTS to the reference where the decode scan does the
compacting, at the published widths.

    python benchmark/boundary.py --config <name> --seeds 1,2,3

``check.drive``'s long sequence stops a few positions short of a window's end
(4064 tokens + 21 decode positions against windows of 2048), so no decode step
of the check closes a window: what it reads are summaries the PREFILL path
made.  This drive is for the other half.  Per seed, two sequences in two
slots, each prefilled by the tiled prefill scan (as ``check._prefill_scan``
feeds a prompt) to a few positions short of a window's end — ``SHORT`` before
the ends of the configuration's second and third windows, so 4087 and 6123
tokens at a window of 2048 — then decoded together across those ends by two
chained ``decode_scan_async`` segments of ``SEGMENT`` steps (no readback
between them, as the scheduler chains a stretch), then ``check.TAIL_STEPS``
flat decode steps on both rows: their logits read, behind the boundary, the
summaries the DECODE scan's compaction left.  The reference computes the full
forward pass of prompt + generated tokens (``check.reference_logits``), the
numbers are ``check.compare``'s and the limits the configuration's own
(``benchmark.correct``): the same numbers, the same limits as ``correct``.
Exit code 0 when every seed is within them.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from benchmark import check, run as harness  # noqa: E402
from benchmark.traffic_gen import FIRST_TOKEN_ID  # noqa: E402

SHORT = (9, 21)    # positions each sequence stops short of its window's end
SEGMENT = 32       # steps of each of the two chained scan segments


def sequences(seed, vocab_size, window):
    rng = np.random.default_rng([int(seed), 0xB0DA])
    return [rng.integers(FIRST_TOKEN_ID, vocab_size,
                         size=(i + 2) * window - short).tolist()
            for i, short in enumerate(SHORT)]


def drive(im, seqs):
    """``check.drive``'s ``(rows, gen)`` for this drive's sequences."""
    from flexflow_tpu.serve.batch_config import BatchConfig

    cap, nreq = im.max_tokens, im.max_requests
    slots = list(range(len(seqs)))
    seq = np.zeros(nreq, np.int32)
    gen = [[check._prefill_scan(im, s, seqs[s], seq)] for s in slots]
    depth = {s: len(seqs[s]) + 1 for s in slots}   # device-side cache depth
    for s in slots:
        seq[s] = depth[s]
    bc = BatchConfig.build([gen[s][0] for s in slots], slots,
                           [len(seqs[s]) for s in slots], seq,
                           max_tokens=cap, max_requests=nreq)
    budget, scans = 2 * SEGMENT + check.TAIL_STEPS, []
    for _ in range(2):
        allowed = np.zeros(cap, np.int32)
        allowed[:len(slots)] = budget
        toks, live, _, bc = im.decode_scan_async(
            bc, SEGMENT, eos=None, sample=None, allowed=allowed,
            max_position=max(depth.values()) - 1,
            counts={"rows": len(slots)})
        scans.append((toks, live))
        budget -= SEGMENT
        for s in slots:
            depth[s] += SEGMENT
    for toks, live in scans:       # the stretch's single readback
        toks, live = np.asarray(toks), np.asarray(live)
        assert live[:, :len(slots)].all(), "a row froze inside its budget"
        for s in slots:
            gen[s] += [int(t) for t in toks[:, s]]
    lazy = []
    for _ in range(check.TAIL_STEPS):
        pos = [depth[s] - 1 for s in slots]
        for s in slots:
            depth[s] += 1
            seq[s] = depth[s]
        bc = BatchConfig.build([gen[s][-1] for s in slots], slots, pos, seq,
                               max_tokens=cap, max_requests=nreq)
        res = im.step(bc)
        got = np.asarray(res.token_ids)
        for s in slots:
            lazy.append((s, pos[s], res, s))
            gen[s].append(int(got[s]))
    rows = []
    for s, p, res, at in lazy:
        lm = np.asarray(res.logits_max).astype(np.float64)
        tk = np.asarray(res.topk_logprobs).astype(np.float64)
        rows.append((s, p, lm[at], tk[at], True))
    return rows, gen


def run_boundary(im, ref, hf, key, dtype, seed, limits, log):
    """Drive, compare, print each number beside its limit; ``(within limits,
    numbers)``."""
    window = hf["window_size"]
    seqs = sequences(seed, hf["vocab_size"], window)
    for ids, short in zip(seqs, SHORT):
        crossed = (len(ids) + 1 + short) % window == 1
        assert crossed and short < 2 * SEGMENT, "the scan must cross the end"
    rows, gen = drive(im, seqs)
    wanted = []
    for s in range(len(seqs)):
        need = {p for seq_i, p, *_ in rows if seq_i == s}
        need |= {len(seqs[s]) - 1 + k for k in range(len(gen[s]))}
        wanted.append(sorted(need))
    logits = check.reference_logits(
        ref, hf, key, dtype, [p + g[:-1] for p, g in zip(seqs, gen)], wanted)
    numbers, info = check.compare(rows, gen, seqs, logits, wanted, im.topk)
    ok = check._judge(numbers, limits, log, "boundary")
    log(f"boundary: {info['rows']} flat rows and {info['tokens']} produced "
        f"tokens behind the ends of windows "
        f"{[len(s) // window for s in seqs]}, crossed by the decode scan at "
        f"steps {list(SHORT)}; logit scale {info['logit_scale']:.3f}, "
        f"{'within' if ok else 'OUTSIDE'} limits")
    return ok, numbers


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    hf, dep, ref = harness.load_config(harness.ROOT, bench, args.config)
    if "window_size" not in hf:
        harness.die(f"{args.config} has no window_size: no cache of its "
                    "compacts")
    devices, _ = harness.require_device(dep["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    llm = harness.build(hf, dep, devices)
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",") if s):
        key = harness.seed_weights(llm, ref, hf, seed, dep["precision"])
        ok, numbers = run_boundary(llm.im, ref, hf, key, dep["precision"],
                                   seed, dep["correct"], print)
        print(json.dumps({"config": args.config, "drive": "boundary",
                          "seed": seed, "within_limits": ok, **numbers}),
              flush=True)
        all_ok = all_ok and ok
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
