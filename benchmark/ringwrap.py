#!/usr/bin/env python3
"""Hold a plain sliding-window RING to the reference where it has wrapped:
past the window and past the ring's end, at the published widths.

    python benchmark/ringwrap.py --config <name> --seeds 1,2,3 [--routing]

``check.drive``'s long sequence ends at 4 085 positions, under
``sliding_window`` (4 096) and under the ring's 4 608 slots: ``run.py``'s own
check never meets a position that has left a window, nor a slot written
twice.  This drive is for that.  Per seed, two sequences in two slots, each
prefilled by the tiled prefill scan (as ``check._prefill_scan`` feeds a
prompt: ``kv_block_write`` into the ring, ``prefill_attention`` with the
window's lower bound): one to ``SHORT[0]`` positions short of TWO rings
(2 x 4 608 - 9 = 9 207 tokens: every chunk after the ninth overwrites slots,
every tile after the 32nd skips blocks before its window), one to ``SHORT[1]``
positions short of the window itself (4 075).  Both then decode together as
``boundary.drive`` decodes its rows: two chained ``decode_scan_async``
segments of 32 steps (no readback between them, as the scheduler chains a
stretch) — the first row crosses the ring's end (slot 4 607 -> 0) inside the
scan, the second crosses the window (from seeing everything to dropping its
oldest position) — then ``check.TAIL_STEPS`` flat decode steps on both rows:
their logits read what the DECODE path wrote into the rings and the full
cache.

The reference computes the full forward pass of prompt + generated tokens
(``check.reference_logits``), the numbers are ``check.compare``'s and the
limits the configuration's own (``benchmark.correct``).  JUDGED are the three
maxima (``JUDGED``: a wrong slot, mask or block is off by the logit scale
itself); the root mean squares are printed beside their limits as READINGS:
over this drive's 8 flat rows they say whether ONE row holds a flipped
routing choice, not how precise the program is (PERF.md section 2).
``--routing`` adds a READING, no limit (``routing.py``'s count, whose
reference side knows one architecture's names): of ``routing.ROWS`` rows
through the deployment's own forward pass, the share whose chosen expert SETS
equal the float32 reference's, and the mean overlap.  Exit code 0 when every
seed's judged numbers are within their limits.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from benchmark import boundary, check, routing, run as harness  # noqa: E402
from benchmark.traffic_gen import FIRST_TOKEN_ID  # noqa: E402

JUDGED = ("logit_max_ulps", "logprob_max", "token_gap_ulps")
SHORT = (9, 21)    # positions each prompt stops short of its mark
STEPS = 2 * boundary.SEGMENT    # scanned steps: ``boundary.drive``'s


def ring_slots(im):
    """Slots of the deployment's plain rings (their state's seq dim)."""
    lens = {bufs["wk"].shape[2] for bufs in im.state.values()
            if "wk" in bufs}
    assert len(lens) == 1, f"one ring length expected, found {sorted(lens)}"
    return lens.pop()


def sequences(seed, vocab_size, window, ring):
    rng = np.random.default_rng([int(seed), 0x21D6])
    return [rng.integers(FIRST_TOKEN_ID, vocab_size,
                         size=mark - short).tolist()
            for mark, short in zip((2 * ring, window), SHORT)]


def reference_choices(ref, hf, key, dtype, ids):
    """``routing.reference_choices`` for a reference whose every layer
    routes on the layer's own norm: ``[[len(ids), k]]`` per layer."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeded_weights as sw

    g = jax.jit(lambda k: sw.draw_table(k, sw.GLOBAL_ID, ref.GLOBAL, hf,
                                        dtype))(key)
    x = ref.embed(hf, g, jnp.asarray(np.asarray(ids, np.int32)[None]))

    @jax.jit
    def layer(k, i, x):
        w = sw.draw_table(k, i, ref.LAYER, hf, dtype)
        n = ref.layer_norm(
            x.h, w["input_layernorm.weight"].astype(jnp.float32),
            hf.get("layer_norm_eps", 1e-5))
        return ref.layer(hf, w, x), ref.route(hf, w, n)[0]

    out = []
    for i in range(ref.num_layers(hf)):
        x, chosen = layer(key, jnp.int32(i), x)
        out.append(np.asarray(chosen)[0])
    return out


def run_ringwrap(im, ref, hf, key, dtype, seed, limits, log):
    """Drive, compare, print each number beside its limit; ``(the JUDGED
    numbers within their limits, all numbers)``."""
    window, ring = hf["sliding_window"], ring_slots(im)
    seqs = sequences(seed, hf["vocab_size"], window, ring)
    assert max(SHORT) < STEPS, "the scan crosses the marks"
    assert len(seqs[0]) + STEPS + check.TAIL_STEPS + 1 <= im.max_seq_len
    rows, gen = boundary.drive(im, seqs)
    wanted = []
    for s in range(len(seqs)):
        need = {p for seq_i, p, *_ in rows if seq_i == s}
        need |= {len(seqs[s]) - 1 + k for k in range(len(gen[s]))}
        wanted.append(sorted(need))
    fed = [p + g[:-1] for p, g in zip(seqs, gen)]
    logits = check.reference_logits(ref, hf, key, dtype, fed, wanted)
    numbers, info = check.compare(rows, gen, seqs, logits, wanted, im.topk)
    ok = check._judge({n: numbers[n] for n in JUDGED}, limits, log,
                      "ringwrap")
    check._judge({n: v for n, v in numbers.items() if n not in JUDGED},
                 limits, log, "ringwrap (a reading)")
    log(f"ringwrap: {info['rows']} flat rows and {info['tokens']} produced "
        f"tokens at contexts up to {len(fed[0]) + 1}; prompts "
        f"{[len(s) for s in seqs]} (window {window}, ring {ring}), {STEPS} "
        f"scanned steps; logit scale {info['logit_scale']:.3f}, "
        f"{'within' if ok else 'OUTSIDE'} limits")
    return ok, numbers


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--routing", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    hf, dep, ref = harness.load_config(harness.ROOT, bench, args.config)
    if not hf.get("sliding_window"):
        harness.die(f"{args.config} has no sliding_window: no ring wraps")
    devices, _ = harness.require_device(dep["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    llm = harness.build(hf, dep, devices)
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",") if s):
        key = harness.seed_weights(llm, ref, hf, seed, dep["precision"])
        ok, numbers = run_ringwrap(llm.im, ref, hf, key, dep["precision"],
                                   seed, dep["correct"], print)
        line = {"config": args.config, "drive": "ringwrap", "seed": seed,
                "within_limits": ok, **numbers}
        if args.routing:
            rng = np.random.default_rng([seed, 0xF11B])
            ids = rng.integers(4, hf["vocab_size"],
                               size=routing.ROWS).tolist()
            got = routing.program_choices(llm.im, ids)
            want = reference_choices(ref, hf, key, dep["precision"], ids)
            equal, common = routing.agreement(
                [got[n] for n in sorted(
                    got, key=lambda n: int(n.split(".")[2]))], want)
            line.update(routing_equal_share=round(equal, 4),
                        routing_mean_overlap=round(common, 4))
        print(json.dumps(line), flush=True)
        all_ok = all_ok and ok
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
