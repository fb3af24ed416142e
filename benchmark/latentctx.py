#!/usr/bin/env python3
"""Hold a LATENT cache to the reference past the original context of its
YaRN scaling, at the published widths.

    python benchmark/latentctx.py --config <name> --seeds 1,2,3 [--routing]

``check.drive``'s long sequence ends at 4 085 positions, under
``rope_scaling.original_max_position_embeddings`` (4 096) and under a third
of what the cell serves (contexts 6k-15k): ``run.py``'s own check meets the
blended frequencies at every position, but never an angle the unscaled
rotary would not have reached, nor more than eight blocks of the latent
kernel.  This drive is for that.  Per seed, two sequences in two slots, each
prefilled by the tiled prefill scan (as ``check._prefill_scan`` feeds a
prompt: the two planes' block writes, the absorbed tile path over the cache's
prefixes): one of ``LONG`` tokens (9 207: eighteen chunks, five of the eight
cache prefixes a chunk may be cut to, eighteen blocks of 512 for the decode
kernel), one ``SHORT`` positions short of the original context (4 075).  Both
then decode together as ``boundary.drive`` decodes its rows: two chained
``decode_scan_async`` segments of 32 steps (no readback between them) — the
second row crosses position 4 096 inside the scan — then
``check.TAIL_STEPS`` flat decode steps on both rows: their logits read what
the DECODE path wrote into the latent cache.

The reference computes the full forward pass of prompt + generated tokens
(``check.reference_logits``: the materialised form), the numbers are
``check.compare``'s and the limits the configuration's own
(``benchmark.correct``).  JUDGED are the three maxima (a wrong block, mask,
plane or frequency is off by the logit scale itself); the root mean squares
are printed beside their limits as READINGS: over this drive's 8 flat rows
they are one row's luck.  ``--routing`` adds a READING, no limit: of
``routing.ROWS`` rows through the deployment's own forward pass, the share
whose chosen expert SETS equal the float32 reference's, and the mean overlap.
Exit code 0 when every seed's judged numbers are within their limits.
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
LONG, SHORT = 9207, 21
STEPS = 2 * boundary.SEGMENT    # scanned steps: ``boundary.drive``'s


def sequences(seed, vocab_size, original, max_seq_len):
    """The long prompt (as long as the cache leaves room for, at most
    ``LONG``) and the one that stops ``SHORT`` under the original context."""
    rng = np.random.default_rng([int(seed), 0x1A7E])
    room = max_seq_len - STEPS - check.TAIL_STEPS - 1
    return [rng.integers(FIRST_TOKEN_ID, vocab_size, size=n).tolist()
            for n in (min(LONG, room), original - SHORT)]


def reference_choices(ref, hf, key, dtype, ids):
    """``[[len(ids), k]]`` per ROUTED layer, in layer order: the reference's
    router on the reference's own hidden states (a layer routes on the norm
    of the stream AFTER its attention)."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeded_weights as sw

    g = jax.jit(lambda k: sw.draw_table(k, sw.GLOBAL_ID, ref.GLOBAL, hf,
                                        dtype))(key)
    x = ref.embed(hf, g, jnp.asarray(np.asarray(ids, np.int32)[None]))
    up = lambda a: a.astype(jnp.float32)
    eps = hf.get("rms_norm_eps", 1e-6)

    @jax.jit
    def layer(k, i, x):
        w = sw.draw_table(k, i, ref.LAYER, hf, dtype)
        h = x.h + ref.attention(hf, w, ref.rms_norm(
            x.h, up(w["input_layernorm.weight"]), eps))
        n = ref.rms_norm(h, up(w["post_attention_layernorm.weight"]), eps)
        return ref.layer(hf, w, x), ref.route(hf, w, n)[0]

    out = []
    for i in range(ref.num_layers(hf)):
        x, chosen = layer(key, jnp.int32(i), x)
        if not ref.is_dense(hf, i):
            out.append(np.asarray(chosen)[0])
    return out


def run_latentctx(im, ref, hf, key, dtype, seed, limits, log):
    """Drive, compare, print each number beside its limit; ``(the JUDGED
    numbers within their limits, all numbers)``."""
    original = hf["rope_scaling"]["original_max_position_embeddings"]
    seqs = sequences(seed, hf["vocab_size"], original, im.max_seq_len)
    assert SHORT < STEPS, "the scan crosses the original context"
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
                      "latentctx")
    check._judge({n: v for n, v in numbers.items() if n not in JUDGED},
                 limits, log, "latentctx (a reading)")
    log(f"latentctx: {info['rows']} flat rows and {info['tokens']} produced "
        f"tokens at contexts up to {len(fed[0]) + 1}; prompts "
        f"{[len(s) for s in seqs]} (original context {original}), {STEPS} "
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
    if not (hf.get("rope_scaling") and hf.get("kv_lora_rank")):
        harness.die(f"{args.config} has no latent cache under a scaled "
                    "rotary")
    devices, _ = harness.require_device(dep["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    llm = harness.build(hf, dep, devices)
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",") if s):
        key = harness.seed_weights(llm, ref, hf, seed, dep["precision"])
        ok, numbers = run_latentctx(llm.im, ref, hf, key, dep["precision"],
                                    seed, dep["correct"], print)
        line = {"config": args.config, "drive": "latentctx", "seed": seed,
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
