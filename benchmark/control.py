#!/usr/bin/env python3
"""Read the numbers that decide ``correct``, for sound runs and the control.

    python benchmark/control.py --config <name> --seeds 1,2,3 \
        [--controls int8_kv,int8_weights --control-seeds 31,32,33]

One process: builds the configuration's deployment once, then for each seed
draws the weights, runs check.py's comparison and prints its numbers; an
empty ``--seeds`` skips the sound runs.  Each name in ``--controls`` then
switches on one of the program's own paths of the nearest lower precision,
as the configuration's ``controls`` names them —
``{"compile": {"kv_dtype": "int8"}}``: arguments merged into ``LLM.compile``
(int8 KV caches); ``{"quantize_int8": true}``: int8 weights through
``serve/quant.quantize_int8`` — and every seed must come out NOT correct.
The limits in the configuration's file were set from these readings (PERF.md
has them); the timed runs do not run a control.  A toy-size version runs
under ``tests/`` on the CPU.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import check, run as harness  # noqa: E402


def _drop(llm):
    import gc

    import jax

    for leaf in jax.tree.leaves((llm.im.params, llm.im.state)):
        leaf.delete()
    gc.collect()


def read_numbers(hf, dep, ref, seeds, control, devices, log=print):
    """``[(seed, within limits, numbers)]`` for one deployment: the
    configuration's own (``control`` empty) or one of its controls."""
    how = dep["controls"][control] if control else {}
    dep = dict(dep, compile={**dep["compile"], **how.get("compile", {})})
    llm, out = None, []
    for seed in seeds:
        if llm is None:
            llm = harness.build(hf, dep, devices)
        key = harness.seed_weights(llm, ref, hf, seed, dep["precision"])
        if how.get("quantize_int8"):
            from flexflow_tpu.serve.quant import quantize_int8

            quantize_int8(llm.im)
        ok, numbers = check.run_check(
            llm.im, ref, hf, key, dep["precision"], seed, hf["vocab_size"],
            dep["correct"], log)
        out.append((seed, ok, numbers))
        if how.get("quantize_int8"):
            # the quantized tree is another tree: build anew for the next
            _drop(llm)
            llm = None
    if llm is not None:
        _drop(llm)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    hf, dep, ref = harness.load_config(harness.ROOT, bench, args.config)
    devices, _ = harness.require_device(dep["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    as_ints = lambda text: [int(s) for s in text.split(",") if s]
    plan = [("", as_ints(args.seeds))] + [
        (c, as_ints(args.control_seeds))
        for c in args.controls.split(",") if c]
    as_wanted = True
    for which, seeds in plan:
        for seed, ok, numbers in read_numbers(hf, dep, ref, seeds, which,
                                              devices):
            print(json.dumps({"config": args.config, "control": which,
                              "seed": seed, "within_limits": ok, **numbers}),
                  flush=True)
            as_wanted = as_wanted and ok == (not which)
    return 0 if as_wanted else 1


if __name__ == "__main__":
    sys.exit(main())
