"""Time ``prefill_attention`` ALONE on the chip, in its forms.

    chiprun -- python scripts/prefill_kernel_bench.py [--shapes long-prompt,...]
                                                      [--tree .parent]

For each shape (a 512-row chunk of a cell's tiled prefill scan: four tiles of
128 queries at the listed first positions, each on a cache row of its own)
the kernel runs in an on-device ``fori_loop`` of two lengths and the slope
between them is one call's time (``decode_kernel_bench.one_call_s``), in five
forms that share the tree's plan, grid and BlockSpecs:

- ``parent``: the kernel as it stood before PR 60
  (``tests/prefill_kernel_forms.py``): float32 copies of q, K and V into both
  contractions, the mask on every live block, the running max and sum read
  as one lane of their scratch and broadcast back;
- ``operands``: ``parent`` with the cache's own operands;
- ``replicated``: ``parent`` with the statistics kept in every lane;
- ``kernel``: the tree's ``_prefill_kernel`` as it is (all three, and the
  mask only on a tile's diagonal);
- ``copies``: no body — the grid, its index maps and its copies.

One JSON line a shape (also ``chiprun_out/prefill_kernel_bench.jsonl``):
microseconds a call, and a LIVE grid step's (a form's call less the
``copies`` call, over the live steps, plus a step's share of ``copies``), and
the least time the call could take by the yardstick's own count
(``benchmark/costs.prefill_attention_cost`` a tile: K and V up to the
frontier once, q in, o out; the operations under the mask) on the v5e's
published peaks (``benchmark/peaks.json``).  ``--tree DIR`` times that tree's
own kernel as ``tree_us`` (a ``git archive`` of another commit unpacked
inside the repository).  A number from a CPU run is no device time: the
script refuses to run off the TPU unless ``--cpu`` (tiny shapes, interpret
mode).
"""

import argparse
import functools
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests"),
                os.path.join(ROOT, "scripts")]

import jax
import jax.numpy as jnp
import numpy as np

import prefill_kernel_forms as forms
from benchmark import costs
from decode_kernel_bench import one_call_s
from flexflow_tpu.ops.pallas import attention

with open(os.path.join(ROOT, "benchmark", "peaks.json")) as _fh:
    PEAK = json.load(_fh)["TPU v5 lite"]

# name: (cache rows, kv heads, q per kv, head, cache seq, tile,
#        first position of each tile)
SHAPES = {
    # opt-6.7b-d12.long-prompt: prompts of 1024-1900 in a cache of 2048
    "long-prompt": (9, 32, 1, 128, 2048, 128, [0, 640, 1408, 1792]),
    # starcoderbase-3b.code-complete: multi-query, prompts of 64-6144
    # (median 1024) in a cache of 8192
    "code-complete": (17, 1, 22, 128, 8192, 128, [0, 896, 1920, 4992]),
}


def tree_attention_module(tree):
    """``flexflow_tpu/ops/pallas/attention.py`` of another checkout, loaded
    beside this tree's (it imports nothing of its own package)."""
    path = os.path.join(tree, "flexflow_tpu", "ops", "pallas", "attention.py")
    spec = importlib.util.spec_from_file_location("attention_of_tree", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def bench_shape(name, interpret, seed, tree):
    rows_n, kv, gq, d, s_len, tile, pstart = SHAPES[name]
    if interpret:  # a rehearsal: a short cache, two heads
        kv, s_len = min(kv, 2), 1024
        pstart = [p % s_len for p in pstart]
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.bfloat16)
    kc, vc = draw(rows_n, kv, s_len, d), draw(rows_n, kv, s_len, d)
    g = len(pstart)
    q = draw(g, tile, kv * gq, d)
    rows = jnp.arange(g, dtype=jnp.int32)
    args = (q, kc, vc, rows, jnp.asarray(pstart, jnp.int32))
    kw = dict(scale=d ** -0.5, interpret=interpret)
    kc_plan, block = attention._prefill_plan(kv, d, 2, 2, False, tile * gq,
                                             512, s_len)
    steps = g * (kv // kc_plan) * (s_len // block)
    live_blocks = sum((p + tile - 1) // block + 1 for p in pstart)
    whole_blocks = sum(max(0, (p + 1) // block) for p in pstart)
    live = live_blocks * (kv // kc_plan)
    ops, nbytes = map(sum, zip(*(
        costs.prefill_attention_cost(tile, p, kv * gq, kv, d)
        for p in pstart)))
    readings = {
        "parent_us": functools.partial(forms.prefill_attention_with,
                                       forms.PARENT, **kw),
        "operands_us": functools.partial(forms.prefill_attention_with,
                                         forms.NATIVE_MASKED, **kw),
        "replicated_us": functools.partial(forms.prefill_attention_with,
                                           forms.REPLICATED, **kw),
        "kernel_us": functools.partial(
            attention.prefill_attention.__wrapped__, **kw),
        "copies_us": functools.partial(forms.prefill_attention_with,
                                       forms.no_body_kernel, **kw),
    }
    if tree:
        readings["tree_us"] = functools.partial(
            tree_attention_module(tree).prefill_attention.__wrapped__, **kw)
    line = dict(shape=name, plan=[kc_plan, block], grid_steps=steps,
                live_steps=live, whole_blocks=whole_blocks,
                masked_blocks=live_blocks - whole_blocks)
    for key, f in readings.items():
        line[key] = round(1e6 * one_call_s(
            f, args, lengths=(1, 2) if interpret else (8, 40)), 2)
    bytes_us = 1e6 * nbytes / PEAK["hbm_bytes_per_s"]
    flops_us = 1e6 * ops / PEAK["flops_bf16"]
    line.update(
        least_bytes_us=round(bytes_us, 2), least_flops_us=round(flops_us, 2),
        **{k.replace("_us", "_live_step_us"):
           round((line[k] - line["copies_us"]) / live
                 + line["copies_us"] / steps, 3)
           for k in readings if k != "copies_us"},
        roofline_pct=round(max(bytes_us, flops_us) / line["kernel_us"] * 100,
                           1),
        parent_roofline_pct=round(
            max(bytes_us, flops_us) / line["parent_us"] * 100, 1),
        device=jax.devices()[0].device_kind)
    print(json.dumps(line), flush=True)
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tree", default="",
                    help="also time another checkout's kernel (tree_us)")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse in interpret mode (no device time)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        raise SystemExit("prefill_kernel_bench times the chip: no TPU here")
    lines = [bench_shape(name, args.cpu, args.seed, args.tree)
             for name in args.shapes.split(",")]
    if args.cpu:   # a rehearsal's numbers are no device times: not kept
        return
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/prefill_kernel_bench.jsonl", "a") as fh:
        for line in lines:
            fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
