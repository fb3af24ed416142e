"""Time the decode scan's K/V row write ALONE on the chip: the chain of
one-row ``dynamic_update_slice`` operations against ``kv_row_write``.

    chiprun -- python scripts/kv_write_bench.py [--shapes rag-ring,opt,...]

For each shape (a cell's decode step: both caches of one layer, one fresh
row a slot) the write runs in an on-device ``fori_loop`` of two lengths with
the caches as the donated carry, the positions one further each turn as the
scan's are, and the slope between the lengths is one layer's write (host
clock around ``block_until_ready``).  Prints one JSON line a shape and
writes them all to ``chiprun_out/kv_write_bench.jsonl``.  A number from a CPU
run is no device time: the script refuses to run off the TPU unless
``--cpu`` (tiny shapes, interpret mode, to rehearse the control flow).
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ops.pallas.attention import kv_row_write
from flexflow_tpu.serve.ops import (SCAN_DUS_MAX_ROWS,
                                    IncMultiHeadSelfAttention)

# name: (slots, kv heads, head, cache seq, cache type) — the cells' decode
# scans, from their programs (PERF.md section 4)
SHAPES = {
    "rag-ring": (128, 1, 128, 4608, "bfloat16"),
    "rag-full": (128, 1, 128, 18432, "bfloat16"),
    "doc-latent": (64, 1, 512, 16384, "bfloat16"),
    "agent-decode": (256, 2, 128, 8192, "bfloat16"),
    "longctx-reason": (48, 2, 128, 32768, "bfloat16"),
    "reason-long": (32, 10, 128, 8192, "bfloat16"),
    "byte-longform": (16, 32, 128, 4096, "bfloat16"),
    "decode-heavy": (8, 32, 128, 2048, "bfloat16"),
    "code-complete": (16, 1, 128, 8192, "bfloat16"),
}


def one_call_s(write, kc, vc, k, v, rows, pos, lengths=(16, 80), repeats=3):
    """Seconds one ``write`` takes: the slope between two loop lengths."""
    def loop(n):
        def run(kc, vc, k, v, rows, pos):
            def body(i, c):
                return write(c[0], c[1], k, v, rows, pos + i)
            return jax.lax.fori_loop(0, n, body, (kc, vc))
        return jax.jit(run, donate_argnums=(0, 1))

    took = {}
    for n in lengths:
        f = loop(n)
        kc, vc = jax.block_until_ready(f(kc, vc, k, v, rows, pos))
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            kc, vc = jax.block_until_ready(f(kc, vc, k, v, rows, pos))
            best = min(best, time.perf_counter() - t0)
        took[n] = best
    lo, hi = lengths
    return (took[hi] - took[lo]) / (hi - lo), kc, vc


def bench_shape(name, interpret, seed):
    slots, kv, d, s, dt = SHAPES[name]
    if interpret:
        slots, s = min(slots, 4), 256
    rng = np.random.default_rng(seed)
    kc = jnp.zeros((slots + 1, kv, s, d), dt)
    vc = jnp.zeros((slots + 1, kv, s, d), dt)
    k = jnp.asarray(rng.normal(size=(slots, kv, d)), dt)
    v = jnp.asarray(rng.normal(size=(slots, kv, d)), dt)
    rows = jnp.arange(slots, dtype=jnp.int32)
    pos = jnp.asarray(rng.integers(0, s - 128, size=slots), jnp.int32)
    put = IncMultiHeadSelfAttention._scatter_rows_pos

    def chain(kc, vc, k, v, rows, pos):
        return (put(kc, rows, pos, k, SCAN_DUS_MAX_ROWS),
                put(vc, rows, pos, v, SCAN_DUS_MAX_ROWS))

    kernel = functools.partial(kv_row_write, interpret=interpret)
    chain_s, kc, vc = one_call_s(chain, kc, vc, k, v, rows, pos)
    kernel_s, kc, vc = one_call_s(kernel, kc, vc, k, v, rows, pos)
    return {"shape": name, "slots": slots, "kv_heads": kv, "head": d,
            "seq": s, "dtype": dt, "chain_us": chain_s * 1e6,
            "kernel_us": kernel_s * 1e6,
            "chain_us_a_row": chain_s * 1e6 / slots,
            "kernel_us_a_row": kernel_s * 1e6 / slots,
            "device": jax.devices()[0].device_kind}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        sys.exit("no TPU: a CPU run times nothing (--cpu rehearses the "
                 "control flow at tiny shapes)")
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kv_write_bench.jsonl"), "a") as f:
        for name in args.shapes.split(","):
            line = json.dumps(bench_shape(name, args.cpu, args.seed))
            print(line, flush=True)
            f.write(line + "\n")


if __name__ == "__main__":
    main()
