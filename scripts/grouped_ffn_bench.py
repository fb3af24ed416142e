"""Time a routed layer's grouped GEMMs ALONE on the chip, by how the rows
fall on the experts.

    chiprun -- python scripts/grouped_ffn_bench.py [--tm 128,256,512]

``mellum2-d8``'s prompt chunk: 8192 sorted pairs ``[8192, 2304]`` bf16 on 64
held experts of ``2304 x 896`` (gate, up) and ``896 x 2304`` (down).  Four
loads: ``one`` — ONE group holds every row (no group ever changes: a step's
own pace); ``balanced`` — 64 groups of 128; ``skew`` — 36 visited experts,
the fullest 900 rows, 8076 pairs (a chunk and layer of the timed cell under
its seeded draw); ``drawn`` — every pair's expert drawn uniformly (a trained
router's load: 64 groups of 128 on average, none ending on a tile).  For
each, microseconds a call (the slope between two on-device loop lengths) of:

- ``gmm_up`` / ``gmm_down``: megablox's ``gmm`` at ``MoEExperts.out_tile``'s
  tiles, float32 out — one of today's three calls;
- ``megablox_layer``: the three calls and the XLA product between them
  (``MoEExperts.lower`` as it stood);
- ``ahead_up_tm<T>``: ``grouped_ffn`` in its ``linear`` form at the up
  projection's shapes, float32 out — megablox's work with the weights
  fetched a group ahead;
- ``ffn_in_tm<T>``: gate and up in one call with the product inside, bf16
  out; ``ffn_out_tm<T>``: the down projection; ``grouped_layer_tm<T>``: both.

One JSON line a load (also ``chiprun_out/grouped_ffn_bench.jsonl``) with the
grid steps each row tile gives and the least time by bytes and by arithmetic
(``benchmark/peaks.json``).  Refuses to run off the TPU unless ``--cpu``
(tiny shapes, interpret mode: no device time).
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

from flexflow_tpu.ops.pallas.grouped_ffn import grouped_ffn
from flexflow_tpu.serve.ssd_moe_ops import GMM_ROWS, MoEExperts

with open(os.path.join(ROOT, "benchmark", "peaks.json")) as _fh:
    PEAK = json.load(_fh)["TPU v5 lite"]


def loads(m, e, rng):
    """Rows per expert of the four loads (see the module's docstring)."""
    one = np.zeros(e, np.int64)
    one[0] = m
    balanced = np.full(e, m // e)
    visited, fullest, pairs = (e * 36) // 64, (m * 900) // 8192, \
        (m * 8076) // 8192
    rest = rng.dirichlet(np.full(visited - 1, 2.0)) * (pairs - fullest)
    rest = np.minimum(np.floor(rest).astype(np.int64), fullest - 1)
    rest[np.argmin(rest)] += pairs - fullest - rest.sum()
    skew = np.zeros(e, np.int64)
    at = np.sort(rng.choice(e, visited, replace=False))
    skew[at] = rng.permutation(np.concatenate([[fullest], rest]))
    drawn = np.bincount(rng.integers(0, e, m), minlength=e)
    return {"one": one, "balanced": balanced, "skew": skew, "drawn": drawn}


def one_call_s(f, args, lengths=(8, 40), repeats=3):
    """Seconds a call ``f(*args, sizes)`` takes on the device: the slope
    between two on-device loop lengths, the least of ``repeats`` readings
    each; a call's sizes depend on the call before (nothing else is added to
    the operands: a pass over 37 MB of rows would be a sixth of a call)."""
    *arrays, sizes = args

    def loop(n):
        @jax.jit
        def run(sizes, *arrays):
            def body(_, c):
                out = f(*arrays, sizes + c)
                return (out.ravel()[0] > 1e30).astype(jnp.int32)
            return jax.lax.fori_loop(0, n, body, jnp.int32(0))
        run(sizes, *arrays).block_until_ready()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run(sizes, *arrays).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best
    lo, hi = (loop(n) for n in lengths)
    return (hi - lo) / (lengths[1] - lengths[0])


def steps(sizes, tm):
    """Grid steps: each row tile once by every group with a row in it."""
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return int(sum(-(-b // tm) - a // tm
                   for a, b, s in zip(starts, ends, sizes) if s))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tm", default="128,256,512")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse in interpret mode (no device time)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        raise SystemExit("grouped_ffn_bench times the chip: no TPU here")
    interp = args.cpu
    m, d, f, e = (512, 128, 256, 8) if interp else (8192, 2304, 896, 64)
    tms = [int(t) for t in args.tm.split(",")]
    rng = np.random.default_rng(args.seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.05,
                                      jnp.bfloat16)
    x, gate, up, down = draw(m, d), draw(e, d, f), draw(e, d, f), \
        draw(e, f, d)
    h16 = draw(m, f)
    hidden_tile = MoEExperts.out_tile(d, f, 2)
    model_tile = MoEExperts.out_tile(f, d, 2)
    mega = lambda a, w, s, t: gmm(a, w, s, jnp.float32,
                                  (GMM_ROWS, w.shape[1], t), interpret=interp)

    def megablox_layer(x, gate, up, down, s):
        h = jax.nn.silu(mega(x, gate, s, hidden_tile)) \
            * mega(x, up, s, hidden_tile)
        return mega(h.astype(x.dtype), down, s, model_tile)

    def grouped_layer(ffn, x, gate, up, down, s):
        h = ffn(x, (gate, up), s, form="swiglu", out_dtype=x.dtype)
        return ffn(h, (down,), s, form="linear", out_dtype=jnp.float32)

    lengths = (1, 2) if interp else (8, 40)
    out = []
    for name, sizes in loads(m, e, rng).items():
        s = jnp.asarray(sizes, jnp.int32)
        pairs, visited = int(sizes.sum()), int((sizes > 0).sum())
        nbytes = visited * 3 * d * f * 2 + pairs * (d * 2 + d * 4)
        line = dict(load=name, pairs=pairs, visited=visited,
                    fullest=int(sizes.max()),
                    steps={tm: steps(sizes, tm) for tm in tms},
                    least_bytes_us=round(1e6 * nbytes
                                         / PEAK["hbm_bytes_per_s"], 1),
                    least_flops_us=round(1e6 * 6 * pairs * d * f
                                         / PEAK["flops_bf16"], 1))
        readings = {
            "gmm_up": (lambda x, w, s: mega(x, w, s, hidden_tile),
                       (x, up, s)),
            "gmm_down": (lambda h, w, s: mega(h, w, s, model_tile),
                         (h16, down, s)),
            "megablox_layer": (megablox_layer, (x, gate, up, down, s)),
        }
        for tm in tms:
            ffn = functools.partial(grouped_ffn, tm=tm, interpret=interp)
            readings[f"ahead_up_tm{tm}"] = (
                lambda x, w, s, ffn=ffn: ffn(x, (w,), s, form="linear",
                                             out_dtype=jnp.float32),
                (x, up, s))
            readings[f"ffn_in_tm{tm}"] = (
                lambda x, g, u, s, ffn=ffn: ffn(x, (g, u), s, form="swiglu",
                                                out_dtype=x.dtype),
                (x, gate, up, s))
            readings[f"ffn_out_tm{tm}"] = (
                lambda h, w, s, ffn=ffn: ffn(h, (w,), s, form="linear",
                                             out_dtype=jnp.float32),
                (h16, down, s))
            readings[f"grouped_layer_tm{tm}"] = (
                functools.partial(grouped_layer, ffn), (x, gate, up, down, s))
        for key, (fn, operands) in readings.items():
            line[key + "_us"] = round(
                1e6 * one_call_s(fn, operands, lengths=lengths), 1)
        line["device"] = jax.devices()[0].device_kind
        print(json.dumps(line), flush=True)
        out.append(line)
    if interp:   # a rehearsal's numbers are no device times: not kept
        return
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/grouped_ffn_bench.jsonl", "a") as fh:
        for line in out:
            fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
