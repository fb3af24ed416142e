"""Time the delta rule's chunked form ALONE on the chip — the Pallas kernel
``delta_rule_chunk`` against the XLA loop ``KimiDeltaAttention._chunked`` on
the same operands — and read both against the recurrence in float64.

    chiprun -- python scripts/delta_chunk_bench.py [--pieces 16,32,64]
        [--shapes solar,kimi] [--loop 16,32]

``solar``: ``solar-open2-d4-e40``'s prompt chunk, 1024 rows on 64 heads of
128 x 128 float32 (17 state rows); ``kimi``: ``kimi-linear-d5-e32``'s, 512
rows on 32 heads (257 state rows).  Two batches a shape: ``wave`` — ONE
segment fills the chunk and continues a stored state (what nearly every
chunk of a long prompt is); ``shared`` — a chunk three prompts share on
16-row tiles (the end of one, a whole short one, the start of a third) with
pad rows between.  A line a shape and batch: microseconds a call (the slope
between two on-device loop lengths) and a piece and head, by piece size, of
the kernel (``kernel_c<C>``) and of the loop (``loop_c<C>``).

Then, ON THE CHIP, the largest error of both forms against the float64
recurrence, relative to the outputs' (the states') largest entry: ``mixed``
— 8 heads, segments that start on ANY row (a flat step's: windows shifted
inside their sublane tile, one-row pieces, a ragged end) — and ``repeated``
— ``beta`` 1.99 on ONE key through two full pieces and a ragged one, decay
0.999.  Interpret mode cannot show what this does: the CPU multiplies
float32 exactly, the MXU only where the kernel asks for it.

One JSON line each (also ``chiprun_out/delta_chunk_bench.jsonl``).  Refuses
to run off the TPU unless ``--cpu`` (toy shapes, interpret mode: no device
time).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax
import jax.numpy as jnp
import numpy as np
from delta_rule_forms import draw, layout, recurrence64, segments

from flexflow_tpu.ops.pallas.delta_rule import delta_rule_chunk
from flexflow_tpu.serve.hybrid_ops import KimiDeltaAttention


def forms(heads, d, c, interpret):
    """The op at piece ``c`` and ``{name: f(q, k, v, g, beta, kda, seg) ->
    (o, kda)}``."""
    op = KimiDeltaAttention(heads * d, heads, d, chunk=c,
                            allow_neg_eigval=True)

    def kernel(q, k, v, g, beta, kda, seg):
        o, kda = delta_rule_chunk(kda, q, k, v, g, beta, op._pieces(seg),
                                  chunk=c, interpret=interpret)
        return jnp.where(seg.live[:, None, None], o, 0.0), kda

    return op, {"kernel": kernel, "loop": op._chunked}


def one_call_s(f, operands, kda, seg, lengths=(2, 6), repeats=3):
    """Seconds a call takes on the device: the slope between two on-device
    loop lengths, the least of ``repeats`` readings each.  The state is the
    loop's carry (each call continues the one before, as a prompt's chunks
    do) and one output enters it, so that nothing is dropped."""
    def loop(n):
        @jax.jit
        def run(kda, *operands):
            def body(_, kda):
                o, kda = f(*operands, kda, seg)
                return kda.at[-1, 0, 0, 0].add(o[0, 0, 0])
            return jax.lax.fori_loop(0, n, body, kda)
        run(kda, *operands).block_until_ready()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run(kda, *operands).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best
    lo, hi = (loop(n) for n in lengths)
    return (hi - lo) / (lengths[1] - lengths[0])


def batches(rows):
    """The two batches of a chunk of ``rows`` rows (see the module's
    docstring): ``{name: (req, pos)}``."""
    tile = lambda n: -(-n // 16) * 16
    a, b = (rows * 3) // 8 + 5, rows // 4 + 3
    rest = rows - tile(a) - tile(b)
    return {
        "wave": layout([(0, 4096, rows)]),
        "shared": layout([(0, 4096, a), (-1, 0, tile(a) - a), (1, 0, b),
                          (-1, 0, tile(b) - b), (2, 0, rest - 7),
                          (-1, 0, 7)]),
    }


def errors(interpret, rng):
    """Both forms against the float64 recurrence (piece 32)."""
    heads, d = (2, 16) if interpret else (8, 128)
    cases = {
        "mixed": (layout([(-1, 0, 3), (0, 5, 37), (1, 0, 3), (-1, 0, 2),
                          (2, 7, 70), (3, 9, 1), (4, 0, 1), (5, 3, 1),
                          (-1, 0, 5), (6, 64, 21)]), {}),
        "repeated": (layout([(0, 0, 70), (-1, 0, 2)]),
                     dict(keys="repeated", decay=0.999, beta=1.99)),
    }
    for name, ((req, pos), how) in cases.items():
        slots = max(req) + 1
        operands = draw(rng, len(req), heads, d, **how)
        kda = jnp.asarray(rng.standard_normal((slots + 1, heads, d, d)),
                          jnp.float32)
        want_o, want_s = recurrence64(*operands, req, pos, kda)
        line = dict(check=name, rows=len(req), heads=heads)
        for form, f in forms(heads, d, 32, interpret)[1].items():
            o, s = jax.jit(lambda kda, *a, f=f: f(
                *a, kda, segments(req, pos, slots)))(kda, *operands)
            line[f"{form}_o_err"] = float(
                np.abs(np.asarray(o) - want_o).max() / np.abs(want_o).max())
            line[f"{form}_state_err"] = float(
                np.abs(np.asarray(s)[:slots] - want_s[:slots]).max()
                / np.abs(want_s[:slots]).max())
        yield line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pieces", default="16,32,64",
                    help="the kernel's piece sizes")
    ap.add_argument("--loop", default="32", help="the XLA loop's")
    ap.add_argument("--shapes", default="solar,kimi")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse in interpret mode (no device time)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        raise SystemExit("delta_chunk_bench times the chip: no TPU here")
    interp = args.cpu
    shapes = {"solar": (1024, 64, 16), "kimi": (512, 32, 256)}
    if interp:
        shapes = {"solar": (160, 4, 3), "kimi": (128, 2, 3)}
    d = 16 if interp else 128
    rng = np.random.default_rng(args.seed)
    sizes = {"kernel": [int(c) for c in args.pieces.split(",")],
             "loop": [int(c) for c in args.loop.split(",") if c]}
    out = list(errors(interp, rng))
    for line in out:
        print(json.dumps(line), flush=True)
    for shape in args.shapes.split(","):
        rows, heads, slots = shapes[shape]
        operands = draw(rng, rows, heads, d)
        kda = jnp.asarray(rng.standard_normal((slots + 1, heads, d, d)),
                          jnp.float32)
        for batch, (req, pos) in batches(rows).items():
            seg = segments(req, pos, slots)
            line = dict(shape=shape, batch=batch, rows=rows, heads=heads)
            for form, cs in sizes.items():
                for c in cs:
                    op, fs = forms(heads, d, c, interp)
                    f, pieces = fs[form], int(op._pieces(seg)[0])
                    s = one_call_s(f, operands, kda, seg,
                                   lengths=(1, 2) if interp else (2, 6))
                    line[f"{form}_c{c}"] = dict(
                        pieces=pieces, call_us=round(1e6 * s, 1),
                        piece_head_us=round(1e6 * s / (pieces * heads), 3))
            line["device"] = jax.devices()[0].device_kind
            print(json.dumps(line), flush=True)
            out.append(line)
    if interp:   # a rehearsal's numbers are no device times: not kept
        return
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/delta_chunk_bench.jsonl", "a") as fh:
        for line in out:
            fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
