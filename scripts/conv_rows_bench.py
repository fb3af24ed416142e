"""Time ``CausalConv1d``'s row form ALONE on the chip: the body before PR 67
(``gather_scatter``: every row gathers its slot's tail, every row scatters a
new one), the SEGMENT form with the stored taps picked per row by a one-hot
product on the MXU (``onehot``: ISSUE 67's form (i)) — both kept in
``tests/conv_row_forms.py`` alone — and the tree's ``CausalConv1d._rows``
(``segments``: form (ii) — the rows that open a segment computed again per
slot and put over the first pass's).

    chiprun -- python scripts/conv_rows_bench.py [--shapes solar,solar3,kimi,flat128]

``solar``: ``solar-open2-d4-e40``'s prompt chunk, 1024 rows of 24 576
channels (q | k | v of 64 heads x 128) over 17 state rows, ONE segment that
continues a stored tail; ``solar3``: the same chunk shared by three prompts
(the end of one, a whole short one, the start of a third) with pad rows
between; ``kimi``: ``kimi-linear-d5-e32``'s, 512 rows of 12 288 channels
over 257 state rows, one segment; ``flat128``: a flat step of 128 one-row
segments at kimi's widths.  bf16, K = 4, no bias.  A line a shape:
microseconds a call of each form (the slope between two on-device loop
lengths; each call's ``y`` is the next call's ``x`` and its tails the next
call's, so that nothing is hoisted or dropped).

Then, ON THE CHIP, ``mixed``: pads before, between and after, segments of 1
and 2 rows on stored tails, a fresh 2-row prompt, rows out of slot order, a
long segment — every form against a float32 NumPy conv (largest gap of
``y`` in bf16 units in the last place, tails to the bit) and against each
other (rows whose ``y`` differs in any bit).

One JSON line each (also ``chiprun_out/conv_rows_bench.jsonl``).  Refuses
to run off the TPU unless ``--cpu`` (toy shapes: no device time).
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

from conv_row_forms import gather_scatter, onehot
from delta_rule_forms import layout, segments

from flexflow_tpu.serve.hybrid_ops import CausalConv1d

FORMS = {"gather_scatter": gather_scatter, "onehot": onehot,
         "segments": lambda op, *a: op._rows(*a)}


def one_call_s(f, x, tails, lengths=(4, 20), repeats=3):
    """Seconds a call takes on the device (see the module's docstring)."""
    def loop(n):
        @jax.jit
        def run(x, tails):
            def body(_, carry):
                y, tails = f(*carry)
                return y.astype(x.dtype), tails
            return jax.lax.fori_loop(0, n, body, (x, tails))
        jax.block_until_ready(run(x, tails))
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x, tails))
            best = min(best, time.perf_counter() - t0)
        return best
    lo, hi = (loop(n) for n in lengths)
    return (hi - lo) / (lengths[1] - lengths[0])


def reference(x, tails, w, req, pos, k):
    """Row by row in float32, newest tap first (the op's order)."""
    x, tails, w = (np.asarray(a.astype(jnp.float32)) for a in (x, tails, w))
    y, left = np.zeros_like(x), tails.copy()
    seen = {}                                     # slot -> its inputs so far
    for r, (s, p) in enumerate(zip(req, pos)):
        if s < 0:
            y[r] = x[r] * w[k - 1]
            continue
        past = seen.setdefault(s, [tails[s, j] if p - (k - 1) + j >= 0
                                   else np.zeros_like(x[r])
                                   for j in range(k - 1)])
        past.append(x[r])
        acc = np.zeros_like(x[r])
        for back in range(k):
            acc = acc + past[-1 - back] * w[k - 1 - back]
        y[r] = acc
        left[s] = np.stack(past[-(k - 1):])
    return y / (1.0 + np.exp(-y)), left


def mixed(c, slots, rng):
    k = 4
    op = CausalConv1d(c, k, dtype=jnp.bfloat16, bias=False)
    req, pos = map(np.asarray, layout(
        [(-1, 0, 3), (5, 9, 1), (2, 4, 2), (-1, 0, 2), (7, 0, 2), (6, 0, 1),
         (1, 1, 1), (0, 30, 70), (-1, 0, 5), (4, 0, 37), (3, 2, 2),
         (-1, 0, 2)]))
    t = len(req)
    draw = lambda *shape: jnp.asarray(
        rng.standard_normal(shape, np.float32), jnp.bfloat16)
    x, tails, w = draw(t, c), draw(slots + 1, k - 1, c), draw(k, c)
    want_y, want_left = reference(x, tails, w, req, pos, k)
    line = dict(check="mixed", rows=t, channels=c, slots=slots)
    live, got = req >= 0, {}
    for name, f in FORMS.items():
        y, left = jax.jit(lambda x, tails, f=f: f(
            op, x, tails, segments(req, pos, slots), w, None))(x, tails)
        y = np.asarray(y.astype(jnp.float32))
        left = np.asarray(left.astype(jnp.float32))
        got[name] = y
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want_y), 1e-30)))
                      - 7)
        line[f"{name}_y_ulps"] = float(
            (np.abs(y - want_y) / ulp)[live].max())
        line[f"{name}_tails_equal"] = bool(
            (left[:slots] == want_left[:slots]).all())
    for name in ("gather_scatter", "onehot"):
        line[f"segments_vs_{name}_rows_differ"] = int(
            (got["segments"] != got[name])[live].any(axis=1).sum())
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="solar,solar3,kimi,flat128")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse at toy shapes (no device time)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        raise SystemExit("conv_rows_bench times the chip: no TPU here")
    big = not args.cpu
    rows, c, few, many = (1024, 24576, 16, 256) if big else (160, 256, 8, 40)
    a, b = (rows * 3) // 8 + 5, rows // 4 + 3
    shapes = {
        "solar": (c, few, [(0, 4096, rows)]),
        "solar3": (c, few, [(0, 4096, a), (-1, 0, 11), (1, 0, b),
                            (-1, 0, 13), (2, 0, rows - a - b - 31),
                            (-1, 0, 7)]),
        "kimi": (c // 2, many, [(3, 2048, rows // 2)]),
        "flat128": (c // 2, many,
                    [(s, 700 + s, 1) for s in range(rows // 8)][::-1]),
    }
    rng = np.random.default_rng(args.seed)
    out = [mixed(c, few, rng)]
    print(json.dumps(out[0]), flush=True)
    for name in args.shapes.split(","):
        c_, slots, pieces = shapes[name]
        req, pos = layout(pieces)
        op = CausalConv1d(c_, 4, dtype=jnp.bfloat16, bias=False)
        draw = lambda *shape: jnp.asarray(
            rng.standard_normal(shape, np.float32), jnp.bfloat16)
        x, tails, w = draw(len(req), c_), draw(slots + 1, 3, c_), draw(4, c_)
        line = dict(shape=name, rows=len(req), channels=c_, slots=slots,
                    segments=sum(s >= 0 for s, _, _ in pieces),
                    device=jax.devices()[0].device_kind)
        for form, f in FORMS.items():
            s = one_call_s(lambda x, tails, f=f: f(
                op, x, tails, segments(req, pos, slots), w, None), x, tails)
            line[f"{form}_us"] = round(s * 1e6, 1)
        out.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "conv_rows_bench.jsonl"),
              "w") as fh:
        fh.writelines(json.dumps(line) + "\n" for line in out)


if __name__ == "__main__":
    main()
