"""Time ``decode_attention`` ALONE on the chip, by seq block.

    chiprun -- python scripts/decode_kernel_bench.py [--shapes rag-ring,...]
                                                     [--stub]
    chiprun -- python scripts/decode_kernel_bench.py --shapes doc-latent --stub

For each shape (a cell's decode step: cache, rows, contexts) and each seq
block, the kernel runs in an on-device ``fori_loop`` of two lengths and the
slope between them is one call's time (host clock around
``block_until_ready``; the loop's carry salts the queries).  The block is
forced by replacing ``attention._decode_plan`` around a fresh ``jax.jit`` of
the undecorated function; the line of the block the kernel plans by itself
says ``"committed": true``.  ``--stub`` times each block a second time with
the kernel's body taken out (the grid, its index maps and its copies only):
what a grid step costs by itself.  Prints one JSON line a reading and writes
them all to ``chiprun_out/decode_kernel_bench.jsonl``.

The shapes whose name ends in ``-latent`` time the LATENT kernel
(``decode_attention(v_cache=None)``: one latent plane that is key and value,
a rotated key plane beside it), which copies a row's live blocks itself: by
block x ring depth at the committed span of the rotated plane, then by span
at the fastest pair; ``--stub`` reads each plan twice more: with the
arithmetic taken out (``attention._latent_attend``; every copy left:
``copies_us``) and with the kernel's own copies taken out (the arithmetic
and the rotated plane's pipeline left: ``arith_us``).  On a tree from before
that kernel (PR 54's) the same shapes time ``decode_attention``'s latent mode
as it plans itself (``"plan": "parent"``).  A number from a CPU
run is no device time: the script refuses to run off the TPU unless
``--cpu`` (tiny shapes, interpret mode, to rehearse the control flow).

The shapes APPROXIMATE the cells' steps: prompt lengths are drawn as the
cell's file under ``benchmark/traffic`` says, but how far the rows have
decoded (``ahead``), the live rows of a step and the cache shapes are
copied here from the cells' ledger lines and programs, and the draw is this
script's own, not the harness's schedule.
"""

import argparse
import contextlib
import functools
import json
import os
import sys
import time
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from flexflow_tpu.ops.pallas import attention

HBM_GBPS = 819.0  # v5e, published

# name: (slots, kv heads, q per kv, head, cache seq, window,
#        contexts: (traffic file, (lo, hi) tokens decoded so far) or a plain
#        (lo, hi) that is no cell's draw,
#        live rows of the slots (the rest are pads on the scratch row),
#        seq blocks)
SHAPES = {
    # command-a-plus-d4-e16.rag-decode: three ring layers and one full
    "rag-ring": (128, 1, 16, 128, 4608, 4096, ("rag-decode", (0, 1024)), 128,
                 [512, 1536, 2304, 4608]),
    "rag-full": (128, 1, 16, 128, 18432, 0, ("rag-decode", (0, 1024)), 128,
                 [512, 1024, 2048, 3072, 4608]),
    # starcoderbase-3b.code-complete: ~5.75 live rows of 16 slots a step
    # (``decode_rows_per_step.lat``), at its prompts' lengths; and longer
    "code-complete": (16, 1, 22, 128, 8192, 0, ("code-complete", (0, 16)), 6,
                      [512, 1024, 2048, 4096]),
    "code-complete-long": (16, 1, 22, 128, 8192, 0, (512, 5120), 6,
                           [512, 1024, 2048, 4096]),
    # nemotron-3-nano-d9-e64.agent-decode: its prompts and the ~2.4k steps
    # of a window; and with the answers' whole length
    "agent-decode": (256, 2, 16, 128, 8192, 0, ("agent-decode", (0, 2432)),
                     256, [512, 1024, 2048]),
    "agent-decode-long": (256, 2, 16, 128, 8192, 0, (512, 7680), 256,
                          [512, 1024, 2048, 4096]),
}

# name: (slots, q heads, latent width, rotated width, cache seq, contexts,
#        live rows, blocks, depths, spans)
LATENT_SHAPES = {
    # deepseek-v2-lite-d5.doc-decode: 64 rows, one a slot, early in a window
    # (the traced span: a mean context of ~7.9k) and late in it (~11k)
    "doc-latent": (64, 16, 512, 64, 15360, ("doc-decode", (256, 256)), 64,
                   [256, 512, 1024, 1536], [2, 3],
                   [1536, 3072, 5120, 7680]),
    "doc-latent-late": (64, 16, 512, 64, 15360, ("doc-decode", (3328, 3328)),
                        64, [512, 1024], [3], [3072]),
}


def draw_contexts(rng, ctx, n):
    """``n`` contexts: a plain uniform ``(lo, hi)``, or a cell's prompt
    lengths (``prompt_len`` of its traffic file) plus tokens decoded."""
    if isinstance(ctx[0], int):
        return rng.integers(ctx[0], ctx[1] + 1, size=n)
    traffic, ahead = ctx
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as fh:
        spec = json.load(fh)["prompt_len"]
    if spec["dist"] == "uniform":
        prompt = rng.integers(spec["lo"], spec["hi"] + 1, size=n)
    else:
        assert spec["dist"] == "lognormal", spec
        prompt = np.clip(spec["median"] * np.exp(
            spec["sigma"] * rng.standard_normal(n)), spec["lo"], spec["hi"])
    return prompt.astype(np.int64) + rng.integers(ahead[0], ahead[1] + 1,
                                                  size=n)


def _stub_kernel(rows_ref, pos_ref, q_ref, k_ref, v_ref, slopes_ref, o_ref,
                 *scratch, **plan):
    """``_decode_kernel`` with no body: the grid steps and their copies."""
    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _write():
        o_ref[...] = q_ref[...]


def one_call_s(f, args, lengths=(8, 40), repeats=3):
    """Seconds a call of ``f(*args)`` takes on the device: the slope between
    two on-device loop lengths, the least of ``repeats`` readings each."""
    def loop(n):
        @jax.jit
        def run(q, *rest):
            def body(_, c):
                out = f(q + c.astype(q.dtype), *rest)
                return out.ravel()[0].astype(jnp.float32) * 1e-9
            return jax.lax.fori_loop(0, n, body, jnp.float32(0))
        run(*args).block_until_ready()
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            run(*args).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best
    lo, hi = (loop(n) for n in lengths)
    return (hi - lo) / (lengths[1] - lengths[0])


def bench_shape(name, interpret, seed, stub):
    slots, kv, gq, d, s_len, window, ctx, live, blocks = SHAPES[name]
    if interpret:  # a rehearsal: a few rows
        slots, live = min(slots, 4), min(live, 2)
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.standard_normal((slots + 1, kv, s_len, d)),
                     jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((slots + 1, kv, s_len, d)),
                     jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((slots, kv * gq, d)), jnp.bfloat16)
    pos = np.minimum(draw_contexts(rng, ctx, slots),
                     (s_len if not window else 2**30) - 1)
    rows = np.arange(slots)
    rows[live:], pos[live:] = slots, 0      # pads: the scratch row, at 0
    rows, pos = jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32)
    seen = np.minimum(np.asarray(pos)[:live] + 1, window or s_len + 1)
    need_bytes = int(seen.sum()) * 2 * kv * d * 2
    committed = attention._decode_plan(kv, d, 2, False, s_len, window)
    real = attention._decode_plan, attention._decode_kernel
    out = []
    for block in blocks:
        line = dict(shape=name, block=block, committed=block == committed)
        for key, kernel in [("call_us", real[1])] + (
                [("stub_us", _stub_kernel)] if stub else []):
            attention._decode_plan = lambda *a, **k: block
            attention._decode_kernel = kernel
            try:
                f = functools.partial(
                    attention.decode_attention.__wrapped__, scale=d ** -0.5,
                    window=window, interpret=interpret)
                line[key] = round(1e6 * one_call_s(
                    f, (q, kc, vc, rows, pos),
                    lengths=(1, 2) if interpret else (8, 40)), 2)
            finally:
                attention._decode_plan, attention._decode_kernel = real
        least_us = need_bytes / HBM_GBPS / 1e3
        line.update(
            us_per_live_row=round(line["call_us"] / live, 3),
            ns_per_seen_position=round(line["call_us"] * 1e3 / seen.sum(), 4),
            least_us=round(least_us, 2),
            roofline_pct=round(least_us / line["call_us"] * 100, 1),
            device=jax.devices()[0].device_kind)
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def _no_attend(q, c, rope, seen, m_ref, l_ref, acc_ref, scale):
    """``_latent_attend`` taken out: the kernel's copies and waits alone."""


class _NoCopy:
    """``pltpu.make_async_copy`` taken out: the arithmetic alone, on the
    ring as it was cleared (its time does not depend on the values)."""

    def __init__(self, *refs):
        pass

    start = wait = lambda self: None


def bench_latent(name, interpret, seed, stub):
    slots, h, d, dr, s_len, ctx, live, blocks, depths, spans = \
        LATENT_SHAPES[name]
    if interpret:  # a rehearsal: a few rows of a short cache
        slots, live, s_len = 4, 3, 3072
        blocks, depths, spans = blocks[1:2], depths[:1], spans[:2]
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.bfloat16)
    ckv, kpe = draw(slots + 1, 1, s_len, d), draw(slots + 1, 1, s_len, dr)
    q, qr = draw(slots, h, d), draw(slots, h, dr)
    pos = np.minimum(draw_contexts(rng, ctx, slots), s_len - 1)
    rows = np.arange(slots)
    rows[live:], pos[live:] = slots, 0      # pads: the scratch row, at 0
    need_bytes = int(pos[:live].sum() + live) * (d + dr) * 2
    rows, pos = jnp.asarray(rows, jnp.int32), jnp.asarray(pos, jnp.int32)
    f = lambda q, ckv, rows, pos, qr, kpe: \
        attention.decode_attention.__wrapped__(
            q, ckv, None, rows, pos, scale=(d + dr) ** -0.5,
            interpret=interpret, q_rope=qr, k_rope=kpe)
    knob_names = ("_LATENT_BLOCK", "_LATENT_DEPTH", "_LATENT_SPAN")
    if not hasattr(attention, "_latent_decode"):
        committed, plans = None, [None]     # a tree before the kernel
    else:
        committed = tuple(getattr(attention, n) for n in knob_names)
        plans = [(b, dp, committed[2]) for b in blocks for dp in depths]
    out = []

    def read(plan):
        """One plan's line: the whole call and, with ``stub``, its two
        halves.  ``(attributes of ``attention`` to set, the copy to use)``
        per reading; None: the tree's own plan."""
        if plan is None:
            line, readings = dict(shape=name, plan="parent"), \
                {"call_us": ({}, None)}
        else:
            line = dict(shape=name, block=plan[0], depth=plan[1],
                        span=plan[2], committed=plan == committed)
            knobs = dict(zip(knob_names, plan))
            readings = {"call_us": (knobs, None)}
            if stub:
                readings["copies_us"] = (
                    dict(knobs, _latent_attend=_no_attend), None)
                readings["arith_us"] = (knobs, _NoCopy)
        for key, (knobs, copier) in readings.items():
            with contextlib.ExitStack() as patched:
                if knobs:
                    patched.enter_context(
                        mock.patch.multiple(attention, **knobs))
                if copier:
                    patched.enter_context(mock.patch.object(
                        attention.pltpu, "make_async_copy", copier))
                line[key] = round(1e6 * one_call_s(
                    f, (q, ckv, rows, pos, qr, kpe),
                    lengths=(1, 2) if interpret else (8, 40)), 2)
        least_us = need_bytes / HBM_GBPS / 1e3
        line.update(
            us_per_live_block_of_512=round(
                line["call_us"] * 512 * (d + dr) * 2 / need_bytes, 4),
            least_us=round(least_us, 2),
            roofline_pct=round(least_us / line["call_us"] * 100, 1),
            device=jax.devices()[0].device_kind)
        print(json.dumps(line), flush=True)
        out.append(line)

    for plan in plans:
        read(plan)
    if plans[0]:    # the rotated plane's span, at the fastest block and ring
        best = min(out, key=lambda ln: ln["call_us"])
        for span in spans:
            if span != committed[2] and span % best["block"] == 0:
                read((best["block"], best["depth"], span))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stub", action="store_true",
                    help="also time each block with the body taken out")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse in interpret mode (no device time)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu" and not args.cpu:
        raise SystemExit("decode_kernel_bench times the chip: no TPU here")
    lines = []
    for name in args.shapes.split(","):
        bench = bench_latent if name in LATENT_SHAPES else bench_shape
        lines += bench(name, args.cpu, args.seed, args.stub)
    if args.cpu:   # a rehearsal's numbers are no device times: not kept
        return
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/decode_kernel_bench.jsonl", "a") as fh:
        for line in lines:
            fh.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
