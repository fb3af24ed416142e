#!/usr/bin/env python3
"""Hold a SPARSE selection to the reference where a row really selects: past
``dense_len``, at the published widths.

    python benchmark/longctx.py --config <name> --seeds 1,2,3

``check.drive``'s long sequence ends at 4 085 positions; below ``dense_len``
(8 192) a sparse-attention row attends every block, so ``run.py``'s own check
never meets a selection.  This drive is for that.  Per seed, two sequences in
two slots, each prefilled by the tiled prefill scan (as ``check._prefill_scan``
feeds a prompt): one to ``SHORT[0]`` positions short of a whole number of
blocks well past ``dense_len`` (192 blocks of 64: 12 268 tokens; prompt rows on
both sides of ``dense_len``, half of the blocks discarded from there on), one to
``SHORT[1]`` positions short of ``dense_len`` itself (8 162 tokens).  Both then
decode together as ``boundary.drive`` decodes its rows: two chained
``decode_scan_async`` segments of 32 steps (no readback between them, as the
scheduler chains a stretch) — the first row crosses a block's end and
completes a compressed key every 16 steps, the second crosses ``dense_len``,
from attending everything to selecting, inside the scan — then
``check.TAIL_STEPS`` flat decode steps on both rows: their logits read what the
DECODE path selected, wrote (K/V and index entries) and accumulated (the
linear-attention state).  What it cannot see: an index entry the scan appended
is chosen BY only once it is a window (2 048 positions) old; a drive that long
needs more reference logits than fit beside the deployment (PERF.md section 2).

The reference computes the full forward pass of prompt + generated tokens
(``check.reference_logits``), the numbers are ``check.compare``'s and the
limits the configuration's own (``benchmark.correct``): the same numbers, the
same limits as ``correct``.  Beside the verdict it prints a READING, no limit:
of the (decode row, K/V group) pairs of the FIRST sparse layer (the newest 68
rows of each sequence), the share whose
attended set — recomputed by the op's own ``select`` from the index the
program left in its cache and the queries in the program's arithmetic — equals
the reference's, and the mean overlap of the two sets.  Exit code 0 when every
seed is within the limits.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from benchmark import boundary, check, run as harness  # noqa: E402
from benchmark.traffic_gen import FIRST_TOKEN_ID  # noqa: E402

SHORT = (20, 30)   # positions each prompt stops short of its mark
STEPS = 2 * boundary.SEGMENT    # scanned steps: ``boundary.drive``'s
READING_ROWS = STEPS + check.TAIL_STEPS   # rows the reading covers


def sequences(seed, vocab_size, block, dense_len):
    rng = np.random.default_rng([int(seed), 0x5A1A])
    # half as far again as dense_len (12 288 = 192 blocks), and dense_len
    marks = (dense_len * 3 // 2 // block * block, dense_len)
    return [rng.integers(FIRST_TOKEN_ID, vocab_size,
                         size=mark - short).tolist()
            for mark, short in zip(marks, SHORT)]


def selection_agreement(im, ref, hf, key, dtype, fed, first_decoded):
    """The reading of the module docstring, for the first sparse layer:
    ``fed[s]`` the tokens slot ``s`` was fed (prompt + generated but the
    last), ``first_decoded[s]`` its first decode position.  Returns
    ``(share of equal sets, mean overlap, pairs)``."""
    import jax
    import jax.numpy as jnp

    from benchmark import seeded_weights as sw
    from flexflow_tpu.ops.norm import _rms_norm
    from flexflow_tpu.serve.hybrid_ops import SparseBlockAttention

    layer = ref.layer_kinds(hf).index(ref.SPARSE)
    name = f"model.layers.{layer}.self_attn"
    op = next(n.op for n in im.model.graph.nodes if n.name == name)
    assert isinstance(op, SparseBlockAttention)
    if layer:   # the queries below are the embedding's: layer 0 only
        return None
    sizes = ref.sparse_sizes(hf)
    g = jax.jit(lambda k: sw.draw_table(k, sw.GLOBAL_ID, ref.GLOBAL, hf,
                                        dtype))(key)
    w = jax.jit(lambda k: sw.draw_table(k, layer, ref.LAYER, hf, dtype))(key)
    f32 = lambda a: a.astype(jnp.float32)
    init = ref.published_init(hf, w)
    equal = overlap = pairs = 0
    for s, ids in enumerate(fed):
        ids = jnp.asarray(ids, jnp.int32)
        # the newest decode rows: every one of a two-segment drive's
        at = jnp.arange(max(first_decoded[s], len(ids) - READING_ROWS),
                        len(ids))
        # the reference's selection, float32
        x = ref.embed(hf, g, ids[None]).h
        n = ref.rms_norm(x, f32(w["input_layernorm.weight"]),
                         hf["rms_norm_eps"])
        h, kv, hd = ref.attention_shape(hf)
        q = ref.mm(n[:, at], f32(init["self_attn.sparse_q_proj"]))
        k = ref.mm(n, f32(init["self_attn.sparse_k_proj"]))
        nb = im.state[name]["k"].shape[2] // op.block_size
        want = ref.attended_blocks(
            q.reshape(1, -1, h, hd), at,
            ref.compressed_keys(k.reshape(1, -1, kv, hd), sizes), nb,
            sizes)[0]
        want = jnp.broadcast_to(want, (len(at), kv, nb))
        # the program's: its own arithmetic for q, ITS index, its ``select``
        p = im.params
        xs = (p["model.embed_tokens"]["weight"][ids[at]]
              * jnp.asarray(hf.get("scale_emb", 1.0), dtype))
        ns = _rms_norm(xs, p[f"model.layers.{layer}.input_layernorm"]["gamma"],
                       hf["rms_norm_eps"])
        qs = jnp.dot(ns, p[name]["qkv"][:, :h * hd],
                     preferred_element_type=jnp.float32).astype(ns.dtype)
        got = op.select(qs.reshape(-1, h, hd),
                        jnp.broadcast_to(im.state[name]["kidx"][s],
                                         (len(at),)
                                         + im.state[name]["kidx"].shape[1:]),
                        at.astype(jnp.int32))
        both = jnp.sum(got & want, axis=-1)
        size = jnp.sum(want, axis=-1)
        equal += int(jnp.sum(jnp.all(got == want, axis=-1)))
        overlap += float(jnp.sum(both / size))
        pairs += int(size.size)
    return equal / pairs, overlap / pairs, pairs


def run_longctx(im, ref, hf, key, dtype, seed, limits, log):
    """Drive, compare, print each number beside its limit; ``(within limits,
    numbers, the selection reading)``."""
    sizes = ref.sparse_sizes(hf)
    block, dense_len = sizes[2], sizes[6]
    seqs = sequences(seed, hf["vocab_size"], block, dense_len)
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
    ok = check._judge(numbers, limits, log, "longctx")
    reading = selection_agreement(im, ref, hf, key, dtype, fed,
                                  [len(s) for s in seqs])
    log(f"longctx: {info['rows']} flat rows and {info['tokens']} produced "
        f"tokens at contexts up to {len(fed[0]) + 1}; prompts "
        f"{[len(s) for s in seqs]} (dense_len {dense_len}), {STEPS} scanned "
        f"steps; logit scale {info['logit_scale']:.3f}, "
        f"{'within' if ok else 'OUTSIDE'} limits")
    if reading:
        log(f"longctx: first sparse layer, {reading[2]} (decode row, group) "
            f"selections: {100 * reading[0]:.1f}% equal to the reference's, "
            f"mean overlap {100 * reading[1]:.2f}% (a reading, no limit)")
    return ok, numbers, reading


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    hf, dep, ref = harness.load_config(harness.ROOT, bench, args.config)
    if not hasattr(ref, "sparse_sizes"):
        harness.die(f"{args.config} has no sparse attention: nothing "
                    "selects")
    devices, _ = harness.require_device(dep["chips"])
    from flexflow_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    llm = harness.build(hf, dep, devices)
    all_ok = True
    for seed in (int(s) for s in args.seeds.split(",") if s):
        key = harness.seed_weights(llm, ref, hf, seed, dep["precision"])
        ok, numbers, reading = run_longctx(
            llm.im, ref, hf, key, dep["precision"], seed, dep["correct"],
            print)
        line = {"config": args.config, "drive": "longctx", "seed": seed,
                "within_limits": ok, **numbers}
        if reading:
            line.update(selection_equal_share=reading[0],
                        selection_mean_overlap=reading[1])
        print(json.dumps(line), flush=True)
        all_ok = all_ok and ok
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
