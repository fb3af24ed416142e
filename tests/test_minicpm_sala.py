"""MiniCPM-SALA (sparse block-selected attention beside Lightning linear
attention) through the normal serve path, against the plain reference
``benchmark/reference/minicpm_sala.py`` — logits, not tokens.

Toy widths, the real mechanisms: hidden 64, 4 query heads on 2 K/V heads of
16, lightning 4 heads of 16, 4 layers ``[minicpm4, lightning-attn,
lightning-attn, minicpm4]``; kernel 4 / stride 2 / block 8 / top-k 3 / window
16 / ``dense_len`` 48, 256 positions.  Past position 48 a row attends block 0,
its 2 newest blocks and 3 CHOSEN ones of up to 29: every sequence here goes
past that, in prefill and in decode.  Weights are the benchmark's seeded ones
in float32 (``seeded_weights.program_params`` also holds the program's
parameter tree to the reference's ``program_tree``, name by name).

The reference is a dense softmax under a mask and a literal sum over earlier
positions; the program keeps a cache, an index, block lists and a matrix
state.  float32 on the CPU against float32 at HIGHEST precision: they differ
by summation order alone and a log-probability agrees to 2e-4 nats — the
selection replaced by the newest blocks, a stale index, a dropped decay or the
residual scale taken from the cut depth move it by 4e-3 or more
(``test_a_break_is_seen`` holds that).
"""

import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, longctx, seeded_weights as sw  # noqa: E402
from benchmark.reference import minicpm_sala as ref  # noqa: E402
from flexflow_tpu.config import FFConfig  # noqa: E402
from flexflow_tpu.model import FFModel  # noqa: E402
from flexflow_tpu.parallel.mesh import make_mesh  # noqa: E402
from flexflow_tpu.serve import BatchConfig  # noqa: E402
from flexflow_tpu.serve import hybrid_ops  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import (  # noqa: E402
    LightningAttention,
    Segments,
    SparseBlockAttention,
    lightning_slopes,
)
from flexflow_tpu.serve.inference_manager import InferenceManager  # noqa: E402
from flexflow_tpu.serve.models import minicpm_sala as builder  # noqa: E402
from flexflow_tpu.serve.models.base import (  # noqa: E402
    ServeModelConfig,
    build_model,
)

HF = dict(model_type="minicpm_sala", hidden_size=64, intermediate_size=96,
          num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
          mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                       "minicpm4"],
          vocab_size=320, rms_norm_eps=1e-6, rope_theta=10000, scale_emb=12,
          scale_depth=1.4, mup_denominator=32, dim_model_base=16,
          qk_norm=True, use_output_norm=True, use_output_gate=True,
          attn_use_output_gate=True, attn_use_rope=False,
          lightning_use_rope=True, sparse_kernel_size=4,
          sparse_kernel_stride=2, sparse_block_size=8, sparse_topk=3,
          sparse_window_size=16, sparse_init_blocks=1, sparse_dense_len=48,
          max_position_embeddings=256,
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 4096
          init_std=0.125, torch_dtype="float32")
SLOTS, CAP, SEQ = 3, 16, 256
DENSE = HF["sparse_dense_len"]
TOL = 2e-4          # nats, see the module docstring
SEED = 4321


def build(cap=CAP, seq=SEQ, use_pallas=False, hf=HF, **kw):
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(hf), cap)
    return InferenceManager(ff, max_requests=SLOTS, max_tokens_per_batch=cap,
                            max_seq_len=seq, topk=HF["vocab_size"],
                            use_pallas=use_pallas, **kw)


def seeded(im):
    im.init_operators_inference()
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        im.params)
    im.params = sw.program_params(ref, HF, sw.base_key(SEED), like, "float32")
    return im


@functools.lru_cache(maxsize=None)
def deployment(use_pallas=False):
    """One compiled deployment per kernel setting, shared by the tests (each
    starts its sequences at position 0 of a slot, which is all a slot needs
    to start clean)."""
    return seeded(build(use_pallas=use_pallas))


@functools.lru_cache(maxsize=None)
def _ref_layer(padded_len):
    return jax.jit(lambda key, i, x: ref.layer(
        HF, sw.draw_table(key, i, ref.LAYER, HF, "float32"), x))


def reference_logprobs(ids):
    """Sorted log-probabilities at every position of ``ids``, and the
    reference's greedy tokens, from its full forward pass."""
    key = sw.base_key(SEED)
    g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, HF, "float32")
    padded = np.zeros(-(-len(ids) // 64) * 64, np.int32)
    padded[:len(ids)] = ids
    x = ref.embed(HF, g, jnp.asarray(padded[None]))
    for i in range(ref.num_layers(HF)):
        x = _ref_layer(len(padded))(key, jnp.int32(i), x)
    logits = ref.head(HF, g, x[:, :len(ids)])[0]
    lp = jax.nn.log_softmax(logits, axis=-1)
    return np.asarray(jnp.sort(lp, axis=-1)[:, ::-1]), \
        np.asarray(jnp.argmax(logits, axis=-1))


def tokens(n, salt=0):
    rng = np.random.default_rng([SEED, salt])
    return rng.integers(4, HF["vocab_size"], size=n).tolist()


def flat_step(im, pieces, seq_lens):
    """One flat step holding ``pieces`` = [(slot, ids, start position)];
    returns the sorted log-probabilities per piece, and the tokens."""
    toks, slots, pos = [], [], []
    for slot, ids, start in pieces:
        toks += list(ids)
        slots += [slot] * len(ids)
        pos += list(range(start, start + len(ids)))
        seq_lens[slot] = start + len(ids)
    bc = BatchConfig.build(toks, slots, pos, seq_lens,
                           max_tokens=im.max_tokens, max_requests=SLOTS)
    res = im.step(bc)
    lp, out, at = np.asarray(res.topk_logprobs), [], 0
    for _, ids, _ in pieces:
        out.append(lp[at:at + len(ids)])
        at += len(ids)
    return out, np.asarray(res.token_ids)


def feed_flat(im, slot, ids, sizes, seq_lens):
    """``ids`` into ``slot`` from position 0 by flat steps of the given
    sizes (cycled); the log-probabilities at every position."""
    rows, at, i = [], 0, 0
    while at < len(ids):
        take = min(sizes[i % len(sizes)], len(ids) - at)
        (lp,), _ = flat_step(im, [(slot, ids[at:at + take], at)], seq_lens)
        rows.append(lp)
        at, i = at + take, i + 1
    return np.concatenate(rows)


def decode_scan(im, slot, first, position, steps):
    """``steps`` decode steps of ``slot`` on the device, in chained scans of
    at most 32: the tokens produced after ``first`` (fed at ``position``)."""
    seq = np.zeros(SLOTS, np.int32)
    seq[slot] = position + 1
    bc = BatchConfig.build([first], [slot], [position], seq,
                           max_tokens=im.max_tokens, max_requests=SLOTS)
    out, done = [], 0
    while done < steps:
        n = min(32, steps - done)
        allowed = np.zeros(im.max_tokens, np.int32)
        allowed[0] = steps - done
        toks, live, _, bc = im.decode_scan_async(
            bc, n, allowed=allowed, max_position=position + done)
        assert np.asarray(live)[:, 0].all()
        out += np.asarray(toks)[:, 0].tolist()
        done += n
    return out


PROMPT = tokens(150)    # 102 of its rows select: 19 blocks, 6 attended


@pytest.mark.parametrize("how", ["uneven_chunks", "tiled_scan",
                                 "tiled_scan_pallas", "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in uneven flat chunks (segments of one request that
    begin anywhere; the chunk that holds position 48 has rows on both sides
    of ``dense_len``) and through the tiled prefill scan, kernels off (the
    masked-dense oracle) and on (block lists through
    ``sparse_decode_attention`` for flat rows, masked-dense tiles for the
    scan): a decode step then reads what each left."""
    want, want_tok = reference_logprobs(PROMPT + tokens(3, salt=1))
    n = len(PROMPT)
    seq_lens = [0] * SLOTS
    im = deployment(use_pallas=how.endswith("pallas"))
    if how.startswith("tiled_scan"):
        first = check._prefill_scan(im, 1, PROMPT, list(seq_lens))
        assert first == want_tok[n - 1]
    else:
        got = feed_flat(im, 1, PROMPT, [7, CAP, 1, 13, 3], seq_lens)
        np.testing.assert_allclose(got, want[:n], atol=TOL, rtol=0)
    for k, tok in enumerate(tokens(3, salt=1)):
        (lp,), _ = flat_step(im, [(1, [tok], n + k)], seq_lens)
        np.testing.assert_allclose(lp[0], want[n + k], atol=TOL, rtol=0)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_scan_passes_dense_len_and_appends_what_prefill_makes(
        use_pallas):
    """A 30-token prompt, then 70 decode steps on the device in chained
    scans: the row passes ``dense_len`` (48) — from attending everything to
    selecting — and completes 35 compressed keys INSIDE THE SCAN.  Flat
    steps then read, at position 100 on, logits that depend on what the scan
    selected, wrote and accumulated; and the K/V, the index and the matrix
    states the scan left are those the same 100 tokens leave when PREFILLED
    into another slot."""
    im = deployment(use_pallas=use_pallas)
    prompt = tokens(30, salt=5)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 29)], seq_lens)
    first = int(toks[0])
    made = decode_scan(im, 0, first, 30, 70)
    full = prompt + [first] + made                  # 101 tokens
    # teacher forcing: the reference is fed what the program produced
    want, want_tok = reference_logprobs(full + tokens(2, salt=6))
    assert full[30:] == want_tok[29:100].tolist()
    # the same tokens, prefilled: every index entry made by the prompt path
    # (before the flat steps below: the matrix state is cumulative)
    feed_flat(im, 2, full[:100], [CAP], seq_lens)
    entries = (100 - 4) // 2 + 1
    for node, bufs in im.state.items():
        for name, live in (("k", 100), ("v", 100), ("kidx", entries),
                           ("lin", None)):
            if name not in bufs:
                continue
            a, b = bufs[name][0], bufs[name][2]
            if live:
                a, b = a[:, :live], b[:, :live]
            assert float(jnp.abs(a).max()) > 1e-2, (node, name)
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
    seq_lens[0] = 100
    for k, tok in enumerate([full[100]] + tokens(2, salt=6)):
        (got,), _ = flat_step(im, [(0, [tok], 100 + k)], seq_lens)
        np.testing.assert_allclose(got[0], want[100 + k], atol=TOL, rtol=0)


def _sparse_op():
    return SparseBlockAttention(64, 4, 2, 16, kernel_size=4, kernel_stride=2,
                                block_size=8, topk=3, window=16,
                                init_blocks=1, dense_len=DENSE)


@pytest.mark.parametrize("salt", [0, 1])
def test_selected_sets_equal_the_references(salt):
    """The op's selection (an index cache, a max pool by slices, ``top_k``,
    a mask) against the reference's (means of slices of all keys, an overlap
    table, ``top_k``) on the same random queries and keys, float32: the same
    SETS, row by row and group by group — 6 blocks of up to 25 past
    ``dense_len``, every block before it."""
    op = _sparse_op()
    rng = np.random.default_rng([SEED, 70 + salt])
    t = 200
    q = jnp.asarray(rng.normal(size=(t, 4, 16)) * 2, jnp.float32)
    k = jnp.asarray(rng.normal(size=(t, 2, 16)) * 2, jnp.float32)
    sizes = ref.sparse_sizes(HF)
    pos = jnp.arange(t, dtype=jnp.int32)
    want = ref.attended_blocks(q[None], pos, ref.compressed_keys(k[None],
                                                                 sizes),
                               SEQ // 8, sizes)[0]
    kbar = ref.compressed_keys(k[None], sizes)[0]           # [NC, KV, hd]
    idx = jnp.zeros((2, SEQ // 2, 16)).at[:, :kbar.shape[0]].set(
        kbar.transpose(1, 0, 2))
    got = op.select(q, jnp.broadcast_to(idx, (t,) + idx.shape), pos)
    assert got.shape == want.shape == (t, 2, SEQ // 8)
    assert bool(jnp.all(got == want))
    count = np.asarray(got.sum(-1))
    assert (count[:DENSE] == np.arange(DENSE)[:, None] // 8 + 1).all()
    assert (count[DENSE:] == 6).all() and op.max_blocks == 6
    # and the two groups do choose differently
    assert bool(jnp.any(got[DENSE:, 0] != got[DENSE:, 1]))
    blocks, n = op.block_list(got)
    assert (np.asarray(n) == count).all()
    assert (np.diff(np.asarray(blocks), axis=-1) >= 0).all()


def _select_by_scatter(op, q, idx, pos):
    """``select`` as it stood before PR 47 — the chosen ids SCATTERED into
    the mask — kept as the reference of the compare that replaced it."""
    score = op.block_scores(q, idx, pos)
    b = jnp.arange(score.shape[-1], dtype=jnp.int32)
    last = (pos // op.block_size)[:, None]
    have = b <= last
    forced = have & ((b < op.init_blocks)
                     | (b > last - op.window // op.block_size))
    free = (have & ~forced)[:, None]
    top, ids = jax.lax.top_k(jnp.where(free, score, -2.0),
                             min(op.topk, score.shape[-1]))
    t_i = jnp.arange(score.shape[0])[:, None, None]
    g_i = jnp.arange(score.shape[1])[None, :, None]
    chosen = jnp.zeros(score.shape, bool).at[t_i, g_i, ids].set(top > -2.0)
    return jnp.where((pos < op.dense_len)[:, None, None], have[:, None],
                     forced[:, None] | chosen)


def _block_list_by_sort(op, mask):
    """``block_list`` as it stood before PR 47: a stable sort of the mask."""
    n = jnp.sum(mask, axis=-1).astype(jnp.int32)
    order = jnp.argsort(~mask, axis=-1, stable=True)
    order = order[..., :op.max_blocks].astype(jnp.int32)
    at = jnp.minimum(jnp.arange(order.shape[-1], dtype=jnp.int32),
                     jnp.maximum(n - 1, 0)[..., None])
    return jnp.take_along_axis(order, at, axis=-1), n


def _scan_rows(rng, slots=12, dead=(3, 8)):
    """A decode scan's batch over ``slots`` slots (+ the scratch row): one
    row a slot in SHUFFLED slot order, the ``dead`` rows on the scratch row
    at position 0, live positions on both sides of ``dense_len``."""
    rows = rng.permutation(slots).astype(np.int32)
    pos = rng.integers(DENSE, SEQ, size=slots).astype(np.int32)
    pos[::3] = rng.integers(0, DENSE, size=len(pos[::3]))
    for d in dead:
        rows[d], pos[d] = slots, 0
    assert (pos[rows < slots] < DENSE).any() and (pos >= DENSE).any()
    return jnp.asarray(rows), jnp.asarray(pos)


@pytest.mark.parametrize("salt", [0, 1, 2])
def test_slot_order_selection_equals_the_gathered_one(salt):
    """The decode scan's selection — queries laid out by slot, scores taken
    against the index where it lies, the scores gathered back — against
    ``select`` on each row's gathered index, and against the scattered mask
    ``select`` built before: the masks are EQUAL, the sorted lists too."""
    op = _sparse_op()
    rng = np.random.default_rng([SEED, 90 + salt])
    rows, pos = _scan_rows(rng)
    t = rows.shape[0]
    q = jnp.asarray(rng.normal(size=(t, 4, 16)) * 2, jnp.float32)
    kidx = jnp.asarray(rng.normal(size=(t + 1, 2, SEQ // 2, 16)) * 2,
                       jnp.float32)
    want = _select_by_scatter(op, q, kidx[rows], pos)
    got = jax.jit(op._select_slots)(q, kidx, rows, pos)
    assert got.shape == want.shape == (t, 2, SEQ // 8)
    assert bool(jnp.all(got == want))
    assert bool(jnp.all(op._select_rows(q, kidx, rows, pos) == want))
    live = np.asarray(rows) < t
    count = np.asarray(got.sum(-1))
    assert (count[live & (np.asarray(pos) >= DENSE)] == 6).all()
    assert (count[~live] == 1).all()        # a pad "attends" block 0 of 0
    for a, b in zip(op.block_list(got), _block_list_by_sort(op, want)):
        assert a.dtype == b.dtype and bool(jnp.all(a == b))


@pytest.mark.parametrize("count", [0, 1, 6, 9, "mixed"])
def test_the_list_built_from_ranks_equals_the_sorted_one(count):
    """``block_list`` (entry ``j`` = the count of blocks whose running count
    is ``<= j``) against the stable sort of the mask it replaced: rows that
    attend nothing, one block, exactly ``max_blocks`` (6) and more."""
    op = _sparse_op()
    rng = np.random.default_rng([SEED, 95])
    nb = SEQ // 8
    counts = rng.integers(0, 12, size=(40, 2)) if count == "mixed" \
        else np.full((40, 2), count)
    mask = np.zeros((40, 2, nb), bool)
    for i, j in np.ndindex(40, 2):
        mask[i, j, rng.choice(nb, size=counts[i, j], replace=False)] = True
    got, n = jax.jit(op.block_list)(jnp.asarray(mask))
    want, want_n = _block_list_by_sort(op, jnp.asarray(mask))
    assert got.shape == (40, 2, op.max_blocks) and got.dtype == want.dtype
    assert (np.asarray(n) == counts).all() and bool(jnp.all(n == want_n))
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("path", ["gathered", "slot_order"])
def test_tied_scores_keep_top_k_order(path):
    """An index whose entries repeat with period 8 (two blocks): block ``b``
    and ``b + 2`` score the same to the bit, and ``top_k`` keeps the LOWER
    id of a tie — the chosen three are those a stable sort of the scores
    puts first, on both paths."""
    op = _sparse_op()
    rng = np.random.default_rng([SEED, 97])
    rows, pos = _scan_rows(rng)
    pos = jnp.where(rows < 12, jnp.maximum(pos, 200), pos)   # 25 blocks seen
    t = rows.shape[0]
    q = jnp.asarray(rng.normal(size=(t, 4, 16)), jnp.float32)
    base = rng.normal(size=(t + 1, 2, 8, 16))
    kidx = jnp.asarray(np.tile(base, (1, 1, SEQ // 16, 1)), jnp.float32)
    score = np.asarray(op.block_scores(q, kidx[rows], pos))
    if path == "gathered":
        got = op.select(q, kidx[rows], pos)
    else:
        got = op._select_slots(q, kidx, rows, pos)
    assert bool(jnp.all(got == _select_by_scatter(op, q, kidx[rows], pos)))
    got, ties = np.asarray(got), 0
    for i in np.flatnonzero(np.asarray(rows) < t):
        last = int(pos[i]) // 8
        free = np.arange(1, last - 1)       # not block 0, not the newest 2
        for g in range(2):
            ties += int((score[i, g, 1:last - 3] == score[i, g, 3:last - 1])
                        .sum())
            first = free[np.argsort(-score[i, g, free], kind="stable")[:3]]
            want = np.zeros(SEQ // 8, bool)
            want[[0, last - 1, last, *first]] = True
            assert (got[i, g] == want).all(), (i, g)
    assert ties > 100


def _kernel_case(case, rng):
    """Rows ``(position, chosen blocks per K/V head | "dense" | None)`` for
    one branch of ``sparse_decode_attention``'s copy engine, at block 8, a
    forced window of 4 blocks (the kernel's chunk), lists of 16, 256
    positions: a row past ``dense_len`` attends block 0, its 4 newest blocks
    and the chosen ones."""
    if case == "dead_row":              # count 0 between two live rows
        return [(200, [[3, 9], [5]]), (0, None), (77, "dense")]
    if case == "shorter_than_a_chunk":  # 1, 2 and 3 blocks: no run, no wait
        return [(5, "dense"), (12, "dense"), (23, "dense")]
    if case == "dense_128_blocks":      # every chunk whole and a run
        return [(127, "dense"), (120, "dense")]
    if case == "chosen_touch_the_run":  # chosen blocks next to the window's
        # run and to each other: consecutive ids ACROSS a chunk's edge, and
        # a scattered chunk that happens to be consecutive
        return [(255, [[27, 26, 25, 24, 23], [1, 2, 3, 27]]),
                (170, [[16, 15, 14, 13, 12, 11, 10, 9, 8, 7, 6], [16]])]
    if case == "newest_block_holds_one":
        return [(248, [[5, 6, 20], [9]]), (64, "dense"), (8, "dense")]
    n = {"rows_64": 64, "rows_512": 512}[case]
    out = []
    for _ in range(n):
        kind = rng.integers(4)
        if kind == 0:
            out.append((0, None))
        elif kind == 1:
            out.append((int(rng.integers(0, 128)), "dense"))
        else:
            p = int(rng.integers(128, 256))
            free = np.arange(1, p // 8 - 3)
            out.append((p, [rng.choice(free, size=min(len(free),
                                                      int(rng.integers(0, 12))),
                                       replace=False).tolist()
                            for _ in range(2)]))
    return out


@pytest.mark.parametrize("case", [
    "dead_row", "shorter_than_a_chunk", "dense_128_blocks",
    "chosen_touch_the_run", "newest_block_holds_one", "rows_64", "rows_512"])
def test_the_kernel_reads_what_the_masked_dense_form_reads(case):
    """``sparse_decode_attention`` (interpret mode: its own copies into the
    ring, semaphores, the fetch cursor that runs ahead across lists) against
    ``_attend_masked`` on the same block masks, through ``block_list``."""
    from flexflow_tpu.ops.pallas.attention import sparse_decode_attention

    op = SparseBlockAttention(64, 4, 2, 16, kernel_size=4, kernel_stride=2,
                              block_size=8, topk=11, window=32,
                              init_blocks=1, dense_len=128)
    assert op.max_blocks == 16
    rng = np.random.default_rng([SEED, sum(map(ord, case))])
    spec = _kernel_case(case, rng)
    t, slots, nb = len(spec), 5, 32
    mask = np.zeros((t, 2, nb), bool)
    pos = np.zeros(t, np.int32)
    for i, (p, chosen) in enumerate(spec):
        pos[i] = p
        if chosen == "dense":
            mask[i, :, :p // 8 + 1] = True
        elif chosen is not None:
            for g in range(2):
                mask[i, g, [0] + list(chosen[g])] = True
                mask[i, g, p // 8 - 3:p // 8 + 1] = True
    rows = rng.integers(0, slots, size=t).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(t, 4, 16)), jnp.float32)
    kc, vc = (jnp.asarray(rng.normal(size=(slots + 1, 2, 256, 16)),
                          jnp.float32) for _ in range(2))
    mask, pos, rows = jnp.asarray(mask), jnp.asarray(pos), jnp.asarray(rows)
    blocks, count = op.block_list(mask)
    assert blocks.shape == (t, 2, 16) and int(count.max()) <= 16
    got = sparse_decode_attention(
        q, kc, vc, rows, pos, blocks, count, scale=op.scaling_factor,
        block=8, tail_run=4, unroll=2, interpret=True)
    want = op._attend_masked(q[:, None], kc, vc, rows, pos[:, None],
                             mask[:, None])[:, 0]
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    dead = np.asarray(count.sum(-1) == 0)
    assert (np.asarray(got)[dead] == 0).all()


def test_chunked_lightning_form_equals_the_recurrence():
    """``LightningAttention._chunked`` on a flat batch that holds a fresh
    segment, a segment that continues a stored state, a one-row segment and
    pads — against ``S = lambda S + k' v; o = s q S`` row by row; then the
    batch cut in two at an arbitrary row (a chunk's end inside a segment):
    the second half starts from the states the first stored."""
    op = LightningAttention(64, 4, 16)
    rng = np.random.default_rng([SEED, 90])
    t, h, d, slots = 24, 4, 16, 3
    q, k, v = (jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32)
               for _ in range(3))
    stored = jnp.asarray(rng.normal(size=(slots + 1, h, d, d)), jnp.float32)
    req = [0] * 9 + [2] * 11 + [1] + [-1] * 3
    pos = list(range(9)) + list(range(40, 51)) + [7] + [0] * 3
    lam = np.exp(-np.asarray(lightning_slopes(h)))

    def by_rows(lo, hi, state):
        state, outs = np.array(state), []
        for i in range(lo, hi):
            if req[i] < 0:
                outs.append(np.zeros((h, d)))
                continue
            s0 = 0 if pos[i] == 0 else state[req[i]]
            s1 = lam[:, None, None] * s0 + np.einsum(
                "hd,he->hde", np.asarray(k[i]), np.asarray(v[i]))
            state[req[i]] = s1
            outs.append(np.einsum("hd,hde->he", np.asarray(q[i]), s1)
                        / math.sqrt(d))
        return np.stack(outs), state

    def chunked(lo, hi, state):
        bc = BatchConfig.build([5] * (hi - lo), req[lo:hi], pos[lo:hi],
                               [9, 8, 51], max_tokens=hi - lo,
                               max_requests=slots)
        live = sum(r >= 0 for r in req[lo:hi])
        out, state = op._chunked(q[lo:hi], k[lo:hi], v[lo:hi], state,
                                 Segments(bc, slots))
        return np.asarray(out)[:live], state

    want, want_state = by_rows(0, t, stored)
    got, got_state = chunked(0, t, stored)
    np.testing.assert_allclose(got, want[:21], atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(got_state[:slots], want_state[:slots],
                               atol=2e-5, rtol=1e-5)
    for cut in (5, 14):    # inside the first segment, inside the second
        a, mid = chunked(0, cut, stored)
        b, end = chunked(cut, t, mid)
        np.testing.assert_allclose(np.concatenate([a, b]), want[:21],
                                   atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(end[:slots], want_state[:slots],
                                   atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drives_are_correct(use_pallas):
    """``benchmark/check.py``'s drive (the tiled prefill scan, a flat
    prompt, a joiner spliced by ``join_slot`` between two chained decode
    scans, flat steps on all three rows: row A selects from position 48 on)
    and ``benchmark/longctx.py``'s (two rows decoding together, one across a
    block's end, one across ``dense_len``)."""
    im = deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    ok, _, reading = longctx.run_longctx(
        im, ref, HF, sw.base_key(SEED), "float32", 78, LIMITS, lines.append)
    assert ok, "\n".join(lines)
    assert reading[0] == 1.0 and reading[2] == 2 * 2 * 68
    paths = im.attention_paths
    assert paths.pop(("kv_block_write", "PrefillBatchConfig"), None) == (
        "pallas" if use_pallas else None)
    # the decode scans' K/V rows: ONE aliased call a layer where the kernels
    # are on, the chain of update-slices where they are off
    assert paths.pop(
        ("kv_row_write", "one_row_per_request")) == (
        "pallas" if use_pallas else "dus_chain")
    assert {k for k, _ in paths} == {"sparse_block_attention",
                                     "lightning_attention", "block_select"}
    assert {b: p for (k, b), p in paths.items() if k == "block_select"} == {
        "PrefillBatchConfig": "gathered", "BatchConfig": "gathered",
        "one_row_per_request": "slot_order"}
    assert paths[("lightning_attention", "one_row_per_request")] == \
        "slot_order"
    assert paths[("lightning_attention", "PrefillBatchConfig")] == "chunked"
    sparse = {b: p for (k, b), p in paths.items()
              if k == "sparse_block_attention"}
    if use_pallas:
        assert sparse == {"PrefillBatchConfig": "masked_dense_tile",
                          "BatchConfig": "sparse_decode_attention",
                          "one_row_per_request": "sparse_decode_attention"}
    else:
        assert set(sparse.values()) == {"xla"}


# readings here: 0.0003 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


def test_a_reused_slot_starts_from_zero_state_and_an_empty_index():
    """A slot that served a long request (a full index, a grown state) then
    serves a short one, fed in chunks and decoded past ``dense_len``: it
    reads what it would alone."""
    im = deployment()
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(170, salt=21), [CAP], seq_lens)
    short = tokens(75, salt=22)
    want, _ = reference_logprobs(short)
    seq_lens[2] = 0
    got = feed_flat(im, 2, short, [10], seq_lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def _newest_blocks(self, q, idx, pos):
    last = (pos // self.block_size)[:, None]
    b = jnp.arange(idx.shape[2] * self.kernel_stride // self.block_size)
    keep = (b <= last) & (b > last - self.max_blocks)
    return jnp.broadcast_to(keep[:, None], (q.shape[0], self.num_kv_heads,
                                            b.shape[0]))


@pytest.mark.parametrize("broken", ["newest_blocks_instead_of_the_selection",
                                    "index_not_appended_in_the_decode_scan",
                                    "decay_dropped",
                                    "scale_depth_from_the_cut_depth"])
def test_a_break_is_seen(broken, monkeypatch):
    """Each way of getting the new state wrong moves the logits by far more
    than the tolerance the other tests hold: what they pass, a broken
    program would not."""
    hf = HF
    if broken.startswith("newest"):
        monkeypatch.setattr(SparseBlockAttention, "select", _newest_blocks)
    elif broken.startswith("index"):
        append = SparseBlockAttention._append_index
        monkeypatch.setattr(
            SparseBlockAttention, "_append_index",
            lambda self, kidx, kc, rows, pos: kidx if rows.shape[0] == SLOTS
            else append(self, kidx, kc, rows, pos))
    elif broken.startswith("decay"):
        monkeypatch.setattr(hybrid_ops, "lightning_slopes",
                            lambda h: jnp.zeros((h,), jnp.float32))
    else:
        hf = {**HF, "mup_denominator": None}
    im = seeded(build(hf=hf))
    prompt = tokens(40, salt=50)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 39)], seq_lens)
    made = decode_scan(im, 0, int(toks[0]), 40, 64)
    full = prompt + [int(toks[0])] + made
    want, _ = reference_logprobs(full)
    seq_lens[0] = 104
    (got,), _ = flat_step(im, [(0, [full[104]], 104)], seq_lens)
    assert np.abs(got[0] - want[104]).max() > 20 * TOL, broken


def test_bytes_per_slot_and_per_token_against_the_hand_formula():
    """A sparse layer: K and V of 2 heads x 16 at every position and an
    index entry of 2 x 16 every 2 positions; a lightning layer: 4 heads of
    16 x 16 float32 whatever ``max_seq_len``."""
    def bytes_at(seq):
        im = build(seq=seq)
        im.allocate_kv_cache()
        return im.kv.bytes_per_slot(), im.kv.bytes_per_token()

    short, tok_short = bytes_at(256)
    long, tok_long = bytes_at(2048)
    spread = (SLOTS + 1) / SLOTS       # the scratch row, over the real slots
    entry, layers = 2 * 16 * 4, 2
    assert short["kv_full"] == layers * 256 * 2 * entry * spread
    assert long["kv_full"] == layers * 2048 * 2 * entry * spread
    assert short["kv_index"] == layers * 128 * entry * spread
    assert long["kv_index"] == layers * 1024 * entry * spread
    assert short["linear_state"] == long["linear_state"] == \
        2 * 4 * 16 * 16 * 4 * spread
    assert short["recurrent"] == short["kv_window"] == \
        short["kv_compact"] == 0
    # a position's price: its K and V entry and half an index entry a layer
    assert tok_short == tok_long == layers * (2 * entry + entry / 2) * spread


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=32), "index of compressed keys"),
    (dict(kv_dtype="int8"), "compressed keys choose"),
    (dict(max_spec_tokens=7), "appended index entry"),
    (dict(tp=2), "selection per K/V head"),
    (dict(pp=2), "stage boundaries"),
])
def test_combinations_not_written_yet_raise_at_compile(kw, needs):
    kw = dict(kw)
    tp, pp = kw.pop("tp", 1), kw.pop("pp", 1)
    axes = {"pp": pp, "tp": tp} if pp > 1 else {"tp": tp}
    mesh = make_mesh(axes, jax.devices()[:tp * pp])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(HF), CAP)
    with pytest.raises(ValueError, match=needs):
        if pp > 1:
            from flexflow_tpu.serve.pp import PipelinedInferenceManager

            PipelinedInferenceManager(ff, max_requests=SLOTS,
                                      max_tokens_per_batch=CAP,
                                      max_seq_len=SEQ)
        else:
            InferenceManager(ff, max_requests=SLOTS, max_tokens_per_batch=CAP,
                             max_seq_len=SEQ, **kw).allocate_kv_cache()


def test_spans_counters_and_the_ledger_name_the_new_state():
    """Through ``RequestManager.generate``: the dispatch spans carry the
    blocks the rows will read and the compressed keys they choose by, the
    counters add up what every position read and wrote, the memory ledger
    prices both new kinds, and the paths taken are counted."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment()
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        outs = rm.generate([tokens(50, salt=31), tokens(9, salt=32)], 40)
        assert [len(o) for o in outs] == [40, 40]
        im.publish_memory(tel)
        measured = tel.memory.report()["plans"][im.plan_key]
        per_slot = im.kv.bytes_per_slot()
        assert measured["slot_kv_index_bytes"]["measured"] == \
            per_slot["kv_index"] > 0
        assert measured["slot_linear_state_bytes"]["measured"] == \
            per_slot["linear_state"] > 0
        counters = tel.metrics.snapshot()
        assert counters["attention_path.sparse_block_attention.xla"] >= 1
        assert counters["attention_path.lightning_attention.slot_order"] >= 1
        assert counters["attention_path.lightning_attention.chunked"] >= 1
        # the selection by the batch's form: rows and slots coincide in
        # the decode scan's programs alone
        assert im.attention_paths[
            ("block_select", "one_row_per_request")] == "slot_order"
        assert im.attention_paths[("block_select", "BatchConfig")] == \
            "gathered"
        assert counters["attention_path.block_select.slot_order"] == 1
        assert counters["attention_path.block_select.gathered"] >= 1
        assert counters["linear.state_resets"] == 2 * 2
        op = _sparse_op()
        written = [range(0, 50 + 39), range(0, 9 + 39)]
        assert counters["sparse.blocks_attended"] == 2 * sum(
            op.attended_blocks(p) for r in written for p in r)
        # the forced window (2 blocks) of a row that selects, the whole
        # pairs of a row that attends every block: the kernel's run copies
        assert [op.run_blocks(p) for p in (0, 7, 8, 23, 47, 48, 200)] == \
            [0, 0, 2, 2, 6, 2, 2]
        assert counters["sparse.window_run_blocks"] == 2 * sum(
            op.run_blocks(p) for r in written for p in r)
        assert 0 < counters["sparse.window_run_blocks"] < \
            counters["sparse.blocks_attended"]
        assert counters["sparse.dense_rows"] == 2 * (48 + 48)
        assert counters["sparse.index_entries_written"] == 2 * (
            op.index_len(88) + op.index_len(47))
        launches = [e["args"] for e in tel.trace.trace_events()
                    if e["name"].endswith("_dispatch")
                    and "attended_blocks_sum" in e.get("args", {})]
        scans = [a for a in launches if a.get("kind") == "decode_scan"]
        assert scans and all(
            0 < a["attended_blocks_sum"] <= 6 * a["rows"]
            and 0 < a["window_run_blocks_sum"] <= a["attended_blocks_sum"]
            and a["index_len_sum"] < a["ctx_sum"] // 2 for a in scans)
    finally:
        im.telemetry = type(im).telemetry


CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_published_config_builds_the_published_model():
    """``from_hf_config`` on the published keys: 8 sparse layers of 253.8 M
    parameters and 24 lightning layers of 285.2 M where ``mixer_types`` says,
    RoPE on the lightning layers alone, the muP scalings with the PUBLISHED
    depth; the benchmark's configuration file holds the catalog row's keys
    unchanged but the two it lists as reduced, and its slice of
    ``mixer_types`` is the published list's entries 9..16."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "minicpm-sala-d8.json")) as f:
        conf = json.load(f)
    reduced = conf["benchmark"]["reduced"]
    assert set(reduced) == {"num_hidden_layers", "mixer_types"}
    kinds = (["minicpm4"] + ["lightning-attn"] * 8 + ["minicpm4"]
             + ["lightning-attn"] * 6 + ["minicpm4"] * 2
             + ["lightning-attn"] * 4 + ["minicpm4"]
             + ["lightning-attn"] * 6 + ["minicpm4"] * 3)
    assert len(kinds) == 32 and kinds.count("minicpm4") == 8
    assert conf["mixer_types"] == kinds[9:17] and conf["num_hidden_layers"] == 8
    if os.path.exists(CATALOG_FILE):
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert row["config"]["mixer_types"] == kinds
        assert {k: conf[k] for k in row["config"] if k not in reduced} == \
            {k: v for k, v in row["config"].items() if k not in reduced}
    published = {k: v for k, v in conf.items() if k != "benchmark"}
    cfg = ServeModelConfig.from_hf_config(
        {**published, "num_hidden_layers": 32, "mixer_types": kinds})
    assert builder.residual_scale(cfg) == 1.4 / math.sqrt(32)
    assert builder.residual_scale(ServeModelConfig.from_hf_config(
        published)) == 1.4 / math.sqrt(32)
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, cfg, 16)
    size = lambda i: sum(math.prod(p.spec.shape) for n in ff.graph.nodes
                         for p in n.op.params() if f".layers.{i}." in n.name)
    mlp = 3 * 4096 * 16384 + 2 * 4096
    assert size(9) == 3 * 4096 ** 2 + 2 * 4096 * 256 + mlp
    assert size(10) == 5 * 4096 ** 2 + mlp + 2 * 128 + 4096
    ops = [n.op for n in ff.graph.nodes
           if isinstance(n.op, (SparseBlockAttention, LightningAttention))]
    assert [isinstance(o, SparseBlockAttention) for o in ops] == \
        [k == "minicpm4" for k in kinds]
    sparse = ops[0]
    assert (sparse.num_q_heads, sparse.num_kv_heads, sparse.head_dim,
            sparse.max_blocks, sparse.output_gate) == (32, 2, 128, 128, True)
    assert ops[1].use_rope and ops[1].qk_norm and ops[1].output_norm
    by_name = {n.name: n.op for n in ff.graph.nodes}
    assert by_name["model.embed_tokens.scale"].scalar == 12
    assert by_name["model.norm.scale"].scalar == 256 / 4096
    with pytest.raises(ValueError, match="mixer_types"):
        build_model(FFModel(FFConfig(), mesh=make_mesh(
            {"tp": 1}, jax.devices()[:1])), ServeModelConfig.from_hf_config(
                {**published, "mixer_types": kinds[:3]}), 16)


def test_row_write_kernel_on_and_off_serves_the_same(row_write_on_and_off):
    """The decode scan's K/V rows by ``kv_row_write`` and by the chain it
    replaced — the sparse layers' caches (the index append stays as it is): the same tokens, the same caches."""
    row_write_on_and_off(lambda: seeded(build(use_pallas=True)),
                         [tokens(40, salt=51), tokens(9, salt=52)])
