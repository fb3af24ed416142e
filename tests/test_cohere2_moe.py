"""Command A+ (``cohere2_moe``: a parallel block of attention and a mixture
of gated experts; three sliding-window rotary layers whose cache is a RING to
one full layer without a positional term) through the normal serve path,
against the plain reference ``benchmark/reference/cohere2_moe.py`` — logits,
not tokens.

Toy widths, the real mechanisms: hidden 64; 2 query heads on 1 K/V head of 16
(share 0 of 8 of 16 on 8); a window of 48 in a ring of 128 slots; a router
over 32 experts with top-8 of which this graph HOLDS 4 (share 0 of 8: one
pair a row on average, as at the published sizes), gated experts of width 32,
4 shared experts averaged; 4 layers ``SSSF``; the head tied.  Weights are the
benchmark's seeded ones in float32 (``seeded_weights.program_params`` also
holds the program's parameter tree to the reference's ``program_tree``, name
by name).

The reference is a masked softmax over the whole sequence and a loop over the
held experts under a 0 / weight mask; the program keeps a ring in three
layers and a full-length cache in the fourth per slot, and sorts (row,
choice) pairs into grouped GEMMs.  float32 on the CPU against float32 at
HIGHEST precision: they differ by summation order alone and a log-probability
agrees to 2e-4 nats — half-split rotary, a dropped window bound, rotary on
the full layer, shared experts summed or an un-normalised top-8 move it by
4e-3 or more (``test_a_break_is_seen`` holds that).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, seeded_weights as sw  # noqa: E402
from benchmark.reference import cohere2_moe as ref  # noqa: E402
from flexflow_tpu.core.op import OpContext  # noqa: E402
from flexflow_tpu.ops.pallas.attention import (  # noqa: E402
    decode_attention,
    prefill_attention,
)
from flexflow_tpu.serve import hybrid_ops, ops as serve_ops  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import (  # noqa: E402
    SlidingWindowAttention,
    SlotCacheAttention,
)
from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention  # noqa: E402
from flexflow_tpu.serve.ssd_moe_ops import (  # noqa: E402
    MoECombine,
    MoEDispatch,
    MoEExperts,
    MoERouter,
    SharedExpertLinear,
)

from reference_rig import Rig  # noqa: E402

WINDOW = 48
HF = dict(model_type="cohere2_moe", vocab_size=320, hidden_size=64,
          num_hidden_layers=4,
          layer_types=["sliding_attention"] * 3 + ["full_attention"],
          num_attention_heads=2, num_key_value_heads=1, head_dim=16,
          sliding_window=WINDOW, rope_theta=50000,
          position_embedding_type="rope_gptj", use_parallel_block=True,
          first_k_dense_replace=0, intermediate_size=32, num_experts=4,
          router_num_experts=32, expert_share_index=0, expert_share_count=8,
          num_experts_per_tok=8, num_shared_experts=4,
          shared_expert_combination_strategy="average", norm_topk_prob=True,
          layer_norm_eps=1e-5, logit_scale=1, tie_word_embeddings=True,
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 4096
          init_std=0.125, torch_dtype="float32")
# a chunk is 48 rows = 3 tiles of 16, the ring 128 slots = 8 tiles: the
# chunk that starts at 96 WRAPS the ring inside itself (tiles at 96, 112, 0)
SLOTS, CAP, SEQ = 3, 48, 512
RING = 128
TOL = 2e-4          # nats, see the module docstring
SEED = 4321


# ONE built deployment per kernel setting and process, reset between uses
# (``tests/reference_rig.py``): the seeded weights, the reference's forward
# pass and the three ways the tests drive the program
RIG = Rig(ref, HF, SLOTS, CAP, SEQ, SEED)
build, seeded, deployment = RIG.build, RIG.seeded, RIG.deployment
reference_logprobs, tokens = RIG.reference_logprobs, RIG.tokens
flat_step, feed_flat, decode_scan = (RIG.flat_step, RIG.feed_flat,
                                     RIG.decode_scan)


# 3 windows and a bit: every ring has wrapped, chunk 3 (96..143) wraps it
# inside itself
PROMPT = tokens(3 * WINDOW + 26)


@pytest.mark.parametrize("how", ["uneven_chunks", "tiled_scan",
                                 "tiled_scan_pallas", "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt, 3.5 windows long, in uneven flat chunks (each row
    reads its slot's ring at ``position % ring``) and through the tiled
    prefill scan (block writes into the ring, a chunk that wraps it, the
    prefill kernel's window bound), kernels off and on: decode steps then
    read what each left, across the ring's end."""
    want, want_tok = reference_logprobs(PROMPT + tokens(3, salt=1))
    n = len(PROMPT)
    assert n > 3 * WINDOW and n > RING and 96 < RING < 96 + CAP
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
    paths = im.attention_paths
    pallas = how.endswith("pallas")
    assert {p for (k, _), p in paths.items() if k == "moe_experts"} == {
        "megablox_gmm" if pallas else "ragged_dot"}
    assert paths[("sliding_window_attention", "BatchConfig")] == (
        "decode_attention" if pallas else "xla")
    if how == "tiled_scan_pallas":
        assert paths[("sliding_window_attention", "PrefillBatchConfig")] == \
            "prefill_attention"
        assert paths[("kv_block_write", "PrefillBatchConfig")] == "pallas"


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_scan_carries_what_prefill_leaves(use_pallas):
    """A prompt of 100, then 80 decode steps on the device in chained scans
    (across the ring's end at 128, past three windows): the scan's tokens are
    the reference's greedy ones; flat steps then read, at position 180 on,
    what the scan wrote; and the rings and the cache it left are those the
    same 180 tokens leave when PREFILLED into another slot."""
    im = deployment(use_pallas=use_pallas)
    prompt = tokens(100, salt=5)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 99)], seq_lens)
    first = int(toks[0])
    made = decode_scan(im, 0, first, 100, 80)
    full = prompt + [first] + made                  # 181 tokens
    # teacher forcing: the reference is fed what the program produced
    want, want_tok = reference_logprobs(full + tokens(2, salt=6))
    assert full[100:] == want_tok[99:180].tolist()
    feed_flat(im, 2, full[:180], [CAP], seq_lens)
    seen = set()
    for node, bufs in im.state.items():
        for name in ("k", "v", "wk", "wv"):
            if name not in bufs:
                continue
            a, b = bufs[name][0], bufs[name][2]
            if name in ("k", "v"):
                a, b = a[:, :180], b[:, :180]
            else:
                # the ring holds positions 52..179: those both slots wrote
                # last, whatever they held before
                assert a.shape[1] == RING
            assert float(jnp.abs(a).max()) > 1e-2, (node, name)
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
            seen.add(name)
    assert seen == {"k", "v", "wk", "wv"}
    seq_lens[0] = 180
    for k, tok in enumerate([full[180]] + tokens(2, salt=6)):
        (got,), _ = flat_step(im, [(0, [tok], 180 + k)], seq_lens)
        np.testing.assert_allclose(got[0], want[180 + k], atol=TOL, rtol=0)
    assert im.attention_paths[
        ("sliding_window_attention", "BatchConfig")] == (
            "decode_attention" if use_pallas else "xla")


# readings here: 0.0002 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive: the tiled prefill scan of 380 tokens
    (7.9 windows, 3 rings), a flat prompt, a JOINER spliced by
    ``join_slot`` between two chained decode scans of the other two rows,
    flat steps on all three."""
    im = deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    assert "contexts up to 401" in lines[-1], lines[-1]
    # the decode scans' K/V rows: ONE aliased call a layer where the kernels
    # are on, the chain of update-slices where they are off
    assert im.attention_paths.pop(
        ("kv_row_write", "one_row_per_request")) == (
        "pallas" if use_pallas else "dus_chain")
    kinds = {k for k, _ in im.attention_paths}
    assert kinds - {"kv_block_write"} == {
        "sliding_window_attention", "moe_experts"} | (
            {"decode_block", "prefill_operands"} if use_pallas else set())


def test_flat_rows_of_several_requests_go_by_segments():
    """One flat step holds the ends of two prompts and a decode row of a
    third request: each row reads ITS slot's ring and cache, and the routed
    layer sorts all their pairs together."""
    im = deployment()
    a, b, c = tokens(70, salt=11), tokens(12, salt=12), tokens(9, salt=13)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, a[:64], [CAP], seq_lens)
    feed_flat(im, 1, b[:7], [CAP], seq_lens)
    feed_flat(im, 2, c[:8], [CAP], seq_lens)
    got, _ = flat_step(im, [(1, b[7:], 7), (2, c[8:], 8), (0, a[64:], 64)],
                       seq_lens)
    for lp, ids, at in zip(got, (b, c, a), (7, 8, 64)):
        want, _ = reference_logprobs(ids)
        np.testing.assert_allclose(lp, want[at:], atol=TOL, rtol=0)


def test_a_reused_slot_reads_nothing_of_the_request_before():
    """A slot that served a long request (a ring that wrapped, a cache) then
    serves a short one: positions below the window's start and ring slots
    the new request has not written are masked, not read."""
    im = deployment()
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(200, salt=21), [CAP], seq_lens)
    short = tokens(25, salt=22)
    seq_lens[2] = 0
    feed_flat(im, 2, short[:-1], [10], seq_lens)
    _, toks = flat_step(im, [(2, short[-1:], 24)], seq_lens)
    made = decode_scan(im, 2, int(toks[0]), 25, 8)
    full = short + [int(toks[0])] + made
    _, want_tok = reference_logprobs(full)
    assert full[25:] == want_tok[24:33].tolist()


# ---- scratch breaks: each must move the logits far past the tolerance -----
def _break(broken, monkeypatch):
    """Break the PROGRAM (the reference stays the published model); returns
    the program's configuration and a hook run on the built graph."""
    hf, after = dict(HF), lambda im: None
    if broken == "half_split_rotary":
        monkeypatch.setattr(
            hybrid_ops, "apply_rope",
            lambda x, pos, theta, interleaved=False:
                serve_ops.apply_rope(x, pos, theta))
    elif broken == "window_bound_dropped":
        sound = SlotCacheAttention._attend_xla

        def whole_ring(self, q, kc, vc, rows, pos):
            window, self.window = self.window, kc.shape[2]
            try:
                return sound(self, q, kc, vc, rows, pos)
            finally:
                self.window = window

        monkeypatch.setattr(SlotCacheAttention, "_attend_xla", whole_ring)
    elif broken == "rotary_on_the_full_layer":
        def after(im):
            full = [n.op for n in im.model.graph.nodes
                    if isinstance(n.op, IncMultiHeadSelfAttention)]
            assert len(full) == 1
            full[0].rotary_embedding = True
            full[0].rope_theta = float(HF["rope_theta"])
    elif broken == "shared_experts_summed":
        hf["shared_expert_combination_strategy"] = "sum"
    elif broken == "top_8_not_normalised":
        hf["norm_topk_prob"] = False
    else:
        raise ValueError(broken)
    return hf, after


BREAKS = ["half_split_rotary", "window_bound_dropped",
          "rotary_on_the_full_layer", "shared_experts_summed",
          "top_8_not_normalised"]


@pytest.mark.parametrize("broken", BREAKS)
def test_a_break_is_seen(broken, monkeypatch):
    """Each way of getting the new mechanisms wrong moves the logits by far
    more than the tolerance the other tests hold: what they pass, a broken
    program would not."""
    hf, after = _break(broken, monkeypatch)
    im = build(hf=hf)
    after(im)
    seeded(im)
    prompt = tokens(150, salt=50)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 149)], seq_lens)
    made = decode_scan(im, 0, int(toks[0]), 150, 10)
    full = prompt + [int(toks[0])] + made
    want, _ = reference_logprobs(full)
    seq_lens[0] = 160
    (got,), _ = flat_step(im, [(0, [full[160]], 160)], seq_lens)
    assert np.abs(got[0] - want[160]).max() > 20 * TOL, broken


# ---- the kernels' ring paths alone -----------------------------------------
def _ring_oracle(q, kc, vc, rows, pos, window, scale):
    """``q [T, QH, D]`` at positions ``pos`` against the ring rows: numpy
    float64, slot by slot."""
    kc, vc, q = (np.asarray(a, np.float64) for a in (kc, vc, q))
    s_len, kv = kc.shape[2], kc.shape[1]
    out = np.zeros_like(q)
    for t in range(q.shape[0]):
        p = int(pos[t])
        seen = [j for j in range(max(0, p - window + 1), p + 1)]
        slots = [j % s_len for j in seen]
        for h in range(q.shape[1]):
            g = h // (q.shape[1] // kv)
            sc = kc[rows[t], g, slots] @ q[t, h] * scale
            w = np.exp(sc - sc.max())
            out[t, h] = (w / w.sum()) @ vc[rows[t], g, slots]
    return out


@pytest.mark.parametrize("start", [0, 32, 112, 240, 1000],
                         ids=lambda s: f"chunk_at_{s}")
def test_prefill_kernel_with_the_window_bound_equals_a_slot_by_slot_loop(
        start):
    """``prefill_attention(window=...)`` (interpreted) on a ring of 256
    slots, window 96, a chunk of 3 tiles of 16 at ``start``: before the
    window fills, inside it, across the ring's end (240..287 wraps at 256)
    and many rings on.  The ring holds the chunk's own keys AND a later
    tile's (written before any tile attends): none of those is seen."""
    rng = np.random.default_rng([SEED, start])
    s_len, window, tile, tiles, kv, gq, d = 256, 96, 16, 3, 2, 2, 128
    # what every position up to the chunk's end would have left in the ring
    upto = start + tiles * tile
    keys = rng.normal(size=(upto, kv, d)).astype(np.float32)
    vals = rng.normal(size=(upto, kv, d)).astype(np.float32)
    kc = np.zeros((3, kv, s_len, d), np.float32)
    vc = np.zeros_like(kc)
    for p in range(upto):       # ascending: the newest on a slot stays
        kc[1, :, p % s_len], vc[1, :, p % s_len] = keys[p], vals[p]
    q = rng.normal(size=(tiles, tile, kv * gq, d)).astype(np.float32)
    rows = np.full(tiles, 1, np.int32)
    pstart = start + tile * np.arange(tiles, dtype=np.int32)
    got = prefill_attention(jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
                            jnp.asarray(rows), jnp.asarray(pstart),
                            scale=0.09, interpret=True, window=window)
    pos = (pstart[:, None] + np.arange(tile)).reshape(-1)
    # the oracle reads positions, not slots: rebuild a cache by position
    by_pos_k = np.moveaxis(keys, 0, 1)[None]
    by_pos_v = np.moveaxis(vals, 0, 1)[None]
    want = _ring_oracle(q.reshape(-1, kv * gq, d), by_pos_k, by_pos_v,
                        np.zeros(len(pos), np.int32), pos, window, 0.09)
    # (positions as slots of a "ring" as long as the sequence: no wrap)
    np.testing.assert_allclose(np.asarray(got).reshape(want.shape), want,
                               atol=2e-5, rtol=1e-5)


def test_the_prefill_kernel_skips_the_blocks_before_the_window():
    """The window's LOWER bound is in the kernel's index map and body: with
    the ring's unseen blocks filled with NaN the result is still finite and
    the same — a masked block would have let the NaN through the
    contraction."""
    rng = np.random.default_rng([SEED, 7])
    s_len, window, tile, kv, gq, d = 1024, 128, 16, 1, 2, 128
    start = 640                      # sees 513..655: slots 512..767 at most
    kc = rng.normal(size=(2, kv, s_len, d)).astype(np.float32)
    vc = rng.normal(size=(2, kv, s_len, d)).astype(np.float32)
    q = rng.normal(size=(1, tile, kv * gq, d)).astype(np.float32)
    args = (jnp.asarray([0], jnp.int32), jnp.asarray([start], jnp.int32))
    call = lambda k, v: np.asarray(prefill_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), *args, scale=0.09,
        interpret=True, window=window, block_s=128))
    want = call(kc, vc)
    holed_k, holed_v = kc.copy(), vc.copy()
    for a in (holed_k, holed_v):
        a[:, :, :512] = np.nan       # blocks 0..3: wholly before the window
        a[:, :, 768:] = np.nan       # blocks 6, 7: past the tile's end
    got = call(holed_k, holed_v)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


def _ring_rows(rng, pos, s_len, kv, d, dtype=np.float32):
    """One ring row a position of ``pos``, holding what positions
    ``0 .. pos`` leave in it (the newest on a slot stays), and the keys and
    values by position ``[1, KV, P, D]`` for the oracle."""
    upto = int(max(pos)) + 1
    # float32 values that the cache's type holds exactly
    keys, vals = (np.asarray(jnp.asarray(
        rng.normal(size=(upto, kv, d)), dtype).astype(jnp.float32))
        for _ in range(2))
    kc = np.zeros((len(pos) + 1, kv, s_len, d), np.float32)
    vc = np.zeros_like(kc)
    for r, p in enumerate(pos):
        live = np.arange(max(0, p - s_len + 1), p + 1)
        kc[r][:, live % s_len] = np.moveaxis(keys[live], 0, 1)
        vc[r][:, live % s_len] = np.moveaxis(vals[live], 0, 1)
    return (kc, vc, np.moveaxis(keys, 0, 1)[None],
            np.moveaxis(vals, 0, 1)[None])


@pytest.mark.parametrize("s_len,window,kv,gq,cache_dt,forced", [
    # PR 50's case (ring 1536, window 1024, float32: 512 bytes of K a slot):
    # the ring is one block now
    (1536, 1024, 1, 2, "float32", None),
    # the cell's ring: 16 query heads on ONE K/V head in bf16, a window of
    # 4096 in 4608 slots - whole, and in blocks that the window straddles
    (4608, 4096, 1, 16, "bfloat16", None),
    (4608, 4096, 1, 16, "bfloat16", 2304),
    (4608, 4096, 1, 16, "bfloat16", 1536),
    (2304, 2048, 2, 8, "bfloat16", None),
    (2304, 2048, 2, 8, "bfloat16", 1152),
])
def test_decode_kernel_reads_a_narrow_ring_whole(
        monkeypatch, s_len, window, kv, gq, cache_dt, forced):
    """``decode_attention``'s ring path on one or two K/V heads of 128:
    equal to the slot-by-slot loop before the window fills, with the window
    inside ONE copied block, on both sides of the window's filling, of the
    ring's end and of the wrap, and rings later.  A slot that the window
    does not hold is NaN wherever its whole block lies outside the window:
    skipped blocks are not read."""
    from flexflow_tpu.ops.pallas import attention

    d = 128
    block = forced or attention._decode_plan(
        kv, d, jnp.dtype(cache_dt).itemsize, False, s_len, window)
    assert forced is not None or block == s_len
    part = block // 3
    rng = np.random.default_rng([SEED, 8, s_len, block])
    pos = np.asarray([5, part - 1, part + 40, window - 1, window, s_len - 1,
                      s_len, s_len + part // 2, s_len + block + 3,
                      2 * s_len + window // 2 + 1], np.int32)
    kc, vc, by_pos_k, by_pos_v = _ring_rows(rng, pos, s_len, kv, d, cache_dt)
    # outside the window, in blocks that hold no slot of it
    for r, p in enumerate(pos):
        held = np.zeros(s_len, bool)
        held[np.arange(max(0, p - window + 1), p + 1) % s_len] = True
        dead = ~held.reshape(-1, block).any(axis=1).repeat(block)
        kc[r][:, dead] = vc[r][:, dead] = np.nan
    q = rng.normal(size=(len(pos), kv * gq, d)).astype(np.float32)
    rows = np.arange(len(pos), dtype=np.int32)
    if forced is not None:
        monkeypatch.setattr(attention, "_decode_plan",
                            lambda *a, **k: forced)
    got = np.asarray(decode_attention.__wrapped__(
        jnp.asarray(q), jnp.asarray(kc, cache_dt), jnp.asarray(vc, cache_dt),
        jnp.asarray(rows), jnp.asarray(pos), scale=0.09, interpret=True,
        window=window))
    assert np.isfinite(got).all()
    want = _ring_oracle(q, np.broadcast_to(by_pos_k, (len(pos),) +
                                           by_pos_k.shape[1:]),
                        np.broadcast_to(by_pos_v, (len(pos),) +
                                        by_pos_v.shape[1:]),
                        rows, pos, window, 0.09)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_interleaved_rotary_by_hand():
    """``apply_rope(interleaved=True)``: entries ``(2 i, 2 i + 1)`` turn by
    ``t * theta^(-2 i / D)``; the default turns ``(i, i + D / 2)`` by the
    same angles — the same rotation of a permuted vector."""
    rng = np.random.default_rng([SEED, 9])
    x = rng.normal(size=(5, 3, 8)).astype(np.float32)
    pos = np.asarray([0, 1, 7, 100, 5000], np.int32)
    theta = 50000.0
    got = np.asarray(serve_ops.apply_rope(jnp.asarray(x), jnp.asarray(pos),
                                          theta, interleaved=True))
    want = np.empty_like(x, np.float64)
    for t, p in enumerate(pos):
        for i in range(4):
            ang = float(p) * theta ** (-2 * i / 8)
            a, b = x[t, :, 2 * i].astype(np.float64), x[t, :, 2 * i + 1]
            want[t, :, 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[t, :, 2 * i + 1] = b * np.cos(ang) + a * np.sin(ang)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=1e-4)
    perm = np.asarray([0, 2, 4, 6, 1, 3, 5, 7])
    split = np.asarray(serve_ops.apply_rope(jnp.asarray(x[..., perm]),
                                            jnp.asarray(pos), theta))
    np.testing.assert_allclose(split, got[..., perm], atol=1e-5)
    assert np.abs(got[1:] - np.asarray(serve_ops.apply_rope(
        jnp.asarray(x), jnp.asarray(pos), theta))[1:]).max() > 0.1


# ---- the routed layer alone ------------------------------------------------
def _ctx(extras=None, name="n"):
    return OpContext(extras={"node_name": name, **(extras or {})})


def routed_layer(x, router, gate, up, down, held_lo, top_k=8, extras=None):
    """The four ops in the graph's order, called directly."""
    d, scored = router.shape
    held = up.shape[0]
    ex = dict(extras or {})
    ids, w = MoERouter(d, scored, top_k, bias=False).lower(
        _ctx(ex), [x], {"weight": router})
    xs, sizes, order = MoEDispatch(held, held_lo).lower(_ctx(ex), [x, ids],
                                                        {})
    ys = MoEExperts(held, d, up.shape[2], form="swiglu").lower(
        _ctx(ex), [xs, sizes], {"gate": gate, "up": up, "down": down})[0]
    out = MoECombine(held, held_lo).lower(_ctx(ex), [ys, order, ids, w],
                                          {})[0]
    return np.asarray(out), np.asarray(ids), np.asarray(w)


def per_row_loop(x, router, gate, up, down, held_lo, top_k=8):
    """The routed layer as a loop over rows and their choices, in numpy
    float64: plain sigmoid top-k (ties to the lower id), normalised."""
    x, router, gate, up, down = (np.asarray(a, np.float64)
                                 for a in (x, router, gate, up, down))
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        s = 1.0 / (1.0 + np.exp(-(row @ router)))
        chosen = np.argsort(-s, kind="stable")[:top_k]
        w = s[chosen] / s[chosen].sum()
        for e, we in zip(chosen, w):
            if held_lo <= e < held_lo + up.shape[0]:
                g = row @ gate[e - held_lo]
                h = g / (1.0 + np.exp(-g)) * (row @ up[e - held_lo])
                out[t] += we * (h @ down[e - held_lo])
    return out


def _layer_weights(salt, d=32, scored=32, held=4, f=24):
    rng = np.random.default_rng([SEED, salt])
    n = lambda *s: rng.normal(size=s).astype(np.float32)
    return (n(d, scored) / np.sqrt(d), n(held, d, f) / np.sqrt(d),
            n(held, d, f) / np.sqrt(d), n(held, f, d) / np.sqrt(f))


@pytest.mark.parametrize("case", ["random", "one_row", "upper_share",
                                  "chunk_of_512", "kernel"])
def test_gated_routed_layer_equals_a_per_row_loop(case):
    """Plain normalised sigmoid top-8 (no bias: the router has no such
    parameter), gate and up as grouped GEMMs over the same sorted rows,
    ``silu(gate) * up``, down — ``ragged_dot`` and Megablox's kernel
    (interpreted, at widths its tiles divide)."""
    wide = case == "kernel"
    router, gate, up, down = _layer_weights(
        3, **(dict(d=128, f=128) if wide else {}))
    rng = np.random.default_rng([SEED, 99])
    rows = {"one_row": 1, "chunk_of_512": 512}.get(case, 40)
    x = rng.normal(size=(rows, router.shape[0])).astype(np.float32)
    held_lo = 12 if case == "upper_share" else 0
    extras = ({"pallas_decode": True, "pallas_interpret": True}
              if wide else None)
    got, ids, w = routed_layer(x, router, gate, up, down, held_lo,
                               extras=extras)
    np.testing.assert_allclose(
        got, per_row_loop(x, router, gate, up, down, held_lo),
        atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    assert [p.name for p in MoERouter(32, 32, 8, bias=False).params()] == \
        ["weight"]
    assert [p.name for p in MoEExperts(4, 32, 24, form="swiglu").params()] \
        == ["gate", "up", "down"]


def test_relu2_experts_keep_their_parameters_and_tiles():
    """The form the routed layer had (nemotron_h's): two tensors, the tiles
    it compiled with; an unknown form is refused."""
    op = MoEExperts(4, 2688, 1856)
    assert op.form == "relu2"
    assert [p.name for p in op.params()] == ["up", "down"]
    # since PR 54 the tiles come from the shapes and the VMEM budget
    # (MoEExperts.out_tile): the two committed shapes keep what they had
    assert [op.out_tile(2688, 1856, 2), op.out_tile(1856, 2688, 2)] == \
        [640, 896]
    assert [MoEExperts.out_tile(4096, 4096, 2)] * 2 == [512, 512]
    with pytest.raises(ValueError, match="form"):
        MoEExperts(4, 32, 24, form="geglu")


# ---- the share ---------------------------------------------------------------
UNCUT = dict(HF, num_attention_heads=16, num_key_value_heads=8,
             num_experts=32, router_num_experts=32, expert_share_index=0,
             expert_share_count=1)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_eight_shares_add_up_to_the_uncut_layer(kind):
    """The 8 shares of ONE layer — each share's 2 query heads on its K/V
    head through its rows of W_o, each share's 4 experts (through the
    program's routed ops, told which ids they hold) — with the shared
    experts and the residual counted ONCE, against the reference's UNCUT
    layer (16 heads on 8, 32 experts held of 32)."""
    layer_i = ref.layer_kinds(HF).index(kind)
    key = sw.base_key(SEED)
    w = sw.draw_table(key, layer_i, ref.LAYER, UNCUT, "float32")
    rng = np.random.default_rng([SEED, 97])
    x = jnp.asarray(rng.normal(size=(1, 3 * WINDOW, 64)).astype(np.float32))
    want = np.asarray(ref.layer(UNCUT, w, ref.Stream(x, jnp.int32(layer_i)))
                      .h)[0]
    n = ref.layer_norm(x, w["input_layernorm.weight"], 1e-5)
    sliding = jnp.asarray(kind == "sliding_attention")
    total = np.asarray(x + ref.shared_experts(UNCUT, w, n))[0]
    for share in range(8):
        hf_s, w_s = ref.share_of(UNCUT, w, share, 8)
        assert ref.held_experts(hf_s) == (4 * share, 4)
        assert ref.attention_shape(hf_s) == (2, 1, 16)
        total = total + np.asarray(ref.attention(hf_s, w_s, n, sliding))[0]
        part, *_ = routed_layer(
            n[0], w["mlp.gate.weight"], w_s["mlp.experts.gate_proj"],
            w_s["mlp.experts.up_proj"], w_s["mlp.experts.down_proj"],
            4 * share)
        total = total + part
        # the reference's own share: the same cut, through ``hf``
        ids, wts = ref.route(hf_s, w_s, n)
        np.testing.assert_allclose(
            part, np.asarray(ref.routed_experts(hf_s, w_s, n, ids, wts))[0],
            atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-4, rtol=1e-5)


def test_the_program_serves_an_upper_share():
    """Share 5 of 8 (experts 20..23) through the program, against the
    reference given the same share: ``expert_share_index`` reaches the
    dispatch and the combine."""
    hf = dict(HF, expert_share_index=5, num_hidden_layers=2,
              layer_types=["sliding_attention", "full_attention"])
    im = seeded(build(hf=hf), hf=hf)
    ids = tokens(60, salt=31)
    got = feed_flat(im, 0, ids, [CAP], [0] * SLOTS)
    want, _ = reference_logprobs(ids, hf=hf)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    lower, _ = reference_logprobs(ids, hf=dict(hf, expert_share_index=0))
    assert np.abs(lower - want).max() > 20 * TOL


# ---- bytes, refusals, spans ----------------------------------------------------
def test_a_slots_bytes_are_a_fixed_part_and_a_part_per_position():
    """The allocator against the hand formula: three rings of 128 slots and
    one cache of 512 positions, one K/V head of 16 float32 each; admission
    prices a request as fixed + positions x per-position."""
    im = deployment()
    ring = 3 * 2 * RING * 16 * 4
    per_pos = 2 * 16 * 4 * (SLOTS + 1) / SLOTS    # the scratch row shared
    per_slot = im.kv.bytes_per_slot()
    near = lambda x: pytest.approx(x, rel=1e-12)
    assert per_slot["kv_window"] == near(ring * (SLOTS + 1) / SLOTS)
    assert per_slot["kv_full"] == near(per_pos * SEQ)
    assert im.kv.bytes_per_token() == near(per_pos)
    assert im.kv.fixed_bytes_per_slot() == per_slot["kv_window"]
    assert im.kv.request_bytes(100) == near(
        per_slot["kv_window"] + 100 * per_pos)
    op = next(n.op for n in im.model.graph.nodes
              if isinstance(n.op, SlidingWindowAttention))
    assert op.ring_len(SEQ) == RING == -(-(WINDOW + CAP) // 128) * 128


def test_admission_counts_the_ring_with_the_positions():
    """Under a byte budget a request is charged its rings too: a budget that
    would hold two requests' positions alone holds one with its rings."""
    from flexflow_tpu.serve.request_manager import (GenerationConfig,
                                                    RequestManager)
    from flexflow_tpu.serve.resilience import ResilienceConfig

    im = deployment()
    need = 40 + 8
    one = im.kv.request_bytes(need)
    positions_only = need * im.kv.bytes_per_token()
    assert one > 2 * positions_only
    rm = RequestManager(
        im, GenerationConfig(max_new_tokens=8, stop_on_eos=False),
        resilience=ResilienceConfig(kv_gate=True,
                                    kv_budget_bytes=1.5 * one))
    first = rm.register_new_request(tokens(40, salt=41))
    second = rm.register_new_request(tokens(40, salt=42))
    assert rm.requests[first].status.name != "REJECTED"
    assert rm.requests[second].status.name == "REJECTED"
    # ... and priced by its positions alone, both would have been admitted
    assert 2 * positions_only < 1.5 * one


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=16), "a ring that wraps"),
    (dict(kv_dtype="int8"), "ring paths take no scale planes"),
    (dict(max_spec_tokens=4), "rolled"),
])
def test_combinations_not_written_yet_raise_at_compile(kw, needs):
    with pytest.raises(ValueError, match="SlidingWindowAttention") as e:
        build(**kw)
    assert needs in str(e.value)


@pytest.mark.parametrize("how", ["tp", "pp"])
def test_sharded_deployments_raise_at_compile(how):
    from flexflow_tpu.serve.inference_manager import \
        refuse_unsupported_slot_state

    im = deployment()
    kw = dict(tp=2) if how == "tp" else dict(pipelined=True)
    with pytest.raises(ValueError) as e:
        refuse_unsupported_slot_state(im.model.graph, **kw)
    text = str(e.value)
    assert "SlidingWindowAttention" in text
    if how == "tp":
        assert "a plain ring's K/V groups" in text
        assert "an exchange of rows" in text
    else:
        assert "pp > 1" in text and "load counters" in text


def test_the_builder_refuses_what_it_does_not_build():
    for change, needs in [
            (dict(layer_types=["sliding_attention"]), "every layer"),
            (dict(layer_types=["sliding_attention"] * 3 + ["linear"]),
             "not an attention"),
            (dict(use_parallel_block=False), "parallel block"),
            (dict(first_k_dense_replace=1), "parallel block"),
            (dict(shared_expert_combination_strategy="max"), "average"),
            (dict(expert_share_index=8), "not among the router's"),
            (dict(sliding_window=None), "sliding_window")]:
        with pytest.raises(ValueError, match=needs):
            build(hf=dict(HF, **change))


def test_the_graph_names_each_mechanism_by_its_class():
    """What a device trace files operations under: three plain ring layers,
    one full-length layer, the routed layer's four classes in every layer,
    the shared experts' three projections apart from the head's Linear."""
    im = deployment()
    names = [type(n.op).__name__ for n in im.model.graph.nodes]
    count = {c: names.count(c) for c in set(names)}
    assert (count["SlidingWindowAttention"],
            count["IncMultiHeadSelfAttention"]) == (3, 1)
    assert all(count[c] == 4 for c in ("MoERouter", "MoEDispatch",
                                       "MoEExperts", "MoECombine"))
    assert count["SharedExpertLinear"] == 12 and count["Linear"] == 1
    assert isinstance(next(n.op for n in im.model.graph.nodes
                           if n.name.endswith("down_proj")),
                      SharedExpertLinear)
    assert im.expert_layers == 4
    # the tied head is the embedding transposed
    np.testing.assert_array_equal(
        np.asarray(im.params["lm_head"]["kernel"]),
        np.asarray(im.params["model.embed_tokens"]["weight"]).T)


def test_logit_scale_multiplies_the_logits():
    hf = dict(HF, logit_scale=0.25, num_hidden_layers=1,
              layer_types=["sliding_attention"])
    im = seeded(build(hf=hf), hf=hf)
    ids = tokens(20, salt=61)
    got = feed_flat(im, 0, ids, [CAP], [0] * SLOTS)
    want, _ = reference_logprobs(ids, hf=hf)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    unscaled, _ = reference_logprobs(ids, hf=dict(hf, logit_scale=1))
    assert np.abs(unscaled - want).max() > 20 * TOL


def test_the_decode_scans_launch_says_what_the_rings_read():
    """``ring_ctx_sum`` beside ``ctx_sum`` on ``decode_scan_dispatch`` and in
    the tick journal: sum of min(context, window) over the rows; the gated
    path keeps the ``commit`` span's expert counts."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment()
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im.take_expert_load()   # earlier tests' scans, which no one read
        # every row under the window: the rings read what the cache does
        rm.generate([tokens(n, salt=70 + n) for n in (5, 6, 7)], 8)
        short = [e["args"] for e in tel.trace.trace_events()
                 if e["name"] == "decode_scan_dispatch"]
        assert short and all(a["ring_ctx_sum"] == a["ctx_sum"] > 0
                             for a in short)
        # every row past it: a window a row, whatever its context
        rm.generate([tokens(n, salt=70 + n) for n in (70, 60, 50)], 24)
        spans = [e["args"] for e in tel.trace.trace_events()
                 if e["name"] == "decode_scan_dispatch"][len(short):]
        assert spans and all(
            a["ring_ctx_sum"] == WINDOW * a["rows"] < a["ctx_sum"]
            for a in spans)
        (ring,) = [n.op for n in im.model.graph.nodes
                   if isinstance(n.op, SlidingWindowAttention)][:1]
        # decode rows at contexts 3, WINDOW and 500 (a scan's rows write
        # several positions; one that writes none reads nothing)
        assert ring.launch_counts(
            [(2, 3), (WINDOW - 1, WINDOW + 7), (499, 500), (40, 40)], None,
            3, True) == ({"ring_ctx_sum": 3 + 2 * WINDOW}, {})
        records = rm.journal.records()
        assert all(r["ring_ctx_sum"] <= r["ctx_sum"] for r in records)
        assert max(r["ring_ctx_sum"] for r in records) > 0
        commits = [e["args"] for e in tel.trace.trace_events()
                   if e["name"] == "commit" and "expert_steps" in e["args"]]
        assert commits
        steps = sum(c["expert_steps"] for c in commits)
        assert steps and steps % 4 == 0
        assert 0 < sum(c["experts_visited"] for c in commits) <= 4 * steps
        assert sum(r["expert_steps"] for r in records) == steps
    finally:
        im.telemetry = type(im).telemetry


def test_the_decode_kernels_block_plans_are_counted():
    """``attention_path.decode_block.<plan>`` once per (layer kind, batch
    class) whose program holds ``decode_attention``: the sliding layers' ring
    plan and the full layer's, in the flat step and in the decode scan."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment(use_pallas=True)
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        rm.generate([tokens(9, salt=91), tokens(60, salt=92)], 6)
        plans = {b: p for (k, b), p in im.attention_paths.items()
                 if k == "decode_block"}
        # the toy's positions are 64 bytes of K: every cache is one block
        assert plans[("sliding_window_attention", "BatchConfig")] == \
            f"ring{RING}"
        full = {b: p for (kind, b), p in plans.items()
                if kind == "inc_multihead_self_attention"}
        assert full == {"BatchConfig": f"full{SEQ}",
                        "one_row_per_request": f"full{SEQ}"}
        counters = tel.metrics.snapshot()
        assert counters[f"attention_path.decode_block.ring{RING}"] == 1
        assert counters[f"attention_path.decode_block.full{SEQ}"] == 2
        assert counters[
            "attention_path.sliding_window_attention.decode_attention"] == 1
    finally:
        im.telemetry = type(im).telemetry


@pytest.mark.parametrize("cache,kw,want", [
    # 32 K/V heads of 128: the block by positions, as before PR 51
    (((17, 32, 2048, 128), jnp.bfloat16), {}, "full256"),
    (((17, 32, 2048, 128), jnp.int8), dict(kv_quant=True), "full256"),
    (((17, 32, 2048, 128), jnp.bfloat16), dict(page_size=512), "full256"),
    (((17, 10, 1024, 128), jnp.bfloat16), dict(window=512), "ring256"),
    # one or two: grown by bytes
    (((129, 1, 18432, 128), jnp.bfloat16), {}, "full2048"),
    (((129, 1, 4608, 128), jnp.bfloat16), dict(window=4096), "ring4608"),
    (((257, 2, 8192, 128), jnp.bfloat16), {}, "full1024"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_layers_block_plan_is_noted_by_its_cache(cache, kw, want):
    paths = {}
    serve_ops.note_decode_block(
        {"attention_paths": paths}, "some_attention", "BatchConfig",
        jax.ShapeDtypeStruct(*cache), **kw)
    assert paths == {("decode_block", ("some_attention", "BatchConfig")): want}
    serve_ops.note_decode_block({}, "some_attention", "BatchConfig",
                                jax.ShapeDtypeStruct(*cache), **kw)


def test_row_write_kernel_on_and_off_serves_the_same(row_write_on_and_off):
    """The decode scan's K/V rows by ``kv_row_write`` and by the chain it
    replaced — rings and the full cache — on the two deployments this module
    already built (kernels on: the aliased call; kernels off: the chain):
    the same tokens, and the same caches off the scratch row to float32's
    rounding (between two writes stands the attention, a kernel on one side
    and XLA on the other: the sums' order differs, the values do not)."""
    prompts = [tokens(40, salt=51), tokens(9, salt=52)]
    on, took_on, state_on = row_write_on_and_off.serve(
        deployment(use_pallas=True), prompts, 6)
    off, took_off, state_off = row_write_on_and_off.serve(
        deployment(use_pallas=False), prompts, 6)
    assert took_on == {"pallas"} and took_off == {"dus_chain"}
    assert on == off and all(len(o) == 6 for o in on)
    for a, b in zip(jax.tree.leaves(state_on), jax.tree.leaves(state_off)):
        np.testing.assert_allclose(a[:-1], b[:-1], atol=2e-5, rtol=0)
