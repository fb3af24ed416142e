"""DeepSeek-V2-Lite (``deepseek_v2``: latent attention over a cache of one
latent and one rotated key part a position, YaRN rotary, one leading dense
layer, then mixtures scored by un-normalised softmax beside summed shared
experts) through the normal serve path, against the plain reference
``benchmark/reference/deepseek_v2.py`` — logits, not tokens.

Toy widths, the real mechanisms: hidden 64; 4 heads of 16 + 8 (values 16) on
a latent of 32 and a rotated key part of 8; YaRN with factor 40 over an
original context of 64, so that the ramp (pairs 1..3 of 4 blend) is live at
every position the tests compare and the contexts run far past it; layer 0
dense (width 96), layers 1 and 2 a softmax router over 8 gated experts of
width 24 with top-3 un-normalised and 2 shared experts summed; the head
untied.  Weights are the benchmark's seeded ones in float32.

The reference expands every head's keys and values from the latents of the
whole sequence (the MATERIALISED form) under a masked softmax; the program
caches the latent alone and reads it ABSORBED.  float32 on the CPU against
float32 at HIGHEST precision: a log-probability agrees to 2e-4 nats — each
break of ``test_a_break_is_seen`` moves it by 4e-3 or more.
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
from benchmark.reference import deepseek_v2 as ref  # noqa: E402
from flexflow_tpu.core.op import OpContext  # noqa: E402
from flexflow_tpu.ops.pallas.attention import decode_attention  # noqa: E402
from flexflow_tpu.serve import hybrid_ops, ops as serve_ops  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import LatentAttention  # noqa: E402
from flexflow_tpu.serve.ssd_moe_ops import MoEExperts, MoERouter  # noqa: E402

from reference_rig import Rig  # noqa: E402

YARN = dict(type="yarn", factor=40, original_max_position_embeddings=64,
            beta_fast=32, beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
HF = dict(model_type="deepseek_v2", vocab_size=320, hidden_size=64,
          num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
          kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
          moe_intermediate_size=24, n_routed_experts=8, n_shared_experts=2,
          num_experts_per_tok=3, first_k_dense_replace=1, moe_layer_freq=1,
          norm_topk_prob=False, scoring_func="softmax",
          topk_method="greedy", n_group=1, topk_group=1,
          routed_scaling_factor=1, rms_norm_eps=1e-6, rope_theta=10000,
          rope_scaling=YARN, attention_bias=False, tie_word_embeddings=False,
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 2048:
          # the router's scores spread as at the published widths
          init_std=0.125, torch_dtype="float32")
LAYERS, RANK, ROPE = 3, 32, 8
SLOTS, CAP, SEQ = 3, 48, 512
TOL = 2e-4          # nats, see the module docstring
SEED = 5454


# ONE built deployment per kernel setting and process, reset between uses
# (``tests/reference_rig.py``): the seeded weights, the reference's forward
# pass and the three ways the tests drive the program
RIG = Rig(ref, HF, SLOTS, CAP, SEQ, SEED)
build, seeded, deployment = RIG.build, RIG.seeded, RIG.deployment
reference_logprobs, tokens = RIG.reference_logprobs, RIG.tokens
flat_step, feed_flat, decode_scan = (RIG.flat_step, RIG.feed_flat,
                                     RIG.decode_scan)


# 2.7 original contexts: the YaRN-scaled rotary far past the toy's 64
PROMPT = tokens(170)


# ---- (a) prompt feeding, decode, a joiner, a reused slot ---------------------
@pytest.mark.parametrize("how", ["uneven_chunks", "tiled_scan",
                                 "tiled_scan_pallas", "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in uneven flat chunks and through the tiled prefill
    scan (block writes of both planes, the absorbed tile path), kernels off
    and on: decode steps then read the latent cache each left, at contexts
    past the original 64 of the toy YaRN scaling."""
    want, want_tok = reference_logprobs(PROMPT + tokens(3, salt=1))
    n = len(PROMPT)
    assert n > 2 * YARN["original_max_position_embeddings"]
    seq_lens = [0] * SLOTS
    pallas = how.endswith("pallas")
    im = deployment(use_pallas=pallas)
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
    assert {p for (k, _), p in paths.items() if k == "moe_experts"} == {
        "megablox_gmm" if pallas else "ragged_dot"}
    assert paths[("latent_attention", "BatchConfig")] == (
        "decode_attention_latent" if pallas else "xla_absorbed")
    if how == "tiled_scan_pallas":
        assert paths[("latent_attention", "PrefillBatchConfig")] == \
            "xla_tile_absorbed"
        assert paths[("kv_block_write", "PrefillBatchConfig")] == "dus_chain"
        assert paths[("decode_block", ("latent_attention", "BatchConfig"))] \
            == "live512"


# readings here: 0.0003 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive: the tiled prefill scan of 380 tokens,
    a flat prompt, a JOINER spliced by ``join_slot`` between two chained
    decode scans of the other two rows, flat steps on all three."""
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
    assert kinds - {"kv_block_write"} == {"latent_attention", "moe_experts"} \
        | ({"decode_block"} if use_pallas else set())


def test_flat_rows_of_several_requests_go_by_segments():
    """One flat step holds the ends of two prompts and a decode row of a
    third request: each row reads ITS slot's latent cache."""
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
    """A slot that served a long request then serves a short one: the
    latents past the new request's frontier are masked, not read."""
    im = deployment()
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(200, salt=21), [CAP], seq_lens)
    short = tokens(25, salt=22)
    want, _ = reference_logprobs(short)
    got = feed_flat(im, 2, short, [11, 3], seq_lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# ---- (b) the decode scan against flat steps ----------------------------------
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_scan_carries_what_prefill_leaves(use_pallas):
    """A prompt of 100, then 40 decode steps on the device in chained scans:
    the scan's tokens are the reference's greedy ones; flat steps then read
    what the scan wrote; and the two planes it left are those the same 140
    tokens leave when PREFILLED into another slot."""
    im = deployment(use_pallas=use_pallas)
    prompt = tokens(100, salt=5)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 99)], seq_lens)
    first = int(toks[0])
    made = decode_scan(im, 0, first, 100, 40)
    full = prompt + [first] + made                  # 141 tokens
    want, want_tok = reference_logprobs(full + tokens(2, salt=6))
    assert full[100:] == want_tok[99:140].tolist()
    feed_flat(im, 2, full[:140], [CAP], seq_lens)
    seen = set()
    for node, bufs in im.state.items():
        assert set(bufs) == {"ckv", "kpe"}, (node, sorted(bufs))
        for name, width in (("ckv", RANK), ("kpe", ROPE)):
            a, b = bufs[name][0, :, :140], bufs[name][2, :, :140]
            assert a.shape == (1, 140, width)       # nothing per head
            assert float(jnp.abs(a).max()) > 1e-2, (node, name)
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
            seen.add(name)
    assert seen == {"ckv", "kpe"} and len(im.state) == LAYERS
    seq_lens[0] = 140
    for k, tok in enumerate([full[140]] + tokens(2, salt=6)):
        (got,), _ = flat_step(im, [(0, [tok], 140 + k)], seq_lens)
        np.testing.assert_allclose(got[0], want[140 + k], atol=TOL, rtol=0)


# ---- (c) absorbed against materialised, (d) the kernel against XLA ----------
def _op_and_cache(seed=3, heads=4, s_len=256, rows=3, dtype=np.float32):
    """A latent operator with random per-head up-projections, a filled
    cache, and queries of ``rows`` decode rows at scattered positions."""
    rng = np.random.default_rng(seed)
    op = LatentAttention(64, heads, 16, ROPE, 16, RANK, rope_scaling=YARN)
    normal = lambda *shape: rng.standard_normal(shape).astype(dtype)
    kv_b = normal(RANK, heads, 32) / np.sqrt(RANK)
    ckv, kpe = normal(rows + 1, 1, s_len, RANK), normal(rows + 1, 1, s_len,
                                                        ROPE)
    q_n, q_r = normal(rows, heads, 16), normal(rows, heads, ROPE)
    pos = np.asarray([s_len - 1, 130, 5][:rows], np.int32)
    return op, map(jnp.asarray, (kv_b, ckv, kpe, q_n, q_r, pos))


def test_absorbed_and_materialised_agree_on_the_same_cache():
    """Two forms, one result: ``softmax(q_n U_k' c + q_r k_r) c U_v`` against
    plain attention over the expanded keys and values, to float32 rounding;
    and a prompt chunk in either form serves the same log-probabilities."""
    op, (kv_b, ckv, kpe, q_n, q_r, pos) = _op_and_cache()
    rows = jnp.arange(3, dtype=jnp.int32)
    mat = op._attend_xla(q_n[:, None], q_r[:, None], kv_b, ckv, kpe, rows,
                         pos[:, None], "materialised")[:, 0]
    q_lat = jnp.einsum("thn,chn->thc", q_n, kv_b[..., :16], precision="highest")
    o_lat = op._attend_xla(q_lat[:, None], q_r[:, None], kv_b, ckv, kpe, rows,
                           pos[:, None], "absorbed")[:, 0]
    absorbed = jnp.einsum("thc,chv->thv", o_lat, kv_b[..., 16:],
                          precision="highest")
    assert mat.shape == absorbed.shape == (3, 4, 16)
    np.testing.assert_allclose(absorbed, mat, atol=3e-5, rtol=1e-4)
    # the whole program with its prompt chunks materialised
    im = build()
    for node in im.model.graph.nodes:
        if isinstance(node.op, LatentAttention):
            node.op.prompt_form = "materialised"
    seeded(im)
    want, _ = reference_logprobs(PROMPT)
    got = feed_flat(im, 0, PROMPT, [CAP, 5], [0] * SLOTS)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert im.attention_paths[("latent_attention", "BatchConfig")] == \
        "xla_materialised"
    sound = feed_flat(deployment(), 0, PROMPT, [CAP, 5], [0] * SLOTS)
    np.testing.assert_allclose(got, sound, atol=5e-5, rtol=0)


@pytest.mark.parametrize("cache_dt", ["float32", "bfloat16"])
def test_latent_decode_kernel_equals_the_xla_oracle(cache_dt):
    """``decode_attention``'s latent kernel (interpret) against the absorbed
    XLA path on the same cache: a row at the cache's end (every block), one
    mid-block, one in the first block; a pad row on the scratch row."""
    op, (kv_b, ckv, kpe, q_n, q_r, pos) = _op_and_cache(s_len=2048)
    del q_n
    rng = np.random.default_rng(9)
    q_lat = jnp.asarray(rng.standard_normal((3, 4, RANK)), jnp.float32)
    dt = jnp.dtype(cache_dt)
    ckv, kpe, q_lat, q_r = (a.astype(dt) for a in (ckv, kpe, q_lat, q_r))
    rows = jnp.arange(3, dtype=jnp.int32)
    want = op._attend_xla(q_lat[:, None], q_r[:, None], kv_b, ckv, kpe, rows,
                          pos[:, None], "absorbed")[:, 0]
    got = decode_attention(q_lat, ckv, None, rows, pos,
                           scale=op.scaling_factor, interpret=True,
                           q_rope=q_r, k_rope=kpe)
    assert got.shape == (3, 4, RANK) and got.dtype == dt
    tol = 2e-5 if cache_dt == "float32" else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol,
                               rtol=tol)
    # the rope term is in the score: dropping it moves the result
    bare = decode_attention(q_lat, ckv, None, rows, pos,
                            scale=op.scaling_factor, interpret=True)
    assert float(jnp.abs(bare.astype(jnp.float32) - want).max()) > 20 * tol


# frontiers the latent kernel's copies turn on (block 1024, pieces of 128,
# spans of 2048 in a cache of 4096): cache rows and positions of the flat rows
FRONTIERS = {
    "position_0": ([0], [0]),
    "a_blocks_last_position": ([1], [1023]),
    "a_blocks_first_position": ([2], [1024]),
    "the_caches_last_position": ([0], [4095]),
    "a_spans_last_and_first": ([1, 2], [2047, 2048]),
    "a_single_row_mid_piece": ([2], [1350]),
    "unlike_lengths_side_by_side": ([0, 1, 2, 1, 0, 2, 1],
                                    [4095, 3, 1500, 0, 2600, 128, 127]),
    "pad_rows_between_live_ones": ([0, 3, 3, 1, 3], [900, 0, 0, 2600, 0]),
}


@pytest.mark.parametrize("cache_dt", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(FRONTIERS))
def test_latent_kernel_copies_each_rows_live_blocks(case, cache_dt):
    """The kernel's own copies against the absorbed XLA oracle wherever they
    branch: a frontier at position 0, at a block's last and first position,
    at the cache's end, across a span of the rotated plane; rows of very
    unlike lengths side by side (the fetch side runs ahead ACROSS rows); a
    single row; pad rows on the scratch row."""
    op, (kv_b, ckv, kpe, _, _, _) = _op_and_cache(s_len=4096)
    rows, pos = (jnp.asarray(a, jnp.int32) for a in FRONTIERS[case])
    rng = np.random.default_rng(len(case))
    dt = jnp.dtype(cache_dt)
    q_lat, q_r = (jnp.asarray(rng.standard_normal((len(pos), 4, w)), dt)
                  for w in (RANK, ROPE))
    ckv, kpe = ckv.astype(dt), kpe.astype(dt)
    want = op._attend_xla(q_lat[:, None], q_r[:, None], kv_b, ckv, kpe, rows,
                          pos[:, None], "absorbed")[:, 0]
    got = decode_attention(q_lat, ckv, None, rows, pos,
                           scale=op.scaling_factor, interpret=True,
                           q_rope=q_r, k_rope=kpe)
    tol = 2e-5 if cache_dt == "float32" else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=tol,
                               rtol=tol)


def test_latent_kernel_copies_nothing_past_a_frontiers_piece():
    """No latent past the 128-position piece that holds a row's frontier
    reaches the ring (NaNs there would poison the weighted sum: zero weights
    do not clear them), and the rotated parts past the frontier, which ride
    a pipeline in whole spans, are masked out of the score."""
    op, (kv_b, ckv, kpe, _, q_r, _) = _op_and_cache(s_len=2048)
    rng = np.random.default_rng(11)
    q_lat = jnp.asarray(rng.standard_normal((3, 4, RANK)), jnp.float32)
    rows = jnp.arange(3, dtype=jnp.int32)
    pos = jnp.asarray([700, 1023, 0], jnp.int32)
    call = lambda c, r: decode_attention(
        q_lat, c, None, rows, pos, scale=op.scaling_factor, interpret=True,
        q_rope=q_r, k_rope=r)
    want = call(ckv, kpe)
    at = jnp.arange(2048)[None, None, :, None]
    piece_end = ((pos // 128 + 1) * 128)[:, None, None, None]
    poisoned = call(
        ckv.at[:3].set(jnp.where(at >= piece_end, jnp.nan, ckv[:3])),
        kpe.at[:3].set(jnp.where(at > pos[:, None, None, None], jnp.nan,
                                 kpe[:3])))
    assert bool(jnp.isfinite(poisoned).all())
    np.testing.assert_array_equal(poisoned, want)


def test_the_latent_is_passed_to_the_kernel_once():
    """One cache-sized operand per plane: the latent block is key AND value
    (calling the K/V kernel with ``k_cache = v_cache`` would stream it
    twice)."""
    _, (kv_b, ckv, kpe, _, q_r, pos) = _op_and_cache(s_len=512)
    q_lat = jnp.zeros((3, 4, RANK), jnp.float32)
    rows = jnp.arange(3, dtype=jnp.int32)

    def operands(fn):
        jaxpr = jax.make_jaxpr(fn)()
        calls = [e for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        return [tuple(v.aval.shape) for v in calls[0].invars]

    latent = operands(lambda: decode_attention(
        q_lat, ckv, None, rows, pos, scale=0.2, interpret=True, q_rope=q_r,
        k_rope=kpe))
    assert latent.count(tuple(ckv.shape)) == 1
    assert latent.count(tuple(kpe.shape)) == 1
    twice = operands(lambda: decode_attention(
        q_lat, ckv, ckv, rows, pos, scale=0.2, interpret=True))
    assert twice.count(tuple(ckv.shape)) == 2
    with pytest.raises(ValueError, match="latent cache"):
        decode_attention(q_lat, ckv, None, rows, pos, scale=0.2, window=64,
                         interpret=True)


# ---- (e) scratch breaks: each must move the logits far past the tolerance ---
def _break(broken, monkeypatch):
    """Break the PROGRAM (the reference stays the published model); returns
    the program's configuration and a hook run on the seeded deployment."""
    hf, after = dict(HF), lambda im: None
    attention = lambda im: [n.op for n in im.model.graph.nodes
                            if isinstance(n.op, LatentAttention)]
    if broken == "half_split_rotary":
        monkeypatch.setattr(
            hybrid_ops, "apply_rope",
            lambda x, pos, theta, interleaved=False, yarn=None:
                serve_ops.apply_rope(x, pos, theta, yarn=yarn))
    elif broken == "plain_frequencies":
        monkeypatch.setattr(
            hybrid_ops, "apply_rope",
            lambda x, pos, theta, interleaved=False, yarn=None:
                serve_ops.apply_rope(x, pos, theta, interleaved=interleaved))
    elif broken == "mscale_squared_dropped":
        def after(im):
            for op in attention(im):
                op.scaling_factor = (op.nope_dim + op.rope_dim) ** -0.5
    elif broken == "rotated_key_part_normed":
        sound = LatentAttention._project

        def normed(self, x, params, pos):
            q_n, q_r, c, k_r = sound(self, x, params, pos)
            return q_n, q_r, c, hybrid_ops._rms_norm(k_r, None, self.eps)

        monkeypatch.setattr(LatentAttention, "_project", normed)
    elif broken == "latent_cached_before_its_norm":
        monkeypatch.setattr(hybrid_ops, "_rms_norm",
                            lambda x, gamma, eps: x)
    elif broken == "top_3_renormalised":
        hf["norm_topk_prob"] = True
    elif broken == "sigmoid_scores":
        hf["scoring_func"] = "sigmoid"
    elif broken == "shared_experts_averaged":
        def after(im):
            for name, group in im.params.items():
                if name.endswith("shared_experts.down_proj"):
                    group["kernel"] = group["kernel"] / HF["n_shared_experts"]
    elif broken == "dense_layer_given_a_mixture":
        hf["first_k_dense_replace"] = 0
    else:
        raise ValueError(broken)
    return hf, after


BREAKS = ["half_split_rotary", "plain_frequencies", "mscale_squared_dropped",
          "rotated_key_part_normed", "latent_cached_before_its_norm",
          "top_3_renormalised", "sigmoid_scores", "shared_experts_averaged",
          "dense_layer_given_a_mixture"]


@pytest.mark.parametrize("router_gain", [ref.ROUTER_GAIN, 1.0],
                         ids=["gain2", "as_drawn"])
@pytest.mark.parametrize("broken", BREAKS)
def test_a_break_is_seen(broken, router_gain, monkeypatch):
    """Each way of getting the new mechanisms wrong moves the logits by far
    more than the tolerance the other tests hold — with the router's draw
    scaled as the benchmark scales it, and as drawn (the routed sum then a
    tenth of the shared experts' size: in float32 a break of the routed path
    is still seen; what bf16 on the chip resolves is why the draw is
    scaled: the reference's docstring)."""
    hf, after = _break(broken, monkeypatch)
    sound_hf = dict(HF, router_gain=router_gain)
    im = build(hf=hf)
    seeded(im, hf=dict(hf, router_gain=router_gain))
    after(im)
    prompt = tokens(150, salt=50)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 149)], seq_lens)
    made = decode_scan(im, 0, int(toks[0]), 150, 10)
    full = prompt + [int(toks[0])] + made
    want, _ = reference_logprobs(full, hf=sound_hf)
    seq_lens[0] = 160
    (got,), _ = flat_step(im, [(0, [full[160]], 160)], seq_lens)
    assert np.abs(got[0] - want[160]).max() > 20 * TOL, broken


# ---- the pieces by hand --------------------------------------------------------
def test_yarn_frequencies_by_hand():
    """The published scaling (factor 40 over 4096, 64-wide rotary part):
    ``low`` 10, ``high`` 23; pairs below 10 keep their turn, pairs from 23 on
    take a fortieth, pair 16 blends by 6/13; ``m`` 1.2608."""
    ys = dict(YARN, original_max_position_embeddings=4096)
    f = np.asarray(serve_ops.rope_frequencies(32, 10000.0, ys), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    ramp = 6 / 13
    np.testing.assert_allclose(
        f[16], plain[16] * (1 - ramp) + plain[16] / 40 * ramp, rtol=1e-6)
    assert serve_ops.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)
    op = LatentAttention(2048, 16, 128, 64, 128, 512, rope_scaling=ys)
    assert op.scaling_factor == pytest.approx(1.2608 ** 2 / 192 ** 0.5,
                                              rel=1e-4)
    # the option of the ONE function equals the reference's own rotation
    x = jnp.asarray(np.random.default_rng(0).standard_normal((70, 2, 8)),
                    jnp.float32)
    got = serve_ops.apply_rope(x, jnp.arange(70), 10000.0, interleaved=True,
                               yarn=YARN)
    np.testing.assert_allclose(got, ref.rope(HF, x[None])[0], atol=1e-5)
    # without the option nothing changed
    np.testing.assert_array_equal(
        serve_ops.apply_rope(x, jnp.arange(70), 10000.0),
        serve_ops.apply_rope(x, jnp.arange(70), 10000.0, yarn=None))
    with pytest.raises(ValueError, match="YaRN"):
        LatentAttention(64, 4, 16, 8, 16, 32,
                        rope_scaling=dict(type="linear", factor=2))


def test_softmax_router_keeps_its_chosen_weights_as_they_are():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((9, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    ctx = OpContext(extras={"node_name": "n"})
    ids, wts = MoERouter(16, 8, 3, norm_topk=False, bias=False,
                         scoring="softmax").lower(ctx, [x], {"weight": w})
    p = np.asarray(jax.nn.softmax(jnp.dot(x, w, precision="highest"), -1))
    order = np.argsort(-p, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(ids, order)
    np.testing.assert_allclose(wts, np.take_along_axis(p, order, -1),
                               rtol=1e-5)
    assert float(jnp.sum(wts, -1).max()) < 1.0     # not renormalised
    assert [s.name for s in MoERouter(16, 8, 3, bias=False,
                                      scoring="softmax").params()] == \
        ["weight"]
    with pytest.raises(ValueError, match="scores by"):
        MoERouter(16, 8, 3, scoring="tanh")


def test_expert_tiles_come_from_the_shapes():
    """``MoEExperts.out_tile``: the two committed shapes keep the tiles they
    compiled with (their programs must not change), the new shape gets two
    tiles either way, and a toy width stays whole."""
    tile = MoEExperts.out_tile
    assert (tile(2688, 1856, 2), tile(1856, 2688, 2)) == (640, 896)
    assert (tile(4096, 4096, 2), tile(4096, 4096, 2)) == (512, 512)
    assert (tile(2048, 1408, 2), tile(1408, 2048, 2)) == (768, 1024)
    assert (tile(64, 24, 4), tile(24, 64, 4)) == (24, 64)


# ---- (g) bytes, (f) refusals, names --------------------------------------------
def test_a_position_is_priced_at_the_latents_bytes():
    """The allocator against the hand formula: per layer one latent of 32
    and one rotated key part of 8 float32 values a position — 3 x 40 x 4 B,
    the toy analogue of 5 x 1 152 B —, nothing fixed per slot."""
    im = deployment()
    per_pos = LAYERS * (RANK + ROPE) * 4 * (SLOTS + 1) / SLOTS
    near = lambda x: pytest.approx(x, rel=1e-12)
    assert im.kv.bytes_per_token() == near(per_pos)
    per_slot = im.kv.bytes_per_slot()
    assert per_slot["kv_latent"] == near(per_pos * SEQ)
    assert per_slot["kv_full"] == 0 and im.kv.fixed_bytes_per_slot() == 0
    assert im.kv.request_bytes(100) == near(100 * per_pos)
    assert im.kv.allocated_bytes() == near(per_pos * SEQ * SLOTS)
    snap = im.kv.observe({0: 100})
    assert snap["live_bytes"] == near(100 * per_pos)
    assert snap["occupancy_frac"] == near(100 / (SLOTS * SEQ))
    im.kv.release(0)
    # the published widths: 1 152 B a position and layer in bf16
    op = LatentAttention(2048, 16, 128, 64, 128, 512, dtype=jnp.bfloat16)
    specs = op.state_specs(64, 15360)
    assert sum(np.prod(shape[2:]) * 2 for shape, _, _ in specs.values()) \
        == 15360 * 1152


def test_admission_counts_positions_at_the_latents_price():
    from flexflow_tpu.serve.request_manager import (GenerationConfig,
                                                    RequestManager)
    from flexflow_tpu.serve.resilience import ResilienceConfig

    im = deployment()
    one = im.kv.request_bytes(40 + 8)
    rm = RequestManager(
        im, GenerationConfig(max_new_tokens=8, stop_on_eos=False),
        resilience=ResilienceConfig(kv_gate=True,
                                    kv_budget_bytes=1.5 * one))
    first = rm.register_new_request(tokens(40, salt=41))
    second = rm.register_new_request(tokens(40, salt=42))
    assert rm.requests[first].status.name != "REJECTED"
    assert rm.requests[second].status.name == "REJECTED"


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=16), "a rotated-key plane of another width"),
    (dict(kv_dtype="int8"), "scale planes beside the latent"),
    (dict(max_spec_tokens=4), "no spec-tree buffers"),
])
def test_combinations_not_written_yet_raise_at_compile(kw, needs):
    with pytest.raises(ValueError, match="LatentAttention") as e:
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
    assert "LatentAttention" in text
    if how == "tp":
        assert "the absorbed heads" in text and "latent cache replicated" in text
        assert "an exchange of rows" in text
    else:
        assert "pp > 1" in text and "a latent cache's two planes" in text


def test_the_builder_refuses_what_it_does_not_build():
    for change, needs in [
            (dict(q_lora_rank=24), "query down-projection"),
            (dict(topk_method="group_limited_greedy", n_group=2),
             "group-limited"),
            (dict(scoring_func="tanh"), "scoring_func"),
            (dict(kv_lora_rank=None), "kv_lora_rank"),
            (dict(router_num_experts=4), "not among the router's"),
            (dict(rope_scaling=dict(type="linear", factor=2)), "YaRN")]:
        with pytest.raises(ValueError, match=needs):
            build(hf=dict(HF, **change))


def test_the_graph_names_each_mechanism_by_its_class():
    """What a device trace files operations under: a latent attention node
    in every layer, a dense FFN in layer 0 alone, the routed layer's four
    classes and the shared experts' three projections in the other two."""
    im = deployment()
    names = [type(n.op).__name__ for n in im.model.graph.nodes]
    count = {c: names.count(c) for c in set(names)}
    assert count["LatentAttention"] == LAYERS
    assert all(count[c] == 2 for c in ("MoERouter", "MoEDispatch",
                                       "MoEExperts", "MoECombine"))
    assert count["SharedExpertLinear"] == 6
    assert count["Linear"] == 3 + 1        # layer 0's FFN and the untied head
    assert im.expert_layers == 2
    assert "IncMultiHeadSelfAttention" not in count
    router = next(n.op for n in im.model.graph.nodes
                  if isinstance(n.op, MoERouter))
    assert (router.scoring, router.norm_topk, router.bias) == \
        ("softmax", False, False)


def test_weight_only_int8_reaches_the_four_projections():
    from flexflow_tpu.serve.quant import quantize_int8

    im = seeded(build())
    want, _ = reference_logprobs(PROMPT[:40])
    quantize_int8(im)
    for name, group in im.params.items():
        if name.endswith("self_attn"):
            for p in LatentAttention.int8_params:
                assert group[p].dtype == jnp.int8, (name, p)
                assert group[f"{p}_scale"].shape == group[p].shape[1:]
            assert group["kv_norm"].dtype == jnp.float32
    got = feed_flat(im, 0, PROMPT[:40], [CAP], [0] * SLOTS)
    err = np.abs(got - want).max()
    assert 20 * TOL < err < 0.5, err    # quantised, and still the model


def test_the_published_tensor_names_are_listed_for_an_importer():
    from flexflow_tpu.serve.weights import DEEPSEEK_V2_TENSORS

    for name, _, _ in ref.LAYER:
        stem = name.replace(".weight", "").replace(
            "mlp.experts.", "mlp.experts.N.")
        assert stem + ".weight" in DEEPSEEK_V2_TENSORS, name
    assert "U_k | U_v" in DEEPSEEK_V2_TENSORS["self_attn.kv_b_proj.weight"][2]


def test_row_write_kernel_on_and_off_serves_the_same(row_write_on_and_off):
    """The decode scan's K/V rows by ``kv_row_write`` and by the chain it
    replaced — the latent plane and its rotated part — on the two deployments
    this module already built (kernels on: the aliased call; kernels off:
    the chain): the same tokens, and the same caches off the scratch row to
    float32's rounding (between two writes stands the attention, a kernel on
    one side and XLA on the other)."""
    prompts = [tokens(40, salt=51), tokens(9, salt=52)]
    on, took_on, state_on = row_write_on_and_off.serve(
        deployment(use_pallas=True), prompts, 6)
    off, took_off, state_off = row_write_on_and_off.serve(
        deployment(use_pallas=False), prompts, 6)
    assert took_on == {"pallas"} and took_off == {"dus_chain"}
    assert on == off and all(len(o) == 6 for o in on)
    for a, b in zip(jax.tree.leaves(state_on), jax.tree.leaves(state_off)):
        np.testing.assert_allclose(a[:-1], b[:-1], atol=2e-5, rtol=0)
