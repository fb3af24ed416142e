"""Solar Open 2 (``solar_open2``: a sequential pre-norm block; GATED softmax
grouped-query attention without a positional term in the first layer of a
period, Kimi Delta Attention with ``beta`` in (0, 2) — a transition with an
eigenvalue in (-1, 1) — in the others; a sigmoid-routed mixture of gated
experts beside one shared expert in EVERY layer) through the normal serve
path, against the plain reference ``benchmark/reference/solar_open2.py`` —
logits, not tokens.

Toy widths, the real mechanisms: hidden 64; the attention layer 8 query
heads of 16 on 2 K/V heads (4 queries a K/V head, 8 x 16 = TWICE the stream's
width, as the published 64 x 128 on 4096) behind an elementwise gate; the
delta-rule layer 4 heads of 32 (twice the stream's width too) behind convs of
4 taps; a router over 32 experts with top-8 renormalised of which THIS graph
holds 4 (share 0 of 8), width 24, one shared expert; 2 layers, one of each
kind (``gqa_layers [0]``, ``gqa_interval`` 1: the layer loop is unrolled, so
a test's compile time goes with the depth); the head untied.  Weights are the
benchmark's seeded ones in float32, the decay and the convs through
``published_init``; a prompt chunk is 48 rows = 3 tiles of 16 = a full
32-row piece of the chunked form and a ragged one of 16.

The reference runs the delta rule token by token; the program runs prompt
chunks through the CHUNKED form and decode rows through the step kernel
(interpret mode) or its XLA oracle.  float32 on the CPU against float32 at
HIGHEST precision: a log-probability agrees to 3e-4 nats (kimi's tolerance:
the same summation orders); each listed break moves one by 6e-3 or more
(``test_a_break_is_seen``).

ONE built deployment per kernel mode and process (``RIG.deployment``, reset
between uses); the breaks are made on the REFERENCE's side, against the one
sound program.  Budget: the file's junit seconds stay under 250 (CHANGES.md,
PR 64, has the measured sum); second seeds and sizes are ``slow``.
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
from benchmark.reference import kimi_linear as kimi_ref  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402
from flexflow_tpu.core.op import OpContext  # noqa: E402
from flexflow_tpu.serve import BatchConfig  # noqa: E402
from flexflow_tpu.serve.batch_config import PrefillBatchConfig  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import (  # noqa: E402
    KimiDeltaAttention,
    Segments,
)
from flexflow_tpu.serve.models import solar_open2 as builder  # noqa: E402
from flexflow_tpu.serve.models.base import ServeModelConfig  # noqa: E402
from flexflow_tpu.serve.ops import IncMultiHeadSelfAttention  # noqa: E402
from flexflow_tpu.serve.ssd_moe_ops import MoERouter  # noqa: E402

from reference_rig import Rig  # noqa: E402

LISTS = dict(num_heads=4, head_dim=32, short_conv_kernel_size=4,
             num_kv_heads=None)
HF = dict(model_type="solar_open2", vocab_size=320, hidden_size=64,
          num_hidden_layers=2, linear_attn_config=LISTS, gqa_layers=[0],
          gqa_interval=1, num_attention_heads=8, num_key_value_heads=2,
          head_dim=16, use_rope=False, use_gqa_gate=True,
          kda_allow_neg_eigval=True, kda_use_full_proj=False,
          partial_rotary_factor=1, rope_theta=10000, intermediate_size=96,
          moe_intermediate_size=24, n_routed_experts=4,
          router_num_experts=32, expert_share_index=0, expert_share_count=8,
          num_experts_per_tok=8, n_shared_experts=1, first_k_dense_replace=0,
          norm_topk_prob=True, routed_scaling_factor=1, rms_norm_eps=1e-5,
          tie_word_embeddings=False, max_position_embeddings=1048576,
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 4096
          init_std=0.125, torch_dtype="float32")
SLOTS, CAP, SEQ = 3, 48, 256
PIECE = 32
TOL = 3e-4          # nats, see the module docstring
SEED = 6464
LIMITS = {"logit_rms_ulps": 0.02, "logit_max_ulps": 0.1,
          "logprob_rms": 2e-4, "logprob_max": 2e-3,
          "tail_logprob_rms": 2e-4, "token_gap_ulps": 0.1}
RIG = Rig(ref, HF, SLOTS, CAP, SEQ, SEED)
tokens = RIG.tokens
PROMPT = tokens(150)        # chunks of 48, 48, 48 and 6 rows


# ---- prompt feeding, the harness drive, prefill into the decode scan -------

def check_prompt_paths(rig, how, prompt, sizes):
    want, want_tok = rig.reference_logprobs(prompt + rig.tokens(3, salt=1))
    n = len(prompt)
    assert n > 3 * rig.cap
    seq_lens = [0] * rig.slots
    im = rig.deployment(use_pallas=how.endswith("pallas"))
    if how.startswith("tiled_scan"):
        first = check._prefill_scan(im, 1, prompt, list(seq_lens))
        assert first == want_tok[n - 1]
        seq_lens[1] = n
    else:
        got = rig.feed_flat(im, 1, prompt, sizes, seq_lens)
        np.testing.assert_allclose(got, want[:n], atol=TOL, rtol=0)
    for k, tok in enumerate(rig.tokens(3, salt=1)):
        (lp,), _ = rig.flat_step(im, [(1, [tok], n + k)], seq_lens)
        np.testing.assert_allclose(lp[0], want[n + k], atol=TOL, rtol=0)
    return im


@pytest.mark.parametrize("how", [
    "uneven_chunks", "tiled_scan", "tiled_scan_pallas",
    # flat chunks with the kernels on are the harness drive's prompt B and
    # joiner every run; the whole prompt that way is slow
    pytest.param("uneven_chunks_pallas", marks=pytest.mark.slow)])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in uneven flat chunks (pieces of 7, 48, 1, 13, 35
    rows: ragged, and one across the 32-row piece boundary) and through the
    tiled prefill scan (four chunks; each a full piece and a ragged one of
    the chunked delta form, the state carried from chunk to chunk; the gated
    layer's block write and prefill kernel), kernels off and on: flat decode
    steps then read the delta state and the K/V cache each left."""
    im = check_prompt_paths(RIG, how, PROMPT, [7, CAP, 1, 13, 35])
    paths, pallas = im.attention_paths, how.endswith("pallas")
    full = "inc_multihead_self_attention"
    assert paths[("kimi_delta_attention", "BatchConfig")] == \
        "chunked+neg_eigval"
    assert paths[("attention_gate", (full, "BatchConfig"))] == "elementwise"
    assert paths.get(("decode_block", (full, "BatchConfig"))) == (
        f"full{SEQ}" if pallas else None)
    if how.startswith("tiled_scan"):
        assert paths[("kimi_delta_attention", "PrefillBatchConfig")] == \
            "chunked+neg_eigval"
        assert paths[("attention_gate", (full, "PrefillBatchConfig"))] == \
            "elementwise"
        # the first layer is the gated attention: the prologue's carried
        # q/k/v feed it, the gate reads the normed rows beside them
        assert im.prefill_overlap
    if how == "tiled_scan_pallas":
        assert paths[("prefill_operands", full)] == "float32"
        assert paths[("kv_block_write", "PrefillBatchConfig")] == "pallas"


@pytest.mark.slow
def test_prompt_feeding_paths_at_a_second_seed_and_size():
    """Slow (a second pair of deployments): another seed, a chunk of 80 rows
    (two full pieces and a ragged one), a whole period of four layers."""
    hf = dict(HF, num_hidden_layers=4, gqa_interval=3)
    rig = Rig(ref, hf, SLOTS, 80, 512, 99)
    for how in ("tiled_scan", "tiled_scan_pallas", "uneven_chunks_pallas"):
        check_prompt_paths(rig, how, rig.tokens(300), [5, 80, 2, 33])


@pytest.mark.parametrize("use_pallas", [
    pytest.param(False, id="xla", marks=pytest.mark.slow), True], ids=str)
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive: the tiled prefill scan of 220 tokens
    (five chunks), a flat prompt, a JOINER fed flat in two pieces and spliced
    by ``join_slot`` between two chained decode scans of the other two rows
    (the step kernel; ``decode_attention`` on the gated layer's cache), flat
    steps on all three."""
    im = RIG.deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    assert "contexts up to 241" in lines[-1], lines[-1]
    paths = im.attention_paths
    assert paths[("kimi_delta_attention", "one_row_per_request")] == (
        "delta_rule_step" if use_pallas else "xla_rows") + "+neg_eigval"
    assert paths[("attention_gate", ("inc_multihead_self_attention",
                                     "one_row_per_request"))] == "elementwise"
    assert paths[("kv_row_write", "one_row_per_request")] == (
        "pallas" if use_pallas else "dus_chain")


@pytest.mark.parametrize("use_pallas", [
    pytest.param(False, id="xla", marks=pytest.mark.slow), True], ids=str)
def test_decode_scan_carries_what_prefill_leaves(use_pallas):
    """A prompt of 100, then 40 decode steps on the device in two chained
    scans through BOTH mixers (the step kernel on the state the chunked form
    left; the gated layer's cache read from prefill into decode): the scan's
    tokens are the reference's greedy ones, and flat steps then read, at
    position 140 on, what the scan wrote."""
    im = RIG.deployment(use_pallas=use_pallas)
    prompt = tokens(100, salt=5)
    seq_lens = [0] * SLOTS
    RIG.feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = RIG.flat_step(im, [(0, prompt[-1:], 99)], seq_lens)
    first = int(toks[0])
    made = RIG.decode_scan(im, 0, first, 100, 40)
    full = prompt + [first] + made                  # 141 tokens
    tail = tokens(2, salt=6)
    # teacher forcing: the reference is fed what the program produced
    want, want_tok = RIG.reference_logprobs(full + tail)
    assert full[100:] == want_tok[99:140].tolist()
    seq_lens[0] = 140
    for k, tok in enumerate([full[140]] + tail[:1]):
        (lp,), _ = RIG.flat_step(im, [(0, [tok], 140 + k)], seq_lens)
        np.testing.assert_allclose(lp[0], want[140 + k], atol=TOL, rtol=0)


# ---- beta in (1, 2): the chunked form against the recurrence ---------------

def _one_request(rows, slots=1):
    bc = BatchConfig(tokens=jnp.zeros(rows, jnp.int32),
                     request_index=jnp.zeros(rows, jnp.int32),
                     token_position=jnp.arange(rows, dtype=jnp.int32),
                     num_tokens=jnp.int32(rows),
                     seq_lens=jnp.zeros((slots,), jnp.int32))
    return Segments(bc, slots)


def _recurrence64(q, k, v, g, beta):
    """The per-row recurrence in float64: ``(outputs, last state)``."""
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    s, out = np.zeros(q.shape[1:] + q.shape[-1:]), []
    for t in range(len(q)):
        s = s * np.exp(g[t])[..., None]
        u = v[t] - np.sum(s * k[t][..., None], axis=-2)
        s = s + (beta[t][..., None] * k[t])[..., None] * u[..., None, :]
        out.append(np.sum(s * q[t][..., None], axis=-2))
    return np.stack(out), s


@pytest.mark.parametrize("decay", [1.0, 0.999], ids=["no_decay", "slow"])
@pytest.mark.parametrize("keys", ["repeated", "alternating", "drawn"])
def test_the_chunked_form_holds_beta_near_2_on_keys_that_repeat(keys, decay):
    """``beta`` 1.99, a decay of 1 or 0.999 a step, 70 rows = two FULL
    32-row pieces and a ragged one, and keys that are all the SAME unit
    vector (or the same with alternating sign): every strictly-lower entry
    of the solve's matrix is +-1.99, the worst the option allows.  An
    arbitrary unit-lower matrix with such entries has an inverse of size
    3^30; this one's is a product of contractions and stays under 2, and
    float32 holds it: against the recurrence in float64 the chunked form is
    off by 9e-6 of the outputs' size at the most (my CPU readings: repeated
    4.4e-6 / 8.7e-6, drawn 7e-7; the float32 recurrence itself 8e-7) —
    held to 5e-5, five times the reading: 32-term float32 sums in another
    order, not a cancellation."""
    rows, heads, d = 70, 2, 16
    rng = np.random.default_rng(7)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(normal(rows, heads, d)) * d ** -0.5
    k = unit(normal(rows, heads, d))
    if keys != "drawn":
        k = jnp.broadcast_to(k[:1], k.shape)
    if keys == "alternating":
        k = k * jnp.where(jnp.arange(rows) % 2 == 0, 1.0, -1.0)[:, None, None]
    v = normal(rows, heads, d)
    g = jnp.full((rows, heads, d), np.log(decay), jnp.float32)
    beta = jnp.full((rows, heads), 1.99, jnp.float32)
    kda = jnp.zeros((2, heads, d, d), jnp.float32)
    op = KimiDeltaAttention(64, heads, d, chunk=PIECE, allow_neg_eigval=True)
    out, new = jax.jit(lambda *a: op._chunked(*a, _one_request(rows)))(
        q, k, v, g, beta, kda)
    want, state = _recurrence64(q, k, v, g, beta)
    assert np.abs(np.asarray(out) - want).max() < 5e-5 * np.abs(want).max()
    assert np.abs(np.asarray(new[0]) - state).max() < \
        5e-5 * np.abs(state).max()
    # the reference's own scan is that recurrence
    whole = kimi_ref.delta_rule(*(a[None] for a in (q, k, v, g, beta)))[0]
    assert np.abs(np.asarray(whole) - want).max() < 5e-6 * np.abs(want).max()


def test_beta_is_doubled_in_the_graph_and_nowhere_else():
    """``allow_neg_eigval`` False is the program before the option: the two
    lowerings differ by ONE multiplication (``beta``'s), and the built
    graph's delta layer carries the option from the config."""
    h, d, e, rows = 2, 16, 32, 5

    def jaxpr(flag):
        op = KimiDeltaAttention(e, h, d, allow_neg_eigval=flag)
        names = [p.name for p in op.params()]
        shapes = [jnp.zeros(p.spec.shape, p.spec.dtype) for p in op.params()]

        def mix(qkv, x, kda, *weights):
            at = jnp.arange(rows, dtype=jnp.int32)
            bc = BatchConfig(tokens=at, request_index=jnp.zeros_like(at),
                             token_position=at, num_tokens=jnp.int32(rows),
                             seq_lens=jnp.zeros((1,), jnp.int32))
            ctx = OpContext(extras={"node_name": "n", "batch_config": bc,
                                    "state": {"kda": kda}})
            return op.lower(ctx, [qkv, x], dict(zip(names, weights)))[0]

        return jax.make_jaxpr(mix)(
            jnp.zeros((rows, 3 * h * d)), jnp.zeros((rows, e)),
            jnp.zeros((2, h, d, d)), *shapes)

    count = lambda j: sum(1 for _ in j.jaxpr.eqns)
    muls = lambda j: sum(e.primitive.name == "mul" for e in j.jaxpr.eqns)
    off, on = jaxpr(False), jaxpr(True)
    assert count(on) == count(off) + 1 and muls(on) == muls(off) + 1
    im = RIG.build()
    (kda,) = [n.op for n in im.model.graph.nodes
              if isinstance(n.op, KimiDeltaAttention)]
    assert kda.allow_neg_eigval and kda.inner == 2 * HF["hidden_size"]
    assert not KimiDeltaAttention(e, h, d).allow_neg_eigval
    assert kda.chunk == PIECE


def test_about_half_the_seeded_rows_have_beta_over_1():
    """``2 sigmoid`` of a centred draw: what makes a dropped ``x 2`` (or a
    solve that failed past 1) visible in every comparison."""
    w = sw.draw_table(sw.base_key(SEED), 1, ref.LAYER, HF, "float32")
    n = jnp.asarray(np.random.default_rng(3).standard_normal((1, 400, 64)),
                    jnp.float32)
    beta = np.asarray(ref.beta_of(HF, w, n))
    assert 0.0 < beta.min() and beta.max() < 2.0
    assert 0.4 < (beta > 1.0).mean() < 0.6
    halved = np.asarray(ref.beta_of(dict(HF, kda_allow_neg_eigval=False), w,
                                    n))
    np.testing.assert_allclose(2 * halved, beta, rtol=1e-6)


# ---- the gate by hand, in both widths --------------------------------------

@pytest.mark.parametrize("gate", ["elementwise", "head", None])
def test_the_output_gate_by_hand(gate):
    """One flat step of 6 rows of one request through the operator alone
    (the gather path, float32), against numpy: causal softmax attention on 2
    K/V heads of 3 queries, ``o * sigmoid(x W_g)`` — a gate a channel
    (``[E, QH D]``) or a gate a head (``[E, QH]``, broadcast over its 8
    channels) — THEN ``W_o``; without the option no ``g_proj`` exists and the
    product is not in the program."""
    e, qh, kvh, d, t = 24, 6, 2, 8, 6
    op = IncMultiHeadSelfAttention(e, qh, kvh, d, rotary_embedding=False,
                                   gate=gate)
    specs = {p.name: p.spec.shape for p in op.params()}
    assert specs.get("g_proj") == {
        "elementwise": (e, qh * d), "head": (e, qh), None: None}[gate]
    rng = np.random.default_rng(4)
    params = {n: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
              for n, s in specs.items()}
    x = jnp.asarray(rng.standard_normal((t, e)), jnp.float32)
    bc = BatchConfig(tokens=jnp.zeros(t, jnp.int32),
                     request_index=jnp.zeros(t, jnp.int32),
                     token_position=jnp.arange(t, dtype=jnp.int32),
                     num_tokens=jnp.int32(t),
                     seq_lens=jnp.full((1,), t, jnp.int32))
    state = {n: jnp.zeros(shape, dt) for n, (shape, dt, _) in
             op.state_specs(1, 16).items()}
    paths = {}
    ctx = OpContext(extras={"node_name": "n", "batch_config": bc,
                            "state": state, "attention_paths": paths})
    (got,) = op.lower(ctx, [x], params)
    w = {n: np.asarray(a, np.float64) for n, a in params.items()}
    x64 = np.asarray(x, np.float64)
    qkv = np.einsum("te,ekgd->tkgd", x64, w["qkv"])
    q, k, v = qkv[:, :, :3], qkv[:, :, 3], qkv[:, :, 4]
    o = np.zeros((t, kvh, 3, d))
    for row in range(t):
        for kv in range(kvh):
            for g in range(3):
                s = k[:row + 1, kv] @ q[row, kv, g] / np.sqrt(d)
                p = np.exp(s - s.max())
                o[row, kv, g] = (p / p.sum()) @ v[:row + 1, kv]
    o = o.reshape(t, qh, d)         # head h = kv x 3 + g: h // 3 its K/V head
    if gate:
        sig = 1 / (1 + np.exp(-(x64 @ w["g_proj"])))
        o = o * sig.reshape(t, qh, -1)
    want = o.reshape(t, qh * d) @ w["o_proj"]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert paths.get(("attention_gate", (op.type_name, "BatchConfig"))) == \
        gate
    with pytest.raises(ValueError, match="'elementwise'"):
        IncMultiHeadSelfAttention(e, qh, kvh, d, gate="sigmoid")


# ---- the routing, the share -------------------------------------------------

def test_the_router_is_sigmoid_top_8_of_320_renormalised_row_by_row():
    """The published router's shape — 320 scored, top-8 of score + bias,
    renormalised, scaling 1 — against a per-row loop; and the routed layer
    with 40 HELD of the 320 (ids 0-39): a pair routed to an absent expert
    adds nothing, row by row."""
    rng = np.random.default_rng(2)
    d, scored, held, k, f = 64, 320, 40, 8, 8
    x = rng.standard_normal((9, d)).astype(np.float32)
    wr = (0.25 * rng.standard_normal((d, scored))).astype(np.float32)
    bias = (0.05 * rng.standard_normal(scored)).astype(np.float32)
    op = MoERouter(d, scored, k, 1.0, True)
    ids, wts = op.lower(OpContext(extras={"node_name": "r"}),
                        [jnp.asarray(x)],
                        {"weight": jnp.asarray(wr),
                         "e_score_correction_bias": jnp.asarray(bias)})
    hf = dict(HF, hidden_size=d, n_routed_experts=held,
              router_num_experts=scored, moe_intermediate_size=f)
    w = {ref.ROUTER: jnp.asarray(wr), ref.ROUTER_BIAS: jnp.asarray(bias)}
    for m, shape in zip(ref.EXPERTS, ((held, d, f), (held, d, f),
                                      (held, f, d))):
        w[m] = jnp.asarray(0.2 * rng.standard_normal(shape), jnp.float32)
    routed = np.asarray(ref.routed_experts(
        hf, w, jnp.asarray(x)[None], ids[None], wts[None]))[0]
    on_held = 0
    for row in range(9):
        s = 1 / (1 + np.exp(-(x[row].astype(np.float64) @ wr)))
        chosen = np.argsort(-(s + bias), kind="stable")[:k]
        assert np.asarray(ids[row]).tolist() == chosen.tolist()
        weights = s[chosen] / s[chosen].sum()
        np.testing.assert_allclose(np.asarray(wts[row]), weights, rtol=1e-5)
        want = np.zeros(d)
        for e, we in zip(chosen, weights):
            if e < held:
                on_held += 1
                gate, up, down = (np.asarray(w[m][e], np.float64)
                                  for m in ref.EXPERTS)
                a, b = x[row] @ gate, x[row] @ up
                want += we * ((a / (1 + np.exp(-a)) * b) @ down)
        np.testing.assert_allclose(routed[row], want, atol=1e-5)
    assert 0 < on_held < 9 * k      # some pairs land here, most do not


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The deployment's cut against the model: a layer's mixture on the
    WHOLE layer (32 experts held of 32) equals the eight shares' routed
    parts summed, the shared expert — which every chip computes alike —
    counted once (the mixers and the norms are whole on every chip and are
    not in the sum at all); share 0 is what the program's graph holds."""
    whole = dict(HF, n_routed_experts=32, router_num_experts=32,
                 expert_share_index=0, expert_share_count=1)
    w = sw.draw_table(sw.base_key(SEED), 1, ref.LAYER, whole, "float32")
    n = jnp.asarray(np.random.default_rng(8).standard_normal((1, 40, 64)),
                    jnp.float32)
    want = ref.mixture(whole, w, n)
    ids, wts = ref.route(whole, w, n)
    parts = []
    for index in range(8):
        cut_hf, cut = ref.share(whole, w, index, 8)
        assert ref.held_experts(cut_hf) == (4 * index, 4)
        same_ids, same_wts = ref.route(cut_hf, cut, n)
        np.testing.assert_array_equal(same_ids, ids)    # the router is whole
        parts.append(ref.routed_experts(cut_hf, cut, n, same_ids, same_wts))
    assert sum(float(jnp.abs(p).max()) > 1e-3 for p in parts) == 8
    np.testing.assert_allclose(sum(parts) + ref.shared_experts(whole, w, n),
                               want, atol=1e-5, rtol=1e-4)
    cut_hf, _ = ref.share(whole, w, 0, 8)
    keys = ("n_routed_experts", "router_num_experts", "expert_share_index",
            "expert_share_count")
    assert {k: cut_hf[k] for k in keys} == {k: HF[k] for k in keys}
    # what every chip holds whole is the same tensor in every share
    for name in ("self_attn.q_proj", "self_attn.g_proj" + ref.GQA,
                 "input_layernorm.weight", ref.ROUTER,
                 "mlp.shared_experts.up_proj"):
        assert ref.share(whole, w, 5, 8)[1][name] is w[name]


@pytest.mark.slow
def test_the_program_on_another_share_follows_the_reference_given_it():
    """Slow (a deployment of its own): the graph built for share 5 of 8
    against the reference given that share, and not share 0's."""
    hf = dict(HF, expert_share_index=5)
    rig = Rig(ref, hf, SLOTS, CAP, SEQ, SEED)
    ids_in = tokens(30, salt=81)
    got = rig.feed_flat(rig.seeded(rig.build()), 0, ids_in, [CAP],
                        [0] * SLOTS)
    want, _ = rig.reference_logprobs(ids_in)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    lower, _ = RIG.reference_logprobs(ids_in)
    assert np.abs(want - lower).max() > 20 * TOL


# ---- what the configuration says, and what is refused ----------------------

def test_gqa_layers_is_read_from_zero():
    cfg = ServeModelConfig.from_hf_config(HF)
    assert cfg.use_gqa_gate and cfg.kda_allow_neg_eigval
    assert not cfg.use_rope and cfg.gqa_interval == 1
    assert builder.layer_kinds(cfg) == ["gqa", "kda"] == ref.layer_kinds(HF)
    published = ServeModelConfig.from_hf_config(dict(
        HF, num_hidden_layers=48, gqa_interval=3,
        gqa_layers=list(range(0, 48, 4))))
    kinds = builder.layer_kinds(published)
    assert kinds[:5] == ["gqa", "kda", "kda", "kda", "gqa"]
    assert kinds.count("gqa") == 12 and kinds[47] == "kda"
    im = RIG.build()
    by_name = {n.name: n.op for n in im.model.graph.nodes}
    full = by_name["model.layers.0.self_attn"]
    assert isinstance(full, IncMultiHeadSelfAttention)
    assert full.gate == "elementwise" and not full.rotary_embedding
    assert (full.num_q_heads, full.num_kv_heads, full.head_dim) == (8, 2, 16)
    assert full.qkv0_consumer       # the prologue's first attention
    assert isinstance(by_name["model.layers.1.self_attn"],
                      KimiDeltaAttention)
    assert by_name["model.layers.1.mlp.gate"].num_experts == 32
    assert not any(n.startswith("model.layers.0.mlp.gate_proj")
                   for n in by_name)       # no dense layer anywhere


@pytest.mark.parametrize("change,needs", [
    ({"gqa_layers": [0, 2]}, "not among the 2 layers"),
    ({"gqa_layers": [-1]}, "0-based"),
    ({"gqa_layers": [1]}, "not every 2th layer from 0"),
    ({"gqa_layers": [0, 0]}, "repeated"),
    ({"linear_attn_config": {k: v for k, v in LISTS.items()
                             if k != "head_dim"}},
     "linear_attn_config.head_dim"),
    ({"linear_attn_config": {k: v for k, v in LISTS.items()
                             if k != "short_conv_kernel_size"}},
     "short_conv_kernel_size"),
    ({"linear_attn_config": dict(LISTS, num_kv_heads=2)}, "num_kv_heads"),
    ({"use_rope": True}, "use_rope"),
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"n_group": 8, "topk_group": 4}, "group-limited"),
    ({"expert_share_index": 8}, "not among the router's 32"),
    ({"n_routed_experts": 0}, "needs n_routed_experts"),
])
def test_the_builder_refuses_what_it_does_not_build(change, needs):
    with pytest.raises(ValueError, match=needs):
        RIG.build(hf=dict(HF, **change))


@pytest.mark.parametrize("kw,needs", [
    ({"kv_page_size": 16}, "a snapshot of the float32 matrix a head"),
    ({"kv_dtype": "int8"}, "plain K/V planes in a few layers beside"),
    ({"max_spec_tokens": 4}, "a delta state has no rollback at all"),
])
def test_combinations_this_pair_of_states_lacks_raise_at_compile(kw, needs):
    """A delta state beside a plain K/V cache: paging (prefix sharing), int8
    KV and speculation are refused by what each lacks."""
    with pytest.raises(ValueError, match="KimiDeltaAttention") as err:
        RIG.build(**kw)
    assert needs in str(err.value)
    assert {"kv_page_size": "kv_page_size", "kv_dtype": "kv_dtype='int8'",
            "max_spec_tokens": "speculation"}[next(iter(kw))] in str(
        err.value)


def test_a_slot_is_priced_by_position_and_by_state():
    """The allocator against the hand formula: by POSITION the one attention
    layer's K and V (2 x 2 heads x 16 float32) and nothing else; FIXED a
    slot the delta state (4 heads x 32 x 32 float32) and the conv tail (3
    rows of 3 x 128 channels); the memory ledger names both."""
    im = RIG.deployment()
    share = (SLOTS + 1) / SLOTS              # the scratch row, amortised
    per_pos = 2 * 2 * 16 * 4 * share
    state = 4 * 32 * 32 * 4 * share
    tails = 3 * 3 * 128 * 4 * share
    near = lambda x: pytest.approx(x, rel=1e-12)
    assert im.kv.bytes_per_token() == near(per_pos)
    per_slot = im.kv.bytes_per_slot()
    assert per_slot["delta_state"] == near(state)
    assert per_slot["recurrent"] == near(tails)
    assert per_slot["kv_full"] == near(per_pos * SEQ)
    assert per_slot["kv_latent"] == per_slot["kv_window"] == 0
    assert im.kv.fixed_bytes_per_slot() == near(state + tails)
    assert im.kv.request_bytes(100) == near(state + tails + 100 * per_pos)
    # the published widths: 4 194 304 B of state and 147 456 B of tail a KDA
    # layer, 4 096 B a position in the attention layer
    op = KimiDeltaAttention(4096, 64, 128, dtype=jnp.bfloat16)
    (shape, dt, _), = op.state_specs(16, 24832).values()
    assert (np.prod(shape[1:]) * 4, dt) == (4194304, "float32")
    full = IncMultiHeadSelfAttention(4096, 64, 8, 128, dtype=jnp.bfloat16,
                                     rotary_embedding=False,
                                     gate="elementwise")
    k_shape = full.state_specs(16, 24832)["k"][0]
    assert 2 * np.prod(k_shape[1:]) * 2 // 24832 == 4096


def test_the_published_tensor_names_are_listed_for_an_importer():
    from flexflow_tpu.serve.weights import SOLAR_OPEN2_TENSORS

    for name, _, _ in ref.LAYER:
        if name.startswith("mlp.") and name.split(".")[1].endswith("_proj"):
            continue        # a dense layer's: none as published
        base, _, mark = name.partition("@")
        key = (base if base.endswith((".weight", "A_log", "dt_bias",
                                      "e_score_correction_bias"))
               else base + ".weight")
        key = key.replace(".weight", f"@{mark}.weight") if mark else key
        key = key.replace("mlp.experts.", "mlp.experts.<e>.")
        assert key in SOLAR_OPEN2_TENSORS, name
    for name in ("model.embed_tokens.weight", "model.norm.weight",
                 "lm_head.weight"):
        assert name in SOLAR_OPEN2_TENSORS


# ---- the breaks --------------------------------------------------------------

BREAKS = {
    "sound": {},
    "beta_not_doubled": {"kda_allow_neg_eigval": False},
    "gate_dropped": {"use_gqa_gate": False},
    "gate_after_the_output_projection": {},
    "gqa_layers_read_from_one": {},
    "no_renormalisation": {"norm_topk_prob": False},
}


@pytest.mark.parametrize("broken", list(BREAKS))
def test_a_break_is_seen(broken, monkeypatch):
    """The one sound PROGRAM on 90 positions against the reference with one
    thing wrong on ITS side — what holds the two together is the tolerance,
    and a break moves a log-probability the same way whichever side makes
    it: the sound reference is within the tolerance, every break 20 x
    outside it.  ``gate_after_the_output_projection``: ``(o W_o) *
    sigmoid(n W_g[:, :d])`` — the gate is twice the stream's width, so the
    misplaced one takes its first ``d`` columns.  ``gqa_layers_read_from_
    one``: ``[0]`` read 1-based names NO layer, so layer 0 runs the delta
    rule on the tensors the draw gives it."""
    if broken == "gate_after_the_output_projection":
        sound = ref.gated_attention

        def wrong(hf, w, n):
            y = sound(dict(hf, use_gqa_gate=False), w, n)
            g = w["self_attn.g_proj" + ref.GQA].astype(jnp.float32)
            return y * jax.nn.sigmoid(ref.mm(n, g[:, :y.shape[-1]]))

        monkeypatch.setattr(ref, "gated_attention", wrong)
    if broken == "gqa_layers_read_from_one":
        monkeypatch.setattr(ref, "layer_kinds", lambda hf: [
            "gqa" if (i + 1) in hf["gqa_layers"] else "kda"
            for i in range(hf["num_hidden_layers"])])
    ids = tokens(90, salt=31)
    got = RIG.feed_flat(RIG.deployment(), 0, ids, [CAP], [0] * SLOTS)
    # a fresh rig: the reference's jitted layers are cached by configuration,
    # and a patched function is not part of one
    want, _ = Rig(ref, dict(HF, **BREAKS[broken]), SLOTS, CAP, SEQ,
                  SEED).reference_logprobs(ids)
    err = np.abs(got - want).max()
    assert (err < TOL) if broken == "sound" else (err > 20 * TOL), err


# ---- what the launches say -----------------------------------------------

def test_the_pieces_a_launch_counts_are_the_loops_trips():
    """The op's ``launch_counts`` against the pieces the device runs, on the
    batch a tiled chunk really is: segments of 37 and 20 rows in tile-padded
    rows of one chunk are 2 + 1 pieces — the count ``delta_rule_chunk``
    takes as its grid's sequential bound (``_pieces``: its scalar-prefetch
    arrays, a piece's first row, own rows, state row, entering state, last)
    and the trips ``_chunked``'s ``fori_loop`` runs, by the same rule."""
    from flexflow_tpu.ops.pallas.delta_rule import CONTINUE, STORED, ZEROS

    (op,) = [n.op for n in RIG.deployment().model.graph.nodes
             if isinstance(n.op, KimiDeltaAttention)][:1]
    # segments of as many rows, wherever they start; no decode row counts
    pieces = lambda runs: op.launch_counts(
        [(9, 10)], [(7 * n, 7 * n + rows) for n, rows in enumerate(runs)], 1,
        True)
    assert pieces([37, 20]) == ({"prompt_kda_pieces": 3}, {})
    assert pieces([5, 32, 33, 64, 65]) == (
        {"prompt_kda_pieces": 1 + 1 + 2 + 2 + 3}, {})
    assert pieces([]) == ({"prompt_kda_pieces": 0}, {})
    # a launch that feeds no prompt says nothing of pieces
    assert op.launch_counts([(9, 12)], None, 1, True) == ({}, {})
    seq = np.zeros(SLOTS, np.int32)
    fields, _ = PrefillBatchConfig.np_fields(
        [(0, list(range(4, 41)), 0), (1, list(range(4, 24)), 64)], seq, 16,
        max_tokens=80, max_requests=SLOTS)
    seg = Segments(BatchConfig(*(jnp.asarray(f) for f in fields)), SLOTS)
    piece = (seg.start | (seg.offset % PIECE == 0)) & seg.live
    assert int(piece.sum()) == 3
    count, first, own, row, init, last = (
        np.asarray(a) for a in op._pieces(seg))
    assert int(count) == 3 == pieces([37, 20])[0]["prompt_kda_pieces"]
    assert first[:3].tolist() == [0, 32, 48] and own[:3].tolist() == \
        [32, 5, 20] and row[:3].tolist() == [0, 0, 1]
    # request 0 is fed from position 0, request 1 continues at 64
    assert init[:3].tolist() == [ZEROS, CONTINUE, STORED]
    assert last[:3].tolist() == [0, 1, 1]


def test_the_prompt_launches_count_their_pieces():
    """``prompt_kda_pieces`` on the dispatch spans and in the tick journal,
    against a count by hand: ONE request of 150 tokens goes in chunks of 48,
    48, 48 and 6 rows = 2 + 2 + 2 + 1 pieces; 31.25 per 1000 prompt tokens
    when every piece is full, more here."""
    from flexflow_tpu.obs import Telemetry, journal
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    assert "prompt_kda_pieces" in journal.FIELDS
    im = RIG.deployment(use_pallas=True)
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        im.take_expert_load()   # earlier tests' launches, which no one read
        rm.generate([PROMPT], 5)
        events = tel.trace.trace_events()
        scans = [e["args"] for e in events
                 if e["name"] == "prefill_scan_dispatch"
                 and not e["args"].get("pad")]
        assert sum(a["prompt_tokens"] for a in scans) == 150
        assert sum(a["prompt_kda_pieces"] for a in scans) == 7
        steps = [e["args"] for e in events if e["name"] == "step_dispatch"]
        assert all(a.get("prompt_kda_pieces", 0) == 0 for a in steps)
        records = rm.journal.records()
        assert sum(r["prompt_kda_pieces"] for r in records) == 7
        assert sum(r["prompt_tokens"] for r in records) == 150
        # the memory ledger prices the pair of states; the paths are counted
        im.publish_memory(tel)
        measured = tel.memory.report()["plans"][im.plan_key]
        per_slot = im.kv.bytes_per_slot()
        for kind in ("delta_state", "recurrent", "kv_full"):
            assert measured[f"slot_{kind}_bytes"]["measured"] == \
                per_slot[kind] > 0, kind
        counters = tel.metrics.snapshot()
        assert counters[
            "attention_path.kimi_delta_attention.chunked+neg_eigval"] >= 1
        # ... the FORM; who ran its pieces is counted under a key of its own
        assert counters["attention_path.delta_pieces.delta_rule_chunk"] >= 1
        assert counters["attention_path.attention_gate.elementwise"] >= 1
    finally:
        im.telemetry = type(im).telemetry
