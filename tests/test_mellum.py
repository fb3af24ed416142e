"""Mellum 2 (``mellum``: a sequential pre-norm block; three sliding-window
layers whose cache is a RING to one full layer, rotary in BOTH under two
parameter sets — plain on the rings, YaRN with a stated ``attention_factor``
on the full caches; a mixture of small gated experts chosen by softmax top-k,
renormalised, no shared expert) through the normal serve path, against the
plain reference ``benchmark/reference/mellum.py`` — logits, not tokens.

Toy widths, the real mechanisms: hidden 64; 4 query heads on 2 K/V heads of
16; a window of 32 in a ring of 128 slots with a prompt chunk of 80 rows (5
tiles of 16) — WIDER than the window, as the timed cell's 2048 is wider than
1024, so whole tiles of a chunk lie outside a row's window; YaRN
over an original context of 32 (so every test is past it); a router over 16
experts with top-4, gated experts of width 32; 2 layers, one of each kind
(``SF``: the layer loop is unrolled, so a test's compile time goes with the
depth — tier-1's seconds are short, ROADMAP C15); the head untied.  Weights
are the benchmark's seeded ones in float32.

float32 on the CPU against float32 at HIGHEST precision: they differ by
summation order alone and a log-probability agrees to 2e-4 nats; each listed
break moves one by 4e-3 or more (``test_a_break_is_seen``).

ONE built deployment per kernel mode and process (``RIG.deployment``, reset
between uses; the harness drive and the decode scan run with the kernels ON
in tier-1 — their kernels-off twins are ``slow``: the XLA paths of a ring and
of a routed layer are ``test_cohere2_moe.py``'s every run, and the rotary
this family adds is applied before either path); the dense layer builds a graph of its own with the kernels
off; the breaks are made on the REFERENCE's side, against the one sound
program.  Budget: the file's junit seconds stay under
300 (CHANGES.md, PR 61, has the measured sum).
"""

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

from benchmark import check, seeded_weights as sw  # noqa: E402
from benchmark.reference import mellum as ref  # noqa: E402
from flexflow_tpu.core.op import OpContext  # noqa: E402
from flexflow_tpu.serve import ops as serve_ops  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import (  # noqa: E402
    SlidingWindowAttention,
    _SlotStateOp,
)
from flexflow_tpu.serve.models import mellum as builder  # noqa: E402
from flexflow_tpu.serve.models.base import ServeModelConfig  # noqa: E402
from flexflow_tpu.serve.ssd_moe_ops import MoEExperts, MoERouter  # noqa: E402

from reference_rig import Rig  # noqa: E402

WINDOW, RING = 32, 128
FACTOR = 1.2772588722239782          # 0.1 ln 16 + 1, as published
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": FACTOR}
PLAIN = {"rope_type": "default", "rope_theta": 10000.0}
HF = dict(model_type="mellum", vocab_size=320, hidden_size=64,
          num_hidden_layers=2,
          layer_types=["sliding_attention", "full_attention"],
          mlp_layer_types=["sparse"] * 2,
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          sliding_window=WINDOW, intermediate_size=96,
          moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
          norm_topk_prob=True, rms_norm_eps=1e-6, attention_bias=False,
          tie_word_embeddings=False, max_window_layers=0,
          use_sliding_window=True,
          rope_parameters={"full_attention": YARN,
                           "sliding_attention": PLAIN},
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 2304
          init_std=0.125, torch_dtype="float32")
# a chunk is 80 rows = 5 tiles of 16 — 2.5 windows; the ring 128 slots: the
# chunk that starts at 80 WRAPS the ring inside itself (tiles at 112, 0, 16)
SLOTS, CAP, SEQ = 3, 80, 256
TOL = 2e-4          # nats, see the module docstring
SEED = 6161
LIMITS = {"logit_rms_ulps": 0.02, "logit_max_ulps": 0.1,
          "logprob_rms": 2e-4, "logprob_max": 2e-3,
          "tail_logprob_rms": 2e-4, "token_gap_ulps": 0.1}
RIG = Rig(ref, HF, SLOTS, CAP, SEQ, SEED)
tokens = RIG.tokens
# 4.7 windows: every ring has wrapped, chunk 2 (80..149) wraps it
PROMPT = tokens(150)


def check_prompt_paths(rig, how, prompt, window, ring, sizes):
    want, want_tok = rig.reference_logprobs(prompt + rig.tokens(3, salt=1))
    n = len(prompt)
    assert n > 3 * window and n > ring and rig.cap > 2 * window
    seq_lens = [0] * rig.slots
    pallas = how.endswith("pallas")
    im = rig.deployment(use_pallas=pallas)
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


@pytest.mark.parametrize("how", ["uneven_chunks", "tiled_scan",
                                 "tiled_scan_pallas", "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt, 4.7 windows long, in uneven flat chunks and through
    the tiled prefill scan with a chunk WIDER than the window (block writes
    into the ring, a chunk that wraps it, the prefill kernel's window bound
    with whole tiles outside it, YaRN'd positions past the original
    context), kernels off and on: decode steps then read what each left."""
    im = check_prompt_paths(RIG, how, PROMPT, WINDOW, RING,
                            [7, CAP, 1, 13, 3])
    paths, pallas = im.attention_paths, how.endswith("pallas")
    assert {p for (k, _), p in paths.items() if k == "moe_experts"} == {
        "megablox_gmm" if pallas else "ragged_dot"}
    # a ring layer says its path itself; a full layer's kernels say theirs
    # by what they note: the decode kernel its block plan, the prefill
    # kernel its operands' type (nothing noted: XLA)
    full = "inc_multihead_self_attention"
    assert paths[("sliding_window_attention", "BatchConfig")] == (
        "decode_attention" if pallas else "xla")
    assert paths.get(("decode_block", (full, "BatchConfig"))) == (
        f"full{SEQ}" if pallas else None)
    if how == "tiled_scan_pallas":
        assert paths[("sliding_window_attention",
                      "PrefillBatchConfig")] == "prefill_attention"
        for kind in ("sliding_window_attention", full):
            assert paths[("prefill_operands", kind)] == "float32"
        assert paths[("kv_block_write", "PrefillBatchConfig")] == "pallas"
        assert paths[("decode_block", ("sliding_window_attention",
                                       "BatchConfig"))] == f"ring{RING}"


@pytest.mark.slow
def test_prompt_feeding_paths_at_a_second_seed_and_size():
    """Slow (a second pair of deployments): another seed, a window of 24
    under a chunk of 112 rows (7 tiles), a ring of 256."""
    rig = Rig(ref, dict(HF, sliding_window=24), SLOTS, 112, 512, 99)
    for how in ("tiled_scan", "tiled_scan_pallas", "uneven_chunks_pallas"):
        check_prompt_paths(rig, how, rig.tokens(300), 24, 256, [5, 112, 2])


@pytest.mark.parametrize("use_pallas", [
    pytest.param(False, id="xla", marks=pytest.mark.slow), True], ids=str)
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive: the tiled prefill scan of 220 tokens
    (6.9 windows, past the ring's end), a flat prompt, a JOINER fed flat in
    two pieces and spliced by ``join_slot`` between two chained decode scans
    of the other two rows, flat steps on all three."""
    im = RIG.deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    assert "contexts up to 241" in lines[-1], lines[-1]
    paths = im.attention_paths
    assert paths[("kv_row_write", "one_row_per_request")] == (
        "pallas" if use_pallas else "dus_chain")
    assert paths.get(("decode_block", ("inc_multihead_self_attention",
                                       "one_row_per_request"))) == (
        f"full{SEQ}" if use_pallas else None)


@pytest.mark.parametrize("use_pallas", [
    pytest.param(False, id="xla", marks=pytest.mark.slow), True], ids=str)
def test_decode_scan_carries_what_prefill_leaves(use_pallas):
    """A prompt of 100, then 64 decode steps on the device in two chained
    scans of 32 (across the ring's end at 128, every step's window edge
    inside the ring): the scan's tokens are the reference's greedy ones, and
    flat steps then read, at position 164 on, what the scan wrote."""
    im = RIG.deployment(use_pallas=use_pallas)
    prompt = tokens(100, salt=5)
    seq_lens = [0] * SLOTS
    RIG.feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = RIG.flat_step(im, [(0, prompt[-1:], 99)], seq_lens)
    first = int(toks[0])
    made = RIG.decode_scan(im, 0, first, 100, 64)
    full = prompt + [first] + made                  # 165 tokens
    tail = tokens(2, salt=6)
    # teacher forcing: the reference is fed what the program produced
    want, want_tok = RIG.reference_logprobs(full + tail)
    assert full[100:] == want_tok[99:164].tolist()
    seq_lens[0] = 164
    for k, tok in enumerate([full[164]] + tail[:1]):
        (lp,), _ = RIG.flat_step(im, [(0, [tok], 164 + k)], seq_lens)
        np.testing.assert_allclose(lp[0], want[164 + k], atol=TOL, rtol=0)


# ---- the two mechanisms by hand ------------------------------------------

def test_yarn_half_pairs_and_the_stated_factor_by_hand():
    """At the published sizes (theta 500000, 128 wide, factor 16 over 8192,
    beta 32 / 1): the ramp runs from pair 18 to pair 35; a vector is turned
    on the pairs (j, j + 64) by ``t f_j`` with cos and sin times the STATED
    ``attention_factor`` — not the one ``mscale`` would give."""
    published = dict(YARN, rope_theta=500000, factor=16,
                     original_max_position_embeddings=8192)
    theta, half = 500000.0, 64
    corr = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (
        2 * math.log(theta))
    assert (math.floor(corr(32)), math.ceil(corr(1))) == (18, 35)
    j = np.arange(half)
    plain = theta ** (-j / half)
    ramp = np.clip((j - 18) / (35 - 18), 0.0, 1.0)
    want_f = plain * (1 - ramp) + plain / 16 * ramp
    got_f = np.asarray(serve_ops.rope_frequencies(half, theta, published))
    np.testing.assert_allclose(got_f, want_f, rtol=1e-6)
    assert (got_f[:19] == np.float32(plain[:19])).all()
    np.testing.assert_allclose(got_f[35:], plain[35:] / 16, rtol=1e-6)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((3, 2, 128)).astype(np.float32)
    pos = np.asarray([0, 7, 901])
    ang = pos[:, None].astype(np.float64) * want_f
    cos, sin = FACTOR * np.cos(ang)[:, None], FACTOR * np.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    want = np.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    got = serve_ops.apply_rope(jnp.asarray(x), jnp.asarray(pos), theta,
                               yarn=published)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    # a stated factor wins over the derived one; without one, mscale's
    derived = serve_ops.apply_rope(
        jnp.asarray(x), jnp.asarray(pos), theta,
        yarn={k: v for k, v in published.items() if k != "attention_factor"})
    np.testing.assert_allclose(np.asarray(derived), want, atol=2e-4)
    other = serve_ops.apply_rope(jnp.asarray(x), jnp.asarray(pos), theta,
                                 yarn=dict(published, attention_factor=1.0))
    np.testing.assert_allclose(np.asarray(other) * FACTOR, want, atol=2e-4)
    assert ref.turns({"rope_parameters": {"full_attention": published},
                      "head_dim": 128, "hidden_size": 2304,
                      "num_attention_heads": 32}, "full_attention")[1] == \
        FACTOR


def test_the_attention_without_rope_scaling_lowers_as_it_did():
    """``rope_scaling`` None is the program before this option: the same
    jaxpr as the rotary by theta alone."""
    op = serve_ops.IncMultiHeadSelfAttention(64, 4, 2, 16, rope_theta=1e4)
    assert op.rope_scaling is None
    x = jnp.ones((5, 2, 16))
    pos = jnp.arange(5)
    old = jax.make_jaxpr(lambda x, p: serve_ops.apply_rope(x, p, 1e4))(x, pos)
    new = jax.make_jaxpr(lambda x, p: serve_ops.apply_rope(
        x, p, op.rope_theta, yarn=op.rope_scaling))(x, pos)
    assert str(old) == str(new)
    with pytest.raises(ValueError, match="YaRN or none"):
        serve_ops.IncMultiHeadSelfAttention(
            64, 4, 2, 16, rope_scaling={"rope_type": "linear", "factor": 2})


def test_the_router_is_softmax_top_k_renormalised_row_by_row():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((9, 64)).astype(np.float32)
    w = (0.25 * rng.standard_normal((64, 16))).astype(np.float32)
    op = MoERouter(64, 16, 4, norm_topk=True, bias=False, scoring="softmax")
    ids, wts = op.lower(OpContext(extras={"node_name": "r"}),
                        [jnp.asarray(x)], {"weight": jnp.asarray(w)})
    for row in range(9):
        s = x[row].astype(np.float64) @ w
        p = np.exp(s - s.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:4]
        assert np.asarray(ids[row]).tolist() == chosen.tolist()
        np.testing.assert_allclose(np.asarray(wts[row]),
                                   p[chosen] / p[chosen].sum(), rtol=1e-5)
        assert abs(float(jnp.sum(wts[row])) - 1.0) < 1e-6


def test_expert_tiles_come_from_the_shapes():
    """2304 x 896 whole into the hidden width, 896 x 2304 in 2 tiles of
    1152 out of it (ISSUE 61)."""
    assert MoEExperts.out_tile(2304, 896, 2) == 896
    assert MoEExperts.out_tile(896, 2304, 2) == 1152


# ---- what the configuration says -----------------------------------------

def test_rope_parameters_are_read_by_layer_kind():
    cfg = ServeModelConfig.from_hf_config(HF)
    assert cfg.mlp_layer_types == HF["mlp_layer_types"]
    assert builder.rope_of(cfg, "sliding_attention") == (10000.0, None)
    theta, scaling = builder.rope_of(cfg, "full_attention")
    assert theta == 10000.0 and scaling["attention_factor"] == FACTOR
    im = RIG.build()
    by_kind = {}
    for n in im.model.graph.nodes:
        if n.name.endswith("self_attn"):
            by_kind.setdefault(type(n.op).__name__, []).append(n.op)
    assert len(by_kind["SlidingWindowAttention"]) == 1
    (full,) = by_kind["IncMultiHeadSelfAttention"]
    assert full.rope_scaling == YARN and full.rotary_embedding
    assert all(op.window == WINDOW and not op.rope_interleaved
               for op in by_kind["SlidingWindowAttention"])


@pytest.mark.parametrize("change,needs", [
    ({"rope_parameters": {"sliding_attention": PLAIN}}, "full_attention"),
    ({"rope_parameters": {"full_attention": YARN}}, "sliding_attention"),
    ({"rope_parameters": PLAIN}, "nests its rotary parameters"),
    ({"rope_parameters": {"full_attention": YARN,
                          "sliding_attention": YARN}}, "plain rope_theta"),
    ({"rope_parameters": {"full_attention": dict(YARN, rope_type="llama3"),
                          "sliding_attention": PLAIN}}, "llama3"),
    ({"mlp_layer_types": ["sparse", "moe"]}, "moe"),
    ({"mlp_layer_types": ["sparse"] * 3}, "names every layer"),
    ({"attention_bias": True}, "attention_bias"),
    ({"layer_types": ["sliding_attention"] * 2, "sliding_window": None},
     "sliding_window"),
])
def test_the_builder_refuses_what_it_does_not_build(change, needs):
    with pytest.raises(ValueError, match=needs):
        RIG.build(hf=dict(HF, **change))


@pytest.mark.parametrize("kw,needs", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"kv_page_size": 16}, "kv_page_size"),
    ({"max_spec_tokens": 4}, "speculation"),
])
def test_combinations_a_ring_lacks_raise_at_compile(kw, needs):
    with pytest.raises(ValueError) as err:
        RIG.build(**kw)
    assert "SlidingWindowAttention" in str(err.value)
    assert needs in str(err.value)


def test_the_published_tensor_names_are_listed_for_an_importer():
    from flexflow_tpu.serve.weights import MELLUM_TENSORS

    for name, _, _ in ref.LAYER:
        key = name if name.endswith(".weight") else name + ".weight"
        key = key.replace("mlp.experts.", "mlp.experts.<e>.")
        assert key in MELLUM_TENSORS, name
    for name in ("model.embed_tokens.weight", "model.norm.weight",
                 "lm_head.weight"):
        assert name in MELLUM_TENSORS


# ---- a dense layer, and the breaks ---------------------------------------

def test_a_dense_layer_is_a_plain_gated_mlp():
    """``mlp_layer_types`` may say ``dense`` (the published list never
    does): a gated MLP of ``intermediate_size`` (one layer, built apart)."""
    hf = dict(HF, num_hidden_layers=1, layer_types=["sliding_attention"],
              mlp_layer_types=["dense"])
    rig = Rig(ref, hf, SLOTS, CAP, SEQ, SEED)
    ids = tokens(70, salt=21)
    want, _ = rig.reference_logprobs(ids)
    got = rig.feed_flat(rig.seeded(rig.build()), 0, ids, [CAP], [0] * SLOTS)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    as_sparse, _ = Rig(ref, dict(hf, mlp_layer_types=["sparse"]), SLOTS, CAP,
                       SEQ, SEED).reference_logprobs(ids)
    assert np.abs(as_sparse - want).max() > 20 * TOL


BREAKS = {
    "sound": {},
    "attention_factor_dropped": {"rope_parameters": {
        "full_attention": dict(YARN, attention_factor=1.0),
        "sliding_attention": PLAIN}},
    "the_rings_theta_on_the_full_layer": {"rope_parameters": {
        "full_attention": PLAIN, "sliding_attention": PLAIN}},
    "no_renormalisation": {"norm_topk_prob": False},
    "window_off_by_one": {"sliding_window": WINDOW + 1},
    "interleaved_pairs": {},
}


@pytest.mark.parametrize("broken", list(BREAKS))
def test_a_break_is_seen(broken, monkeypatch):
    """The one sound PROGRAM on 90 positions (past the window and the
    YaRN'd original context) against the reference with one thing wrong on
    ITS side — what holds the two together is the tolerance, and a break
    moves a log-probability the same way whichever side makes it: the sound
    reference is within the tolerance, every break 20 x outside it."""
    if broken == "interleaved_pairs":
        def wrong(hf, kind, x):
            f, amp = ref.turns(hf, kind)
            ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f
            cos, sin = amp * jnp.cos(ang)[:, None], amp * jnp.sin(ang)[:, None]
            a, b = x[..., 0::2], x[..., 1::2]
            return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                             axis=-1).reshape(x.shape)

        monkeypatch.setattr(ref, "rope", wrong)
    ids = tokens(90, salt=31)
    got = RIG.feed_flat(RIG.deployment(), 0, ids, [CAP], [0] * SLOTS)
    # a fresh rig: the reference's jitted layers are cached by configuration,
    # and the patched rotary is not part of one
    want, _ = Rig(ref, dict(HF, **BREAKS[broken]), SLOTS, CAP, SEQ,
                  SEED).reference_logprobs(ids)
    err = np.abs(got - want).max()
    assert (err < TOL) if broken == "sound" else (err > 20 * TOL), err


# ---- what the launches say -----------------------------------------------

def test_the_prompt_launches_count_their_experts_and_ring_reads():
    """``prefill_expert_*`` on the ``commit`` spans and in the tick journal,
    ``prompt_ring_ctx_sum`` on the dispatch spans, against a count by hand:
    every prompt row routes to 4 of 16 held experts in each of 2 layers, and
    reads min(position + 1, window) keys of a ring layer."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = RIG.deployment(use_pallas=True)
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im.take_expert_load()   # earlier tests' launches, which no one read
        lens = (150, 70, 100)
        rm.generate([tokens(n, salt=40 + n) for n in lens], 5)
        events = tel.trace.trace_events()
        scans = [e["args"] for e in events
                 if e["name"] == "prefill_scan_dispatch"
                 and not e["args"].get("pad")]
        assert sum(a["prompt_tokens"] for a in scans) == sum(lens)
        by_hand = sum(min(p + 1, WINDOW) for n in lens for p in range(n))
        assert sum(a["prompt_ring_ctx_sum"] for a in scans) == by_hand
        (ring,) = [n.op for n in im.model.graph.nodes
                   if isinstance(n.op, SlidingWindowAttention)][:1]
        assert ring.launch_counts(
            None, [(0, 5), (30, 40), (WINDOW, 99)], 3, True) == ({
                "prompt_ring_ctx_sum": 15 + (31 + 32 + 8 * WINDOW)
                + (99 - WINDOW) * WINDOW}, {})
        commits = [e["args"] for e in events if e["name"] == "commit"]
        total = lambda k: sum(c.get(k, 0) for c in commits)
        layers, k, held = 2, 4, 16
        chunks = sum(a["n_chunks"] for a in scans)
        assert total("prefill_expert_pairs") == sum(lens) * k * layers
        assert total("prefill_expert_chunks") == chunks * layers
        # 22 tiles of 16 rows in chunks of 5 tiles
        assert chunks >= 5
        assert 0 < total("prefill_experts_visited") <= chunks * layers * held
        assert total("prefill_expert_pairs") / held / layers / chunks <= \
            total("prefill_expert_pairs_max") / layers / chunks <= CAP * k
        # the decode scans keep their own four
        assert total("expert_steps") and total("expert_pairs") == \
            total("scan_tokens") * k * layers
        records = rm.journal.records()
        for field in ("prefill_expert_pairs", "prefill_expert_chunks",
                      "prefill_experts_visited", "prefill_expert_pairs_max"):
            assert sum(r[field] for r in records) == total(field)
        assert sum(r["prompt_ring_ctx_sum"] for r in records) == by_hand
        counters = tel.metrics.snapshot()
        assert counters["moe.prefill_pairs"] == total("prefill_expert_pairs")
        assert counters["moe.prefill_visited"] == \
            total("prefill_experts_visited")
        assert im.take_expert_load() is None
    finally:
        im.telemetry = type(im).telemetry


# ---- the seam: what a launch means to an op lives beside the op -----------

class _Tally(_SlotStateOp):
    """An op no module of the program names: it counts a launch's rows."""

    launch_reads = ("step",)

    def __init__(self, step):
        self.step = step

    def launch_counts(self, decode, prompt, layers, counted):
        rows = len(decode or ()) + len(prompt or ())
        return ({"tally_rows": self.step * rows},
                {"tally.launches": layers} if rows and counted else {})


class _Silent(_SlotStateOp):
    """A per-slot state that says nothing of a launch (the default)."""


def _graph(*ops):
    node = lambda op: type("Node", (), {"op": op})()
    return type("Graph", (), {"nodes": [node(op) for op in ops]})()


def test_one_walk_of_the_graph_asks_each_class_once():
    """``launch_counter``: the first node of a class answers for its
    siblings (``layers`` says how many), the classes' answers merge, an op
    that keeps the default is not asked, a graph without any gives nothing
    — and siblings that differ in what the hook reads are refused.  Where
    no one keeps the counters, none is made."""
    from flexflow_tpu.serve.hybrid_ops import launch_counter

    ring = SlidingWindowAttention(64, 4, 2, 16, window=8)
    launch = launch_counter(_graph(_Tally(2), _Silent(), ring, _Tally(2),
                                   object()))
    count = lambda decode, prompt: launch(decode, prompt, True)
    assert count([(3, 4), (20, 21)], [(0, 5)]) == (
        {"tally_rows": 6, "ring_ctx_sum": 4 + 8,
         "prompt_ring_ctx_sum": 15}, {"tally.launches": 2})
    assert launch([(3, 4), (20, 21)], [(0, 5)], False) == (
        count([(3, 4), (20, 21)], [(0, 5)])[0], {})
    # a decode scan feeds no prompt, a prefill scan decodes nothing: the
    # names that speak of the missing rows are left out, not zero
    assert count([(3, 7)], None) == (
        {"tally_rows": 2, "ring_ctx_sum": 4}, {"tally.launches": 2})
    assert count(None, [(0, 5), (8, 9)]) == (
        {"tally_rows": 4, "prompt_ring_ctx_sum": 15 + 8},
        {"tally.launches": 2})
    assert count([], []) == (
        {"tally_rows": 0, "ring_ctx_sum": 0, "prompt_ring_ctx_sum": 0}, {})
    assert launch_counter(_graph(_Silent(), object()))(
        [(3, 4)], [], True) == ({}, {})
    with pytest.raises(AssertionError, match="step"):
        launch_counter(_graph(_Tally(2), _Tally(3)))


def test_an_op_the_scheduler_never_heard_of_counts_its_launches():
    """The seam, held: a subclass defined HERE returns a made-up argument
    and a made-up counter from ``launch_counts`` — both are on the
    ``prefill_scan_dispatch``, ``decode_scan_dispatch`` and ``step_dispatch``
    spans and in the metrics registry of served requests, with no edit of
    ``request_manager.py`` — beside what the class it extends says."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = RIG.deployment(use_pallas=True)
    (ring,) = [n.op for n in im.model.graph.nodes
               if isinstance(n.op, SlidingWindowAttention)][:1]

    class Tallied(type(ring)):
        def launch_counts(self, decode, prompt, layers, counted):
            args, counters = super().launch_counts(decode, prompt, layers,
                                                   counted)
            rows = len(decode or ()) + len(prompt or ())
            return ({**args, "tally_rows": rows},
                    {**counters, "tally.rows": rows * layers})

    tel = Telemetry()
    ring.__class__ = Tallied
    try:
        rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                            telemetry=tel)
        # 1 token off the prompt, then scans of 4 and 2 steps and a flat
        # step for the last
        rm.generate([tokens(70, salt=66), tokens(40, salt=67)], 8)
        launches = {}
        for e in tel.trace.trace_events():
            if e["name"].endswith("_dispatch") and not e["args"].get("pad"):
                launches.setdefault(e["name"], []).append(e["args"])
        assert set(launches) == {"prefill_scan_dispatch",
                                 "decode_scan_dispatch", "step_dispatch"}
        assert all("tally_rows" in a for v in launches.values() for a in v)
        assert all(a["tally_rows"] == a["segments"]
                   and "prompt_ring_ctx_sum" in a
                   for a in launches["prefill_scan_dispatch"])
        assert all(a["tally_rows"] == 2 and "ring_ctx_sum" in a
                   for a in launches["decode_scan_dispatch"])
        assert tel.metrics.snapshot()["tally.rows"] == sum(
            a["tally_rows"] for v in launches.values() for a in v)
    finally:
        ring.__class__ = SlidingWindowAttention
        im.telemetry = type(im).telemetry
