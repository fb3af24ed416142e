"""Kimi Linear (``kimi_linear``: Kimi Delta Attention — a gated delta rule
with a per-channel decay over a float32 matrix a head, behind three short
convolutions — in three layers of four, latent attention WITHOUT a positional
term in the fourth, one leading dense layer, then sigmoid-routed mixtures
beside one shared expert) through the normal serve path, against the plain
reference ``benchmark/reference/kimi_linear.py`` — logits, not tokens.

Toy widths, the real mechanisms: hidden 64; 4 delta-rule heads of 16 behind
convs of 4 taps; the latent layer 4 heads of 16 + 8 (values 16) on a latent
of 32 and a second key part of 8, nothing rotated; five layers — dense KDA,
KDA, KDA, latent, KDA, the published period — with layer 1 dense (width 96)
and layers 2-5 a sigmoid router over 32 experts top-8 renormalised x 2.446 of
which THIS graph holds 4 (share 0 of 8: one of a row's eight choices lands
here on average, as in the benchmark's cut), width 24, one shared expert; the
head untied.  Weights are the benchmark's seeded ones in float32, the decay
and the convs through ``published_init``.

The reference runs the delta rule token by token and expands every head's
keys and values (the MATERIALISED form); the program runs prompt chunks
through the CHUNKED form, decode rows through the step kernel (interpret
mode) or its XLA oracle, and reads the latent cache ABSORBED.  float32 on the
CPU against float32 at HIGHEST precision: a log-probability agrees to 3e-4
nats — each break of ``test_a_break_is_seen`` (tests/test_kimi_linear_breaks.py:
a file of its own, so that the two share no worker) moves it by 6e-3 or more.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_tpu_aot_compile import _pallas_calls  # noqa: E402

from benchmark import check, seeded_weights as sw  # noqa: E402
from benchmark.reference import kimi_linear as ref  # noqa: E402
from flexflow_tpu.config import FFConfig  # noqa: E402
from flexflow_tpu.model import FFModel  # noqa: E402
from flexflow_tpu.ops.pallas.delta_rule import delta_rule_step  # noqa: E402
from flexflow_tpu.parallel.mesh import make_mesh  # noqa: E402
from flexflow_tpu.serve import BatchConfig  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import (  # noqa: E402
    CausalConv1d,
    KimiDeltaAttention,
    LatentAttention,
    Segments,
    unit_lower_inverse,
)
from flexflow_tpu.serve.inference_manager import InferenceManager  # noqa: E402
from flexflow_tpu.serve.models import kimi_linear as builder  # noqa: E402
from flexflow_tpu.serve.models.base import (  # noqa: E402
    ServeModelConfig,
    build_model,
)
from flexflow_tpu.serve.ssd_moe_ops import MoEExperts, MoERouter  # noqa: E402

LISTS = dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4], num_heads=4,
             head_dim=16, short_conv_kernel_size=4)
HF = dict(model_type="kimi_linear", vocab_size=320, hidden_size=64,
          num_hidden_layers=5, linear_attn_config=LISTS,
          num_attention_heads=4, num_key_value_heads=4, head_dim=16,
          kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
          rope_scaling=None, rope_theta=10000, intermediate_size=96,
          moe_intermediate_size=24, num_experts=4, router_num_experts=32,
          expert_share_index=0, expert_share_count=8,
          num_experts_per_token=8, num_shared_experts=1,
          first_k_dense_replace=1, moe_layer_freq=1, moe_renormalize=True,
          moe_router_activation_func="sigmoid", num_expert_group=1,
          topk_group=1, use_grouped_topk=True, routed_scaling_factor=2.446,
          rms_norm_eps=1e-5, hidden_act="silu", tie_word_embeddings=False,
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 2304
          init_std=0.125, torch_dtype="float32")
LAYERS, KDA_LAYERS, HEADS, HD, RANK, ROPE = 5, 4, 4, 16, 32, 8
SLOTS, CAP, SEQ = 3, 48, 512
TOL = 3e-4          # nats, see the module docstring
SEED = 5757


def build(cap=CAP, seq=SEQ, use_pallas=False, hf=HF, slots=SLOTS, **kw):
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, ServeModelConfig.from_hf_config(hf), cap)
    return InferenceManager(ff, max_requests=slots, max_tokens_per_batch=cap,
                            max_seq_len=seq, topk=HF["vocab_size"],
                            use_pallas=use_pallas, **kw)


def seeded(im, hf=HF):
    im.init_operators_inference()
    like = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        im.params)
    im.params = sw.program_params(ref, hf, sw.base_key(SEED), like, "float32")
    return im


@functools.lru_cache(maxsize=None)
def deployment(use_pallas=False):
    """One compiled deployment per kernel setting, shared by the tests (each
    starts its sequences at position 0 of a slot)."""
    return seeded(build(use_pallas=use_pallas))


@functools.lru_cache(maxsize=None)
def _ref_layer(padded_len, hf_items):
    hf = json.loads(hf_items)
    return jax.jit(lambda key, i, x: ref.layer(
        hf, sw.draw_table(key, i, ref.LAYER, hf, "float32"), x))


def reference_logprobs(ids, hf=HF):
    """The reference's full forward pass of ``ids``: sorted
    log-probabilities at every position, and its greedy tokens."""
    key = sw.base_key(SEED)
    g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, hf, "float32")
    padded = np.zeros(-(-len(ids) // 64) * 64, np.int32)
    padded[:len(ids)] = ids
    x = ref.embed(hf, g, jnp.asarray(padded[None]))
    layer = _ref_layer(len(padded), json.dumps(hf, sort_keys=True))
    for i in range(ref.num_layers(hf)):
        x = layer(key, jnp.int32(i), x)
    logits = ref.head(hf, g, x[:, :len(ids)])[0]
    lp = jax.nn.log_softmax(logits, axis=-1)
    return (np.asarray(jnp.sort(lp, axis=-1)[:, ::-1]),
            np.asarray(jnp.argmax(logits, axis=-1)))


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
                           max_tokens=im.max_tokens,
                           max_requests=im.max_requests)
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
    seq = np.zeros(im.max_requests, np.int32)
    seq[slot] = position + 1
    bc = BatchConfig.build([first], [slot], [position], seq,
                           max_tokens=im.max_tokens,
                           max_requests=im.max_requests)
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


PROMPT = tokens(170)


# ---- (a) prompt feeding, decode, a joiner, a reused slot ---------------------
@pytest.mark.parametrize("how", ["uneven_chunks", "tiled_scan",
                                 "tiled_scan_pallas", "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in uneven flat chunks (pieces that end inside a
    chunk, a chunk of one row) and through the tiled prefill scan, kernels
    off and on: the chunked form carries the delta state and the conv tail
    across chunks, decode steps then read what each left."""
    want, want_tok = reference_logprobs(PROMPT + tokens(3, salt=1))
    n = len(PROMPT)
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
    assert paths[("kimi_delta_attention", "BatchConfig")] == "chunked"
    assert paths[("latent_attention", "BatchConfig")] == (
        "decode_attention_latent" if pallas else "xla_absorbed")
    if how == "tiled_scan_pallas":
        assert paths[("kimi_delta_attention", "PrefillBatchConfig")] == \
            "chunked"
        assert paths[("latent_attention", "PrefillBatchConfig")] == \
            "xla_tile_absorbed"


# readings here: 0.0004 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive: the tiled prefill scan of 380 tokens,
    a flat prompt, a JOINER spliced by ``join_slot`` between two chained
    decode scans of the other two rows (admitted among decoders: its fresh
    segment starts from zero state beside theirs), flat steps on all
    three."""
    im = deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    assert "contexts up to 401" in lines[-1], lines[-1]
    paths = im.attention_paths
    assert paths[("kimi_delta_attention", "one_row_per_request")] == (
        "delta_rule_step" if use_pallas else "xla_rows")
    assert paths[("kimi_delta_attention", "PrefillBatchConfig")] == "chunked"
    # the FORM above; who ran its pieces under a key of its own
    assert paths[("delta_pieces", "kimi_delta_attention")] == (
        "delta_rule_chunk" if use_pallas else "xla_loop")
    kinds = {k for k, _ in paths}
    assert kinds - {"kv_block_write", "kv_row_write"} == {
        "kimi_delta_attention", "latent_attention", "moe_experts",
        "causal_conv1d", "delta_pieces"} \
        | ({"decode_block"} if use_pallas else set())
    # the conv's two forms: the decode scans step the tails in slot order,
    # the prompt's chunks and the flat steps go by rows
    assert {b: p for (k, b), p in paths.items() if k == "causal_conv1d"} == {
        "one_row_per_request": "slot_order", "PrefillBatchConfig": "rows",
        "BatchConfig": "rows"}


def test_flat_rows_of_several_requests_go_by_segments():
    """One flat step holds the ends of two prompts and a decode row of a
    third request: each piece starts from ITS slot's delta state and conv
    tail, and leaves its own behind."""
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


def test_a_reused_slot_starts_from_zero_state():
    """A slot that served a long request then serves a short one: its delta
    states and tails are zero again at position 0 (no reset program: a
    segment that starts at 0 starts from zero), and the latents past the new
    frontier are masked."""
    im = deployment()
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(200, salt=21), [CAP], seq_lens)
    assert all(float(jnp.abs(b["kda"][2]).max()) > 1e-3
               for b in im.state.values() if "kda" in b)
    short = tokens(25, salt=22)
    want, _ = reference_logprobs(short)
    got = feed_flat(im, 2, short, [11, 3], seq_lens)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


# ---- (b) the decode scan against flat steps ----------------------------------
@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_scan_carries_what_prefill_leaves(use_pallas):
    """A prompt of 100, then 40 decode steps on the device in chained scans
    (the step kernel in interpret mode, or its XLA oracle): the scan's
    tokens are the reference's greedy ones; flat steps then read what the
    scan wrote; and the delta states, tails and planes it left are those the
    same 140 tokens leave when PREFILLED (chunked) into another slot."""
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
    seen = {}
    for node, bufs in im.state.items():
        for name, buf in bufs.items():
            a, b = buf[0], buf[2]
            if name in ("ckv", "kpe"):
                a, b = a[:, :140], b[:, :140]
            assert float(jnp.abs(a).max()) > 1e-3, (node, name)
            np.testing.assert_allclose(a, b, atol=3e-5, rtol=2e-4)
            seen[name] = seen.get(name, 0) + 1
    assert seen == {"kda": KDA_LAYERS, "conv": KDA_LAYERS, "ckv": 1, "kpe": 1}
    seq_lens[0] = 140
    for k, tok in enumerate([full[140]] + tokens(2, salt=6)):
        (got,), _ = flat_step(im, [(0, [tok], 140 + k)], seq_lens)
        np.testing.assert_allclose(got[0], want[140 + k], atol=TOL, rtol=0)


# ---- (c) the chunked form against the recurrence ------------------------------
def _delta_inputs(rows, seed=0, heads=2, d=16, strong=False):
    """Inputs of the delta rule for ``rows`` flat rows: unit keys, queries,
    values, a log-decay a channel and a beta a head.  ``strong``: decays of
    e^-20 .. e^-60 a step in half the channels — over a piece of 32 rows
    ``exp(-G)`` would reach e^1900, far past float32."""
    rng = np.random.default_rng(seed)
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k, v = unit(normal(rows, heads, d)) * d ** -0.5, \
        unit(normal(rows, heads, d)), normal(rows, heads, d)
    g = -jnp.exp(normal(rows, heads, d) - 2.0)
    if strong:
        g = jnp.where(jnp.arange(d) % 2 == 0, g,
                      -20.0 - 40.0 * jnp.abs(normal(rows, heads, d)))
    beta = jax.nn.sigmoid(normal(rows, heads))
    return q, k, v, g, beta


def _segments(pieces, slots):
    """``Segments`` of a flat batch of ``pieces`` = [(slot, start, rows)];
    slot -1 is a pad."""
    req = sum(([s] * n for s, _, n in pieces), [])
    pos = sum((list(range(p, p + n)) for _, p, n in pieces), [])
    bc = BatchConfig(tokens=jnp.zeros(len(req), jnp.int32),
                     request_index=jnp.asarray(req, jnp.int32),
                     token_position=jnp.asarray(pos, jnp.int32),
                     num_tokens=jnp.int32(len(req)),
                     seq_lens=jnp.zeros((slots,), jnp.int32))
    return Segments(bc, slots)


CHUNKED = {
    "one_request_across_pieces": ([(0, 0, 70)], 16),
    "a_piece_of_one_row": ([(1, 0, 33)], 32),
    "two_requests_in_one_piece": ([(0, 0, 5), (2, 0, 9)], 16),
    "a_carried_state_and_a_fresh_one": ([(1, 40, 21), (0, 0, 11)], 8),
    "pads_between_requests": ([(0, 0, 7), (-1, 0, 3), (2, 12, 10),
                               (-1, 0, 2)], 4),
    "every_row_its_own_request": ([(0, 9, 1), (1, 30, 1), (2, 0, 1)], 8),
}


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
@pytest.mark.parametrize("case", list(CHUNKED))
def test_chunked_form_equals_the_recurrence(case, strong):
    """``KimiDeltaAttention._chunked`` against the reference's token-by-token
    recurrence, to float32 rounding: across piece boundaries, two requests
    in one piece, a state carried in from the slot beside a fresh one, pads
    — at decays so strong that a naive ``exp(-G)`` overflows."""
    pieces, chunk = CHUNKED[case]
    slots, heads, d = 3, 2, 16
    rows = sum(n for _, _, n in pieces)
    q, k, v, g, beta = _delta_inputs(rows, seed=len(case), strong=strong)
    rng = np.random.default_rng(1)
    kda = jnp.asarray(rng.standard_normal((slots + 1, heads, d, d)),
                      jnp.float32)
    op = KimiDeltaAttention(64, heads, d, chunk=chunk)
    out, new = jax.jit(lambda *a: op._chunked(*a, _segments(pieces, slots)))(
        q, k, v, g, beta, kda)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(new).all())
    at, touched = 0, set()
    for slot, start, n in pieces:
        rows_ = slice(at, at + n)
        at += n
        if slot < 0:
            np.testing.assert_array_equal(out[rows_], 0.0)
            continue
        # the recurrence from the slot's stored state (zero at position 0):
        # the reference's scan, entered with that state
        s = jnp.zeros((heads, d, d)) if start == 0 else kda[slot]
        want = []
        for t in range(at - n, at):
            s = s * jnp.exp(g[t])[..., None]
            u = v[t] - jnp.sum(s * k[t][..., None], axis=-2)
            s = s + (beta[t][..., None] * k[t])[..., None] * u[..., None, :]
            want.append(jnp.sum(s * q[t][..., None], axis=-2))
        np.testing.assert_allclose(out[rows_], jnp.stack(want), atol=2e-5,
                                   rtol=2e-4)
        np.testing.assert_allclose(new[slot], s, atol=2e-5, rtol=2e-4)
        touched.add(slot)
    for slot in set(range(slots)) - touched:
        np.testing.assert_array_equal(new[slot], kda[slot])
    # the reference's own scan is that recurrence
    whole = ref.delta_rule(*(a[None] for a in (q, k, v, g, beta)))[0]
    if len(pieces) == 1 and pieces[0][1] == 0:
        np.testing.assert_allclose(out, whole, atol=2e-5, rtol=2e-4)


def test_unit_lower_inverse_is_the_forward_substitution():
    rng = np.random.default_rng(2)
    # entries as the delta rule's are: beta_i k_i . k_j decay, under 1
    n = jnp.tril(jnp.asarray(0.3 * rng.standard_normal((3, 32, 32)),
                             jnp.float32), -1)
    want = np.linalg.inv(np.eye(32) + np.asarray(n, np.float64))
    np.testing.assert_allclose(unit_lower_inverse(n), want,
                               atol=1e-5 * np.abs(want).max(), rtol=1e-4)
    with pytest.raises(ValueError, match="power of two"):
        KimiDeltaAttention(64, 2, 16, chunk=24)


# ---- (d) the step kernel against its XLA oracle -------------------------------
@pytest.mark.parametrize("case", ["all_live", "pads_between", "pads_first",
                                  "all_pads"])
def test_step_kernel_equals_the_xla_oracle(case):
    """``delta_rule_step`` (interpret mode) against the op's XLA rows path on
    the same state: the outputs and every slot's state; a pad row — between
    live rows, before the first, or a batch of nothing else — leaves EVERY
    slot's state untouched, bit for bit."""
    slots, heads, d = 5, 4, 16
    rows, live = {
        "all_live": ([3, 0, 4, 1, 2], [1, 1, 1, 1, 1]),
        "pads_between": ([3, 5, 5, 0, 5, 2], [1, 0, 0, 1, 0, 1]),
        "pads_first": ([5, 5, 1, 4], [0, 0, 1, 1]),
        "all_pads": ([5, 5, 5], [0, 0, 0]),
    }[case]
    t = len(rows)
    q, k, v, g, beta = _delta_inputs(t, seed=t, heads=heads, d=d)
    rng = np.random.default_rng(3)
    kda = jnp.asarray(rng.standard_normal((slots + 1, heads, d, d)),
                      jnp.float32)
    req = [r if on else -1 for r, on in zip(rows, live)]
    seg = _segments([(r, 7 + i, 1) for i, r in enumerate(req)], slots)
    op = KimiDeltaAttention(64, heads, d)

    def run(pallas):
        from flexflow_tpu.core.op import OpContext

        ctx = OpContext(extras={"pallas_decode": pallas,
                                "pallas_interpret": pallas})
        return op._step(q, k, v, g, beta, kda, seg, ctx)

    want, want_state, how = run(False)
    got, got_state, path = run(True)
    assert (how, path) == ("xla_rows", "delta_rule_step")
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    held = [r for r, on in zip(rows, live) if on]
    for slot in range(slots):
        if slot in held:
            np.testing.assert_allclose(got_state[slot], want_state[slot],
                                       atol=1e-5, rtol=1e-5)
            assert float(jnp.abs(got_state[slot] - kda[slot]).max()) > 1e-3
        else:
            np.testing.assert_array_equal(got_state[slot], kda[slot])
    np.testing.assert_array_equal(got[jnp.asarray(live) == 0], 0.0)
    # the kernel alone, called as the op calls it: one pallas_call whose
    # state operand is aliased to its output
    jaxpr = jax.make_jaxpr(lambda s: delta_rule_step(
        s, jnp.exp(g), k, k, q, v, seg.rows, seg.live, interpret=True))(kda)
    call, = _pallas_calls(jaxpr.jaxpr)
    assert dict(call.params["input_output_aliases"]) == {3: 1}


# ---- (h) the share adds up ------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The deployment's cut against the model: a routed layer's mixture on
    the WHOLE layer (32 experts held of 32) equals the eight shares' routed
    parts summed, the shared expert — which every chip computes alike —
    counted once; and share 0 is what the program's graph holds."""
    whole = dict(HF, num_experts=32, router_num_experts=32,
                 expert_share_index=0, expert_share_count=1)
    w = sw.draw_table(sw.base_key(SEED), 2, ref.LAYER, whole, "float32")
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
    assert {k: cut_hf[k] for k in ("num_experts", "router_num_experts",
                                   "expert_share_index",
                                   "expert_share_count")} == \
        {k: HF[k] for k in ("num_experts", "router_num_experts",
                            "expert_share_index", "expert_share_count")}
    # the program on another share follows the reference given that share
    hf = dict(HF, expert_share_index=5, num_hidden_layers=2,
              linear_attn_config=dict(LISTS, kda_layers=[1],
                                      full_attn_layers=[2]))
    ids_in = tokens(30, salt=81)
    got = feed_flat(seeded(build(hf=hf), hf=hf), 0, ids_in, [CAP],
                    [0] * SLOTS)
    want, _ = reference_logprobs(ids_in, hf=hf)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    lower, _ = reference_logprobs(ids_in, hf=dict(hf, expert_share_index=0))
    assert np.abs(want - lower).max() > 20 * TOL


# ---- (g) bytes, (f) refusals, names --------------------------------------------
def test_a_slot_is_priced_by_position_and_by_state():
    """The allocator against the hand formula: by POSITION the one latent
    layer's 32 + 8 float32 values (the toy analogue of 1 152 B) and nothing
    else; FIXED a slot the four delta states (4 heads x 16 x 16 float32) and
    their conv tails (3 rows of 3 x 64 channels); admission and occupancy by
    both."""
    im = deployment()
    share = (SLOTS + 1) / SLOTS              # the scratch row, amortised
    per_pos = (RANK + ROPE) * 4 * share
    state = KDA_LAYERS * HEADS * HD * HD * 4 * share
    tails = KDA_LAYERS * 3 * 3 * HEADS * HD * 4 * share
    near = lambda x: pytest.approx(x, rel=1e-12)
    assert im.kv.bytes_per_token() == near(per_pos)
    per_slot = im.kv.bytes_per_slot()
    assert per_slot["delta_state"] == near(state)
    assert per_slot["recurrent"] == near(tails)
    assert per_slot["kv_latent"] == near(per_pos * SEQ)
    assert per_slot["kv_full"] == per_slot["linear_state"] == \
        per_slot["ssd_state"] == per_slot["kv_window"] == 0
    assert im.kv.fixed_bytes_per_slot() == near(state + tails)
    assert im.kv.request_bytes(100) == near(state + tails + 100 * per_pos)
    snap = im.kv.observe({0: 100})
    assert snap["live_bytes"] == near(100 * per_pos)
    assert snap["occupancy_frac"] == near(100 / (SLOTS * SEQ))
    im.kv.release(0)
    # the published widths: 2 097 152 B of state and 73 728 B of tail a KDA
    # layer, 1 152 B a position in the latent layer
    op = KimiDeltaAttention(2304, 32, 128, dtype=jnp.bfloat16)
    (shape, dt, _), = op.state_specs(256, 10240).values()
    assert (np.prod(shape[1:]) * 4, dt) == (2097152, "float32")
    conv = CausalConv1d(3 * 4096, 4, dtype=jnp.bfloat16, bias=False)
    (shape, _, _), = conv.state_specs(256, 10240).values()
    assert np.prod(shape[1:]) * 2 == 73728
    assert [p.name for p in conv.params()] == ["weight"]
    assert [p.name for p in CausalConv1d(8, 4).params()] == ["weight", "bias"]


def test_admission_prices_the_state_and_the_positions():
    from flexflow_tpu.serve.request_manager import (GenerationConfig,
                                                    RequestManager)
    from flexflow_tpu.serve.resilience import ResilienceConfig

    im = deployment()
    one = im.kv.request_bytes(40 + 8)
    assert im.kv.fixed_bytes_per_slot() > 0.5 * one     # mostly state
    rm = RequestManager(
        im, GenerationConfig(max_new_tokens=8, stop_on_eos=False),
        resilience=ResilienceConfig(kv_gate=True,
                                    kv_budget_bytes=1.5 * one))
    first = rm.register_new_request(tokens(40, salt=41))
    second = rm.register_new_request(tokens(40, salt=42))
    assert rm.requests[first].status.name != "REJECTED"
    assert rm.requests[second].status.name == "REJECTED"


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=16), "a snapshot of the float32 matrix a head"),
    (dict(kv_dtype="int8"), "a delta state is float32 by its recurrence"),
    (dict(max_spec_tokens=4), "a delta state has no rollback at all"),
])
def test_combinations_not_written_yet_raise_at_compile(kw, needs):
    with pytest.raises(ValueError, match="KimiDeltaAttention") as e:
        build(**kw)
    assert needs in str(e.value)
    assert "LatentAttention" in str(e.value)    # both kinds, each its lack


@pytest.mark.parametrize("how", ["tp", "pp"])
def test_sharded_deployments_raise_at_compile(how):
    from flexflow_tpu.serve.inference_manager import \
        refuse_unsupported_slot_state

    im = deployment()
    kw = dict(tp=2) if how == "tp" else dict(pipelined=True)
    with pytest.raises(ValueError) as e:
        refuse_unsupported_slot_state(im.model.graph, **kw)
    text = str(e.value)
    assert "KimiDeltaAttention" in text
    if how == "tp":
        assert "for the delta rule a rule that shards its heads" in text
        assert "an exchange of rows" in text
    else:
        assert "pp > 1" in text and "nor a delta state's matrices" in text


def test_the_builder_refuses_what_it_does_not_build():
    for change, needs in [
            (dict(q_lora_rank=24), "query down-projection"),
            (dict(mla_use_nope=False), "without a positional term"),
            (dict(num_expert_group=2), "group-limited"),
            (dict(moe_router_activation_func="softmax"),
             "moe_router_activation_func"),
            (dict(kv_lora_rank=None), "kv_lora_rank"),
            (dict(router_num_experts=2), "not among the router's"),
            (dict(linear_attn_config=dict(LISTS, kda_layers=[1, 2, 3])),
             "neither"),
            (dict(linear_attn_config=dict(LISTS, full_attn_layers=[4, 5])),
             "both"),
            (dict(linear_attn_config={k: v for k, v in LISTS.items()
                                      if k != "head_dim"}),
             "linear_attn_config.head_dim")]:
        with pytest.raises(ValueError, match=needs):
            build(hf=dict(HF, **change))


def test_the_graph_names_each_mechanism_by_its_class():
    """What a device trace files operations under: a delta-rule node behind
    ONE bias-free conv in four layers, a latent node in the fourth layer, a
    dense FFN in layer 1 alone, the routed layer's four classes and the
    shared expert's three projections in the other four."""
    im = deployment()
    names = [type(n.op).__name__ for n in im.model.graph.nodes]
    count = {c: names.count(c) for c in set(names)}
    assert count["KimiDeltaAttention"] == count["CausalConv1d"] == KDA_LAYERS
    assert count["LatentAttention"] == 1
    assert all(count[c] == 4 for c in ("MoERouter", "MoEDispatch",
                                       "MoEExperts", "MoECombine"))
    assert count["SharedExpertLinear"] == 12
    # the four fused q | k | v projections, layer 1's FFN, the untied head
    assert count["Linear"] == 4 + 3 + 1
    assert im.expert_layers == 4
    kinds = [builder.layer_kind(ServeModelConfig.from_hf_config(HF), i)
             for i in range(LAYERS)]
    assert kinds == ["kda", "kda", "kda", "latent", "kda"] == \
        ref.layer_kinds(HF)
    latent, = [n.op for n in im.model.graph.nodes
               if isinstance(n.op, LatentAttention)]
    assert (latent.use_rope, latent.rope_scaling) == (False, None)
    assert latent.scaling_factor == pytest.approx(24 ** -0.5)
    assert LatentAttention(64, 4, 16, 8, 16, 32).use_rope   # deepseek's
    router = next(n.op for n in im.model.graph.nodes
                  if isinstance(n.op, MoERouter))
    assert (router.scoring, router.norm_topk, router.bias, router.top_k,
            router.num_experts) == ("sigmoid", True, True, 8, 32)
    assert router.scaling == pytest.approx(2.446)
    convs = [n.op for n in im.model.graph.nodes
             if isinstance(n.op, CausalConv1d)]
    assert all(not c.bias and c.channels == 3 * HEADS * HD for c in convs)


def test_expert_tiles_at_the_published_shape():
    """``MoEExperts.out_tile`` at 2304 x 1024: two tiles either way; the
    three committed shapes keep the tiles they compiled with."""
    tile = MoEExperts.out_tile
    assert (tile(2304, 1024, 2), tile(1024, 2304, 2)) == (512, 1152)
    assert (tile(2688, 1856, 2), tile(1856, 2688, 2)) == (640, 896)
    assert (tile(4096, 4096, 2), tile(4096, 4096, 2)) == (512, 512)
    assert (tile(2048, 1408, 2), tile(1408, 2048, 2)) == (768, 1024)


def test_weight_only_int8_reaches_the_gate_and_the_output_projection():
    from flexflow_tpu.serve.quant import quantize_int8

    im = seeded(build())
    want, _ = reference_logprobs(PROMPT[:40])
    quantize_int8(im)
    seen = 0
    for name, group in im.params.items():
        if name.endswith("self_attn") and "f_a" in group:
            for p in KimiDeltaAttention.int8_params:
                assert group[p].dtype == jnp.int8, (name, p)
            for p in ("f_a", "f_b", "b_proj", "dt_bias", "A_log"):
                assert group[p].dtype == jnp.float32, (name, p)
            seen += 1
        if name.endswith("self_attn.qkv_proj"):
            assert group["kernel"].dtype == jnp.int8    # a Linear
    assert seen == KDA_LAYERS
    got = feed_flat(im, 0, PROMPT[:40], [CAP], [0] * SLOTS)
    err = np.abs(got - want).max()
    assert 20 * TOL < err < 0.5, err    # quantised, and still the model


def test_the_published_tensor_names_are_listed_for_an_importer():
    from flexflow_tpu.serve.weights import KIMI_LINEAR_TENSORS

    for name, _, _ in ref.LAYER:
        stem = name.replace(".weight", "").replace(
            "block_sparse_moe.experts.", "block_sparse_moe.experts.N.")
        key = stem if stem.endswith(("A_log", "dt_bias",
                                     "e_score_correction_bias")) \
            else stem + ".weight"
        assert key in KIMI_LINEAR_TENSORS, name
    assert KIMI_LINEAR_TENSORS["self_attn.q_proj@latent.weight"][1] == \
        "q_proj"


def test_the_seeded_decay_is_a_vector_neither_1_nor_0():
    """``published_init``: a head's 16 decays a step spread over the range
    the family initialises them to (0.999 .. 0.2), not one value a head."""
    w = sw.draw_table(sw.base_key(SEED), 1, ref.LAYER, HF, "float32")
    init = ref.published_init(HF, w)
    step = jax.nn.softplus(init["self_attn.dt_bias"]).reshape(HEADS, HD)
    alpha = jnp.exp(-jnp.exp(init["self_attn.A_log"])[:, None] * step)
    assert 0.15 < float(alpha.min()) < 0.35 and float(alpha.max()) > 0.998
    spread = alpha.max(axis=1) - alpha.min(axis=1)
    assert float(spread.min()) > 0.05       # a vector in every head
    assert float(jnp.abs(
        init["block_sparse_moe.gate.e_score_correction_bias"]).max()) == 0


def test_spans_counters_and_the_ledger_name_the_new_state():
    """Through ``RequestManager.generate``: the decode scans' dispatch spans
    carry ``ctx_sum`` (what the ONE latent layer reads), the ``commit`` spans
    and the tick journal the routed layers' load, the memory ledger prices
    the delta state beside the tails and the latent planes, and the paths
    both mixers took are counted."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment()
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    try:
        im._paths_counted = 0
        im.take_expert_load()   # earlier tests' scans, which no one read
        outs = rm.generate([tokens(50, salt=31), tokens(9, salt=32)], 24)
        assert [len(o) for o in outs] == [24, 24]
        im.publish_memory(tel)
        measured = tel.memory.report()["plans"][im.plan_key]
        per_slot = im.kv.bytes_per_slot()
        for kind in ("delta_state", "recurrent", "kv_latent"):
            assert measured[f"slot_{kind}_bytes"]["measured"] == \
                per_slot[kind] > 0, kind
        counters = tel.metrics.snapshot()
        assert counters["attention_path.kimi_delta_attention.chunked"] >= 1
        assert counters["attention_path.kimi_delta_attention.xla_rows"] >= 1
        assert counters["attention_path.latent_attention.xla_absorbed"] >= 1
        assert counters["attention_path.causal_conv1d.slot_order"] >= 1
        assert counters["attention_path.causal_conv1d.rows"] >= 1
        scans = [e["args"] for e in tel.trace.trace_events()
                 if e["name"] == "decode_scan_dispatch"]
        assert scans and all(a["ctx_sum"] > 0 and a["rows"] == 2
                             for a in scans)
        commits = [e["args"] for e in tel.trace.trace_events()
                   if e["name"] == "commit" and "expert_steps" in e["args"]]
        steps = sum(c["expert_steps"] for c in commits)
        assert steps and steps % 4 == 0          # four routed layers a step
        assert 0 < sum(c["experts_visited"] for c in commits) <= 4 * steps
        assert 0 < sum(c["expert_pairs"] for c in commits) <= 2 * 8 * steps
        records = rm.journal.records()
        assert sum(r["expert_steps"] for r in records) == steps
    finally:
        im.telemetry = type(im).telemetry
