"""Nemotron-H (Mamba-2 layers, a GQA layer, routed experts with a shared
expert; ONE mixer a block) through the normal serve path, against the plain
reference ``benchmark/reference/nemotron_h.py`` — logits, not tokens.

Toy widths, the real mechanisms: hidden 64; Mamba-2 with 8 heads of 8 in 2
B/C groups, state 16, conv 4; 4 query heads on 2 K/V heads of 16; a router
over 8 experts with top-3 of which this graph HOLDS 4 (share 0 of 2), experts
of width 32, a shared expert of 48; 5 blocks ``MEM*E``.  Weights are the
benchmark's seeded ones in float32 (``seeded_weights.program_params`` also
holds the program's parameter tree to the reference's ``program_tree``, name
by name).

The reference is the recurrence position by position and a loop over the held
experts under a 0 / weight mask; the program keeps a matrix state and a conv
tail per slot, a K/V cache in one layer, and sorts (row, choice) pairs into a
grouped GEMM.  float32 on the CPU against float32 at HIGHEST precision: they
differ by summation order alone and a log-probability agrees to 2e-4 nats —
a dropped state or conv tail, a head on the wrong B/C group, the router's
bias in the weights, a dropped scaling factor or an absent expert's pair
computed move it by 4e-3 or more (``test_a_break_is_seen`` holds that).
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

from benchmark import check, seeded_weights as sw  # noqa: E402
from benchmark.reference import nemotron_h as ref  # noqa: E402
from flexflow_tpu.config import FFConfig  # noqa: E402
from flexflow_tpu.core.op import OpContext  # noqa: E402
from flexflow_tpu.model import FFModel  # noqa: E402
from flexflow_tpu.parallel.mesh import make_mesh  # noqa: E402
from flexflow_tpu.serve import BatchConfig  # noqa: E402
from flexflow_tpu.serve import ssd_moe_ops  # noqa: E402
from flexflow_tpu.serve.hybrid_ops import CausalConv1d  # noqa: E402
from flexflow_tpu.serve.inference_manager import InferenceManager  # noqa: E402
from flexflow_tpu.serve.models import nemotron_h as builder  # noqa: E402
from flexflow_tpu.serve.models.base import (  # noqa: E402
    ServeModelConfig,
    build_model,
)
from flexflow_tpu.serve.ssd_moe_ops import (  # noqa: E402
    Mamba2Scan,
    MoECombine,
    MoEDispatch,
    MoEExperts,
    MoERouter,
)

HF = dict(model_type="nemotron_h", vocab_size=320, hidden_size=64,
          num_hidden_layers=5, hybrid_override_pattern="MEM*E",
          num_attention_heads=4, num_key_value_heads=2, head_dim=16,
          mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
          conv_kernel=4, n_routed_experts=4, router_num_experts=8,
          expert_share_index=0, num_experts_per_tok=3, n_shared_experts=1,
          moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
          routed_scaling_factor=2.5, norm_topk_prob=True,
          layer_norm_epsilon=1e-5, intermediate_size=32,
          time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
          # std * sqrt(width) ~ 1, as 0.02 nearly is at the published 2688
          init_std=0.125, torch_dtype="float32")
SLOTS, CAP, SEQ = 3, 16, 256
TOL = 2e-4          # nats, see the module docstring
SEED = 4321
E_LAYERS = HF["hybrid_override_pattern"].count("E")


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
    starts its sequences at position 0 of a slot, which is all a slot needs
    to start clean)."""
    return seeded(build(use_pallas=use_pallas))


@functools.lru_cache(maxsize=None)
def _ref_layer(padded_len):
    return jax.jit(lambda key, i, x: ref.layer(
        HF, sw.draw_table(key, i, ref.LAYER, HF, "float32"), x))


def reference_forward(ids):
    """The reference's full forward pass of ``ids``: sorted
    log-probabilities at every position, its greedy tokens, and the input
    of every layer (``[layers + 1, T, d]``)."""
    key = sw.base_key(SEED)
    g = sw.draw_table(key, sw.GLOBAL_ID, ref.GLOBAL, HF, "float32")
    padded = np.zeros(-(-len(ids) // 64) * 64, np.int32)
    padded[:len(ids)] = ids
    x = ref.embed(HF, g, jnp.asarray(padded[None]))
    hidden = [x.h[0, :len(ids)]]
    for i in range(ref.num_layers(HF)):
        x = _ref_layer(len(padded))(key, jnp.int32(i), x)
        hidden.append(x.h[0, :len(ids)])
    logits = ref.head(HF, g, x[:, :len(ids)])[0]
    lp = jax.nn.log_softmax(logits, axis=-1)
    return (np.asarray(jnp.sort(lp, axis=-1)[:, ::-1]),
            np.asarray(jnp.argmax(logits, axis=-1)), hidden)


def reference_logprobs(ids):
    lp, tok, _ = reference_forward(ids)
    return lp, tok


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


PROMPT = tokens(90)


@pytest.mark.parametrize("how", ["uneven_chunks", "tiled_scan",
                                 "tiled_scan_pallas", "uneven_chunks_pallas"])
def test_prompt_feeding_paths_agree_with_the_reference(how):
    """The same prompt in uneven flat chunks (segments of one request that
    begin anywhere: the state and the conv tail are handed from chunk to
    chunk) and through the tiled prefill scan, kernels off (``ragged_dot``)
    and on (Megablox's grouped GEMM, the attention kernels): a decode step
    then reads what each left."""
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
    experts = {b: p for (k, b), p in im.attention_paths.items()
               if k == "moe_experts"}
    assert set(experts.values()) == {
        "megablox_gmm" if how.endswith("pallas") else "ragged_dot"}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_decode_scan_carries_what_prefill_leaves(use_pallas):
    """A 30-token prompt, then 50 decode steps on the device in chained
    scans.  Flat steps then read, at position 80 on, logits that depend on
    the state the scan accumulated; and the K/V, the matrix states and the
    conv tails the scan left are those the same 80 tokens leave when
    PREFILLED into another slot."""
    im = deployment(use_pallas=use_pallas)
    prompt = tokens(30, salt=5)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 29)], seq_lens)
    first = int(toks[0])
    made = decode_scan(im, 0, first, 30, 50)
    full = prompt + [first] + made                  # 81 tokens
    # teacher forcing: the reference is fed what the program produced
    want, want_tok = reference_logprobs(full + tokens(2, salt=6))
    assert full[30:] == want_tok[29:80].tolist()
    feed_flat(im, 2, full[:80], [CAP], seq_lens)
    seen = set()
    for node, bufs in im.state.items():
        for name, live in (("k", 80), ("v", 80), ("ssd", None),
                           ("conv", None)):
            if name not in bufs:
                continue
            a, b = bufs[name][0], bufs[name][2]
            if live:
                a, b = a[:, :live], b[:, :live]
            assert float(jnp.abs(a).max()) > 1e-2, (node, name)
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
            seen.add(name)
    assert seen == {"k", "v", "ssd", "conv"}
    seq_lens[0] = 80
    for k, tok in enumerate([full[80]] + tokens(2, salt=6)):
        (got,), _ = flat_step(im, [(0, [tok], 80 + k)], seq_lens)
        np.testing.assert_allclose(got[0], want[80 + k], atol=TOL, rtol=0)
    assert im.attention_paths[("mamba2_scan", "one_row_per_request")] == \
        "slot_order"
    assert im.attention_paths[("mamba2_scan", "BatchConfig")] == "chunked"


# readings here: 0.0004 ulps at most, 0.0000 nats (four decimals)
LIMITS = {"logit_rms_ulps": 0.01, "logit_max_ulps": 0.05,
          "logprob_rms": 5e-5, "logprob_max": 5e-4, "tail_logprob_rms": 5e-5,
          "token_gap_ulps": 0.05}


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_harness_drive_is_correct(use_pallas):
    """``benchmark/check.py``'s drive: the tiled prefill scan, a flat
    prompt, a joiner spliced by ``join_slot`` between two chained decode
    scans, flat steps on all three rows."""
    im = deployment(use_pallas=use_pallas)
    lines = []
    ok, _ = check.run_check(im, ref, HF, sw.base_key(SEED), "float32", 77,
                            HF["vocab_size"], LIMITS, lines.append)
    assert ok, "\n".join(lines)
    paths = im.attention_paths
    assert paths.pop(("kv_block_write", "PrefillBatchConfig"), None) == (
        "pallas" if use_pallas else None)
    # the one GQA layer's decode kernel says which block it planned, in
    # the flat step's program and in the decode scan's
    blocks = {b: paths.pop(("decode_block", b))
              for b in [b for k, b in paths if k == "decode_block"]}
    assert set(blocks) == ({("inc_multihead_self_attention", "BatchConfig"),
                            ("inc_multihead_self_attention",
                             "one_row_per_request")} if use_pallas else set())
    assert all(p.startswith("full") for p in blocks.values())
    # the decode scans' K/V rows: ONE aliased call a layer where the kernels
    # are on, the chain of update-slices where they are off
    assert paths.pop(
        ("kv_row_write", "one_row_per_request")) == (
        "pallas" if use_pallas else "dus_chain")
    # the GQA layer's prompt tiles, on the toy's float32 cache
    assert paths.pop(
        ("prefill_operands", "inc_multihead_self_attention"), None) == (
        "float32" if use_pallas else None)
    assert {k for k, _ in paths} == {"mamba2_scan", "moe_experts",
                                     "causal_conv1d"}
    # the conv's two forms: the decode scans step the tails in slot order,
    # the prompt's chunks and the flat steps go by rows
    assert {b: p for (k, b), p in paths.items() if k == "causal_conv1d"} == {
        "one_row_per_request": "slot_order", "PrefillBatchConfig": "rows",
        "BatchConfig": "rows"}
    assert paths[("mamba2_scan", "PrefillBatchConfig")] == "chunked"


def test_flat_rows_of_several_requests_go_by_segments():
    """One flat step holds the ends of two prompts and a decode row of a
    third request: each row reads ITS slot's state and conv tail, and the
    routed layer sorts all their pairs together."""
    im = deployment()
    a, b, c = tokens(20, salt=11), tokens(12, salt=12), tokens(9, salt=13)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, a[:14], [CAP], seq_lens)
    feed_flat(im, 1, b[:7], [CAP], seq_lens)
    feed_flat(im, 2, c[:8], [CAP], seq_lens)
    got, _ = flat_step(im, [(1, b[7:], 7), (2, c[8:], 8), (0, a[14:], 14)],
                       seq_lens)
    for lp, ids, at in zip(got, (b, c, a), (7, 8, 14)):
        want, _ = reference_logprobs(ids)
        np.testing.assert_allclose(lp, want[at:], atol=TOL, rtol=0)


def test_a_reused_slot_starts_from_zero_state():
    """A slot that served a long request (a grown state, a full conv tail, a
    cache) then serves a short one, fed in chunks and decoded in the scan:
    it reads what it would alone."""
    im = deployment()
    seq_lens = [0] * SLOTS
    feed_flat(im, 2, tokens(70, salt=21), [CAP], seq_lens)
    short = tokens(25, salt=22)
    seq_lens[2] = 0
    feed_flat(im, 2, short[:-1], [10], seq_lens)
    _, toks = flat_step(im, [(2, short[-1:], 24)], seq_lens)
    made = decode_scan(im, 2, int(toks[0]), 25, 8)
    full = short + [int(toks[0])] + made
    want, want_tok = reference_logprobs(full)
    assert full[25:] == want_tok[24:33].tolist()
    # ... and from position 0 INSIDE the decode scan: a one-token prompt
    fresh = decode_scan(im, 1, short[0], 0, 6)
    _, tok = reference_logprobs([short[0]] + fresh)
    assert fresh == tok[:6].tolist()


# ---- scratch breaks: each must move the logits far past the tolerance -----
def _zero_state(self, la, dx, b, c, ssd, seg):
    y, ssd = Mamba2Scan._slot_order_sound(self, la, dx, b, c,
                                          jnp.zeros_like(ssd), seg)
    return y, ssd


def _break(broken, monkeypatch):
    if broken == "state_dropped_in_the_decode_scan":
        monkeypatch.setattr(Mamba2Scan, "_slot_order_sound",
                            Mamba2Scan._slot_order, raising=False)
        monkeypatch.setattr(Mamba2Scan, "_slot_order", _zero_state)
    elif broken == "state_dropped_between_chunks":
        sound = Mamba2Scan._chunked
        monkeypatch.setattr(
            Mamba2Scan, "_chunked",
            lambda self, la, dx, b, c, ssd, seg: sound(
                self, la, dx, b, c, jnp.zeros_like(ssd), seg))
    elif broken == "conv_tail_dropped":
        lower = CausalConv1d.lower

        def no_tail(self, ctx, inputs, params):
            state = ctx.extras["state"]
            ctx.extras["state"] = {"conv": jnp.zeros_like(state["conv"])}
            return lower(self, ctx, inputs, params)

        monkeypatch.setattr(CausalConv1d, "lower", no_tail)
    elif broken == "a_head_on_the_wrong_group":
        monkeypatch.setattr(
            Mamba2Scan, "_heads",
            lambda self, a: jnp.roll(jnp.repeat(
                a, self.num_heads // self.n_groups, axis=-2), 1, axis=-2))
    elif broken == "bias_used_in_the_weights":
        def lower(self, ctx, inputs, params):
            x = inputs[0].astype(jnp.float32)
            s = jax.nn.sigmoid(jnp.dot(x, params["weight"],
                                       precision=ssd_moe_ops.HI)) \
                + 0.3 * jnp.arange(self.num_experts) / self.num_experts
            w, ids = jax.lax.top_k(s, self.top_k)
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
            return [ids.astype(jnp.int32), w * self.scaling]

        monkeypatch.setattr(MoERouter, "lower", lower)
    elif broken == "scaling_factor_dropped":
        init = MoERouter.__init__

        def unscaled(self, *a, **kw):
            init(self, *a, **kw)
            self.scaling = 1.0

        monkeypatch.setattr(MoERouter, "__init__", unscaled)
    elif broken == "an_absent_experts_pair_computed":
        # pairs on experts 4..7 land on held experts 0..3
        monkeypatch.setattr(
            ssd_moe_ops, "_held",
            lambda ctx, ids, lo, count: (ids >= 0, ids % count))
    else:
        raise ValueError(broken)


BREAKS = ["state_dropped_in_the_decode_scan", "state_dropped_between_chunks",
          "conv_tail_dropped", "a_head_on_the_wrong_group",
          "bias_used_in_the_weights", "scaling_factor_dropped",
          "an_absent_experts_pair_computed"]


@pytest.mark.parametrize("broken", BREAKS)
def test_a_break_is_seen(broken, monkeypatch):
    """Each way of getting the new mechanisms wrong moves the logits by far
    more than the tolerance the other tests hold: what they pass, a broken
    program would not."""
    _break(broken, monkeypatch)
    im = seeded(build())
    prompt = tokens(40, salt=50)
    seq_lens = [0] * SLOTS
    feed_flat(im, 0, prompt[:-1], [CAP], seq_lens)
    _, toks = flat_step(im, [(0, prompt[-1:], 39)], seq_lens)
    made = decode_scan(im, 0, int(toks[0]), 40, 24)
    full = prompt + [int(toks[0])] + made
    want, _ = reference_logprobs(full)
    seq_lens[0] = 64
    (got,), _ = flat_step(im, [(0, [full[64]], 64)], seq_lens)
    assert np.abs(got[0] - want[64]).max() > 20 * TOL, broken


# ---- the routed layer alone ------------------------------------------------
def _ctx(extras=None, name="n"):
    return OpContext(extras={"node_name": name, **(extras or {})})


def routed_layer(x, gate, bias, up, down, held_lo, top_k=3, scaling=2.5,
                 extras=None, counters=None):
    """The four ops in the graph's order, called directly."""
    d, scored = gate.shape
    held = up.shape[0]
    ex = dict(extras or {}, counters=counters)
    ids, w = MoERouter(d, scored, top_k, scaling).lower(
        _ctx(ex), [x], {"weight": gate, "e_score_correction_bias": bias})
    xs, sizes, order = MoEDispatch(held, held_lo).lower(_ctx(ex), [x, ids],
                                                        {})
    ys = MoEExperts(held, d, up.shape[2]).lower(
        _ctx(ex), [xs, sizes], {"up": up, "down": down})[0]
    out = MoECombine(held, held_lo).lower(_ctx(ex), [ys, order, ids, w],
                                          {})[0]
    return np.asarray(out), np.asarray(ids), np.asarray(w), np.asarray(sizes)


def per_row_loop(x, gate, bias, up, down, held_lo, top_k=3, scaling=2.5):
    """The routed layer as a loop over rows and their choices, in numpy
    float64: ties to the lower id, the bias chooses and is not weighed."""
    x, gate, up, down = (np.asarray(a, np.float64) for a in (x, gate, up,
                                                             down))
    out = np.zeros_like(x)
    for t, row in enumerate(x):
        s = 1.0 / (1.0 + np.exp(-(row @ gate)))
        chosen = np.argsort(-(s + np.asarray(bias, np.float64)),
                            kind="stable")[:top_k]
        w = s[chosen] / (s[chosen].sum() + 1e-20) * scaling
        for e, we in zip(chosen, w):
            if held_lo <= e < held_lo + up.shape[0]:
                h = np.maximum(row @ up[e - held_lo], 0.0) ** 2
                out[t] += we * (h @ down[e - held_lo])
    return out


def _layer_weights(salt, d=32, scored=8, held=4, f=24):
    rng = np.random.default_rng([SEED, salt])
    n = lambda *s: rng.normal(size=s).astype(np.float32)
    return (n(d, scored) / np.sqrt(d), np.zeros(scored, np.float32),
            n(held, d, f) / np.sqrt(d), n(held, f, d) / np.sqrt(f))


@pytest.mark.parametrize("case", ["random", "one_row", "ties",
                                  "an_expert_gets_no_row",
                                  "all_rows_on_one_expert", "upper_share",
                                  "rows_of_no_request", "chunk_of_512"])
def test_routed_layer_equals_a_per_row_loop(case):
    gate, bias, up, down = _layer_weights(3)
    rng = np.random.default_rng([SEED, 99])
    rows = {"one_row": 1, "chunk_of_512": 512}.get(case, 40)
    x = rng.normal(size=(rows, 32)).astype(np.float32)
    held_lo, extras = 0, None
    if case == "ties":
        # experts 1, 2, 5 and 6 score alike on every row: the lower ids win
        gate[:, [2, 5, 6]] = gate[:, [1]]
    elif case == "an_expert_gets_no_row":
        bias[2] = -10.0
    elif case == "all_rows_on_one_expert":
        bias[1] = 10.0
        bias[[4, 5]] = 5.0          # the other two choices are absent
    elif case == "upper_share":
        held_lo = 4
    elif case == "rows_of_no_request":
        live = np.arange(rows) % 3 != 1
        extras = {"batch_config": BatchConfig.build(
            [5] * rows, np.where(live, 0, -1).tolist(), list(range(rows)),
            [rows], max_tokens=rows, max_requests=1)}
    counters = {}
    got, ids, w, sizes = routed_layer(x, gate, bias, up, down, held_lo,
                                      extras=extras, counters=counters)
    want = per_row_loop(x, gate, bias, up, down, held_lo)
    if case == "rows_of_no_request":
        want[~live] = 0.0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    # the load the dispatch counted, against a count from the choices
    chosen = ids if extras is None else ids[live]
    at = chosen[(chosen >= held_lo) & (chosen < held_lo + 4)] - held_lo
    count = np.bincount(at, minlength=4)
    assert sizes.tolist() == count.tolist()
    assert np.asarray(counters["n"]).tolist() == [
        int((count > 0).sum()), int(count.sum()), int(count.max())]
    if case == "ties":
        assert not ({5, 6} & set(ids.ravel().tolist())) or \
            all(1 in r and 2 in r for r in ids.tolist() if 5 in r or 6 in r)
    elif case == "an_expert_gets_no_row":
        assert count[2] == 0 and got.any()
    elif case == "all_rows_on_one_expert":
        assert count.tolist() == [0, rows, 0, 0]


def test_the_grouped_gemm_kernel_equals_ragged_dot():
    """Megablox's kernel (interpreted) on the sorted rows of a toy layer
    whose widths its tiles divide: the same rows as ``lax.ragged_dot``'s,
    where a group has them (the rest is never read)."""
    gate, bias, up, down = _layer_weights(4, d=128, f=128)
    rng = np.random.default_rng([SEED, 98])
    x = rng.normal(size=(24, 128)).astype(np.float32)
    plain, *_ = routed_layer(x, gate, bias, up, down, 0)
    kernel, *_ = routed_layer(x, gate, bias, up, down, 0, extras={
        "pallas_decode": True, "pallas_interpret": True})
    np.testing.assert_allclose(kernel, plain, atol=2e-5, rtol=1e-5)


def test_two_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """Shares 0 and 1 of 2 of one expert layer, the shared expert counted
    once, against the reference's UNCUT layer (8 held of 8 scored)."""
    uncut = {**HF, "n_routed_experts": 8, "router_num_experts": 8}
    key = sw.base_key(SEED)
    w = sw.draw_table(key, 1, ref.LAYER, uncut, "float32")
    rng = np.random.default_rng([SEED, 97])
    n = jnp.asarray(rng.normal(size=(1, 24, 64)).astype(np.float32))
    want = np.asarray(ref.experts(uncut, w, n))[0]
    # the program's tensors: the draw as ``program_tree`` maps it (the
    # experts' down projections centred; the reference centres its own)
    init = ref.published_init(uncut, w)
    shared = np.asarray(ref.relu2_mlp(
        n, w["mixer.shared_experts.up_proj"],
        init["mixer.shared_experts.down_proj"]))[0]
    gate = w["mixer.gate.weight"]
    total = shared
    for share in (0, 1):
        lo = 4 * share
        part, *_ = routed_layer(
            n[0], gate, jnp.zeros((8,)), w["mixer.experts.up_proj"][lo:lo + 4],
            init["mixer.experts.down_proj"][lo:lo + 4], lo)
        total = total + part
        # the reference's own share: the same cut, through ``hf``
        cut = {**HF, "expert_share_index": share}
        w_cut = dict(w, **{
            "mixer.experts.up_proj": w["mixer.experts.up_proj"][lo:lo + 4],
            "mixer.experts.down_proj":
                w["mixer.experts.down_proj"][lo:lo + 4]})
        ids, wts = ref.route(cut, w_cut, n)
        np.testing.assert_allclose(
            part, np.asarray(ref.routed_experts(cut, w_cut, n, ids, wts))[0],
            atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=1e-5)


def test_the_draw_takes_the_common_term_out_of_the_experts_down_projections():
    """``published_init`` centres every expert's down projection: its rows
    then sum to zero, so the one positive mean that ``relu^2`` gives every
    hidden unit adds nothing that all rows share.  Counted on the layer's
    own output for rows of independent directions: the power of the mean
    row over the mean power of a row falls several-fold; the program's tree
    holds the centred tensors, the uncentred ones are what was drawn."""
    wide = {**HF, "moe_shared_expert_intermediate_size": 256, "init_std": 0.125}
    w = sw.draw_table(sw.base_key(SEED), 1, ref.LAYER, wide, "float32")
    init = ref.published_init(wide, w)
    for name in ("mixer.experts.down_proj", "mixer.shared_experts.down_proj"):
        assert float(jnp.max(jnp.abs(jnp.sum(init[name], axis=-2)))) < 1e-5
        assert float(jnp.max(jnp.abs(jnp.sum(w[name], axis=-2)))) > 0.1
    rng = np.random.default_rng([SEED, 98])
    n = rng.normal(size=(1, 512, 64)).astype(np.float32)
    n = jnp.asarray(n / np.sqrt((n * n).mean(-1, keepdims=True)))

    def common_share(down):
        out = np.asarray(ref.relu2_mlp(
            n, w["mixer.shared_experts.up_proj"], down))[0]
        return float((out.mean(0) ** 2).sum() / (out ** 2).sum(-1).mean())

    as_drawn = common_share(w["mixer.shared_experts.down_proj"])
    centred = common_share(init["mixer.shared_experts.down_proj"])
    assert as_drawn > 0.1 and centred < as_drawn / 4
    g = sw.draw_table(sw.base_key(SEED), sw.GLOBAL_ID, ref.GLOBAL, HF,
                      "float32")
    layers = [sw.draw_table(sw.base_key(SEED), i, ref.LAYER, HF, "float32")
              for i in range(ref.num_layers(HF))]
    tree = ref.program_tree(HF, g, layers)
    np.testing.assert_array_equal(
        tree["backbone.layers.1.mixer.experts"]["down"],
        ref.centered(layers[1]["mixer.experts.down_proj"]))


# ---- the SSD scan alone ----------------------------------------------------
def test_chunked_form_and_slot_order_equal_the_recurrence():
    """``Mamba2Scan`` on a flat batch of three segments (one continuing a
    slot's stored state, one fresh, one of a single row) and pads, and on a
    one-row-per-request batch: outputs and the states left against the
    recurrence row by row in numpy."""
    h, p, g, n = 4, 8, 2, 16
    op = Mamba2Scan(h, p, g, n)
    rng = np.random.default_rng([SEED, 96])
    params = {"A_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
              "D": jnp.ones((h,)), "dt_bias": jnp.asarray(
                  rng.normal(size=h).astype(np.float32) - 2.0)}
    stored = rng.normal(size=(4, h, p, n)).astype(np.float32)

    def recurrence(xbc, dt, slots, pos):
        state, ys = stored.copy(), []
        for r in range(len(slots)):
            if slots[r] < 0:
                ys.append(np.zeros((h, p)))
                continue
            s = np.zeros((h, p, n)) if pos[r] == 0 else state[slots[r]]
            x = xbc[r, :h * p].reshape(h, p)
            b = np.repeat(xbc[r, h * p:h * p + g * n].reshape(g, n), h // g,
                          0)
            c = np.repeat(xbc[r, h * p + g * n:].reshape(g, n), h // g, 0)
            delta = np.log1p(np.exp(dt[r] + np.asarray(params["dt_bias"])))
            a = -np.exp(np.asarray(params["A_log"]))
            s = np.exp(delta * a)[:, None, None] * s \
                + (delta[:, None] * x)[:, :, None] * b[:, None, :]
            state[slots[r]] = s
            ys.append((s * c[:, None, :]).sum(-1) + x)
        return np.stack(ys).reshape(len(slots), h * p), state

    def run(slots, pos, one_row):
        t = len(slots)
        xbc = rng.normal(size=(t, h * p + 2 * g * n)).astype(np.float32)
        dt = rng.normal(size=(t, h)).astype(np.float32)
        bc = BatchConfig.build([5] * t, slots, pos, [64] * 3, max_tokens=t,
                               max_requests=3)
        ctx = _ctx({"batch_config": bc, "state": {"ssd": jnp.asarray(stored)},
                    "one_row_per_request": one_row})
        y = op.lower(ctx, [jnp.asarray(xbc), jnp.asarray(dt)], params)[0]
        want_y, want_state = recurrence(xbc, dt, slots, pos)
        live = np.asarray(slots) >= 0
        np.testing.assert_allclose(np.asarray(y)[live], want_y[live],
                                   atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(
            np.asarray(ctx.extras["state_out"]["ssd"])[:3], want_state[:3],
            atol=2e-5, rtol=1e-4)

    # slot 1 continues at position 7, slot 0 starts fresh, slot 2 one row
    run([1] * 5 + [0] * 6 + [2] + [-1] * 4,
        list(range(7, 12)) + list(range(6)) + [30] + [0] * 4, False)
    run([2, -1, 0], [9, 0, 0], True)


# ---- the conv alone --------------------------------------------------------
def _conv_batch(layout, rng):
    """``(slots, request_index, positions)`` of a one-row-per-request
    batch; ``-1`` rows are pads."""
    if layout == "pads_before_between_and_after":
        slots, rows = 40, np.full(32, -1)
        live = np.r_[3:11, 14:21, 24:27]
        rows[live] = rng.permutation(slots)[:live.size]
    elif layout == "rows_out_of_slot_order":
        slots, rows = 32, np.arange(32)[::-1].copy()
        rows[[5, 6]] = rows[[6, 5]]
    elif layout == "one_live_row":
        slots, rows = 32, np.full(32, -1)
        rows[17] = 9
    elif layout == "256_rows":       # over DUS_MAX_TOKENS: the row form
        slots = 256                  # writes back by one scatter
        rows = rng.permutation(slots)
        rows[[0, 100, 255]] = -1
    else:
        raise ValueError(layout)
    # positions 0, 1, 2 on live rows: taps before the request began
    pos = rng.integers(0, 7, size=rows.size)
    return slots, rows.astype(np.int32), pos.astype(np.int32)


@pytest.mark.parametrize("operands", ["bfloat16", "float32"])
@pytest.mark.parametrize("kernel,bias", [(4, True), (4, False), (3, True),
                                         (2, False)],
                         ids=["k4_bias", "k4", "k3_bias", "k2"])
@pytest.mark.parametrize("layout", [
    "pads_before_between_and_after", "rows_out_of_slot_order",
    "one_live_row", "256_rows"])
def test_the_convs_slot_order_form_equals_its_row_form(layout, kernel, bias,
                                                       operands):
    """``CausalConv1d``'s two forms on one decode-scan batch (every live row
    a request of its own; 32 rows — the row form's chain of update-slices —
    and 256 — its scatter): every slot's stored tail holds a PREVIOUS
    request's values, so a row at position 0, 1 or 2 shows the zeros.  The
    tails left (pure copies) are equal TO THE BIT on every slot, touched or
    not; ``y`` before the cast to the op's type (the op is built in float32
    over operands of the given type) is equal to the bit over bf16 operands,
    the deployments', and to float32 rounding over float32 ones: the taps
    are summed in ONE order, but this backend contracts a product and a sum
    into one rounding in some fusions and not in others.  The scratch row
    is any pad's to write and is not compared."""
    rng = np.random.default_rng([SEED, 97, kernel, bias])
    slots, rows, pos = _conv_batch(layout, rng)
    c, t = 48, rows.size
    op = CausalConv1d(c, kernel, dtype=jnp.float32, bias=bias)
    draw = lambda *shape: jnp.asarray(
        rng.normal(size=shape).astype(np.float32), operands)
    x, tails = draw(t, c), draw(slots + 1, kernel - 1, c)
    params = {"weight": draw(kernel, c)}
    if bias:
        params["bias"] = draw(c)
    bc = BatchConfig(tokens=jnp.zeros((t,), jnp.int32),
                     request_index=jnp.asarray(rows),
                     token_position=jnp.asarray(pos),
                     num_tokens=jnp.int32((rows >= 0).sum()),
                     seq_lens=jnp.zeros((slots,), jnp.int32))

    @functools.partial(jax.jit, static_argnums=0)
    def run(one_row, x, tails, params):
        paths = {}
        ctx = _ctx({"batch_config": bc, "state": {"conv": tails},
                    "one_row_per_request": one_row,
                    "attention_paths": paths})
        y = op.lower(ctx, [x], params)[0]
        assert paths == {("causal_conv1d", "one_row_per_request" if one_row
                          else "BatchConfig"):
                         "slot_order" if one_row else "rows"}
        return y, ctx.extras["state_out"]["conv"]

    (y_rows, left_rows), (y_slots, left_slots) = (
        jax.tree.map(np.asarray, run(one, x, tails, params))
        for one in (False, True))
    live = rows >= 0
    assert left_slots.dtype == left_rows.dtype == tails.dtype
    np.testing.assert_array_equal(left_slots[:slots].view(np.uint8),
                                  left_rows[:slots].view(np.uint8))
    # ... and they are what the definition says, not only each other
    x32, tails32, w, b = (np.asarray(a, np.float32) for a in (
        x, tails, params["weight"], params.get("bias", 0.0)))
    want = tails32.copy()
    back = np.arange(kernel - 2, -1, -1)            # of tail entry j
    for r in np.flatnonzero(live):
        new = np.concatenate([tails32[rows[r], 1:], x32[r][None]])
        new[pos[r] < back] = 0.0
        want[rows[r]] = new
    np.testing.assert_array_equal(
        left_slots[:slots].astype(np.float32), want[:slots])
    # each form rounds K + 1 times, by half a unit of a partial sum at most
    size = np.abs(x32 * w[-1]) + np.abs(b) + np.abs(
        tails32[np.where(live, rows, 0)] * w[:-1]).sum(axis=1)
    assert np.abs(y_rows).max() > 0.1
    gap = np.abs(y_slots[live] - y_rows[live])
    assert (gap <= (kernel + 2) * 2.0 ** -23 * size[live]).all(), \
        (gap / size[live]).max() * 2.0 ** 23
    if operands == "bfloat16":
        # the deployments' type: a bf16 x bf16 product is exact in float32,
        # so contracted or not it rounds once — equal to the bit
        assert not gap.any()


def _conv_feed(scenario, t):
    """``[[(slot, rows) ...] ...]``: the batches of ``t`` rows a scenario
    feeds, in order; slot ``-1`` = pad rows.  ``big`` = a length that grows
    with the batch (either side of ``DUS_MAX_TOKENS``)."""
    big = t // 4
    fill = lambda batch: batch + [(-1, t - sum(n for _, n in batch))]
    if scenario == "one_segment_fills_the_batch":
        # ... and the next batch continues it from the tail it left
        return [[(2, t)], [(2, t)], [(2, t - 5), (-1, 5)]]
    if scenario == "three_segments_and_pads":
        return [fill([(-1, 2), (1, big), (-1, 3), (0, big + 1), (-1, 1),
                      (3, 4)]),
                fill([(-1, 1), (3, big - 1), (-1, 2), (1, 5), (-1, 4),
                      (0, big)]),
                fill([(0, 3), (-1, 1), (1, big), (3, 2)])]
    if scenario == "segments_shorter_than_the_tail":
        # 1 and 2 rows on stored tails (the new tail splices old entries and
        # new rows), again and again; slots 3, 4 open with 2 rows and with 1
        # (the zeros before position 0 show, and stay in the tail)
        return [fill([(0, big), (1, 6), (2, 1)]),
                fill([(0, 1), (1, 2), (-1, 1), (2, 1), (3, 2), (4, 1)]),
                fill([(4, 1), (3, 1), (2, 2), (-1, 2), (1, 1), (0, 2)]),
                fill([(0, 1), (1, 1), (2, 1), (3, 1), (4, 2)]),
                fill([(3, big), (4, 3), (0, 2)])]
    if scenario == "rows_out_of_slot_order":
        return [fill([(5, 3), (2, big), (4, 1), (0, 6)]),
                fill([(4, big), (0, 1), (5, 2), (-1, 3), (2, 2)]),
                fill([(2, 1), (5, big), (0, 3), (4, 4)])]
    if scenario == "a_flat_step_of_one_row_segments":
        # a prompt's end among decode rows, every later step one row each
        slots = [6, 1, 4, 0, 5, 3]
        return ([fill([(s, 1 + i) for i, s in enumerate(slots)]
                      + [(2, big)])]
                + [fill([(-1, 1)] + [(s, 1) for s in order])
                   for order in (slots, slots[::-1], slots[2:] + slots[:2])])
    raise ValueError(scenario)


@pytest.mark.parametrize("kernel,bias", [(4, True), (4, False), (3, True),
                                         (2, False)],
                         ids=["k4_bias", "k4", "k3_bias", "k2"])
@pytest.mark.parametrize("t", [32, 1024], ids=["rows32", "rows1024"])
@pytest.mark.parametrize("scenario", [
    "one_segment_fills_the_batch", "three_segments_and_pads",
    "segments_shorter_than_the_tail", "rows_out_of_slot_order",
    "a_flat_step_of_one_row_segments"])
def test_the_convs_row_form_equals_a_conv_over_each_whole_request(
        scenario, t, kernel, bias):
    """``CausalConv1d``'s row form (by SEGMENTS) against a NumPy conv run
    request by request over each request's WHOLE input with zeros before
    position 0 — fed in batches of 32 and of 1024 rows (either side of
    ``DUS_MAX_TOKENS``), each request continuing where its last batch
    stopped, on the tail that batch left.  Every slot starts on another
    request's values, so what comes before position 0 has to be masked, not
    read.  bf16 operands (the deployments'; their products are exact in
    float32 and the taps are summed newest first in both): ``y`` of every
    live row and the tails of every slot a request ran on are equal TO THE
    BIT; the slots no request ran on and the scratch row (no pad writes it:
    it is nobody's to read) keep their first values to the bit, after every
    batch."""
    rng = np.random.default_rng([SEED, 67, kernel, bias, t])
    feed = _conv_feed(scenario, t)
    slots, c = 7, 48
    op = CausalConv1d(c, kernel, dtype=jnp.float32, bias=bias)
    draw = lambda *shape: jnp.asarray(
        rng.normal(size=shape).astype(np.float32), jnp.bfloat16)
    first_tails = draw(slots + 1, kernel - 1, c)
    params = {"weight": draw(kernel, c)}
    if bias:
        params["bias"] = draw(c)
    w = np.asarray(params["weight"], np.float32)
    b = np.asarray(params.get("bias", jnp.zeros((c,))), np.float32)

    @jax.jit
    def run(x, tails, rows, pos):
        bc = BatchConfig(tokens=jnp.zeros((t,), jnp.int32),
                         request_index=rows, token_position=pos,
                         num_tokens=jnp.sum(rows >= 0).astype(jnp.int32),
                         seq_lens=jnp.zeros((slots,), jnp.int32))
        paths = {}
        ctx = _ctx({"batch_config": bc, "state": {"conv": tails},
                    "attention_paths": paths})
        y = op.lower(ctx, [x], params)[0]
        assert paths == {("causal_conv1d", "BatchConfig"): "rows"}
        return y, ctx.extras["state_out"]["conv"]

    whole = {}                       # slot -> every input fed to it so far
    tails = first_tails
    for batch in feed:
        assert sum(n for _, n in batch) == t and min(
            n for _, n in batch) >= 0, batch
        rows = np.concatenate([np.full(n, s) for s, n in batch])
        # where each piece starts: its row of the batch, its position
        starts = [(sum(n for _, n in batch[:i]), len(whole.get(s, ())))
                  for i, (s, _) in enumerate(batch)]
        pos = np.concatenate([(done + np.arange(n)) * (s >= 0) for (s, n),
                              (_, done) in zip(batch, starts)])
        x = draw(t, c)
        y, tails = run(x, tails, jnp.asarray(rows, jnp.int32),
                       jnp.asarray(pos, jnp.int32))
        y, x32 = np.asarray(y), np.asarray(x, np.float32)
        assert tails.dtype == first_tails.dtype
        for (s, n), (at, done) in zip(batch, starts):
            if s < 0 or not n:
                continue
            whole[s] = np.concatenate(
                [whole.get(s, np.zeros((0, c), np.float32)), x32[at:at + n]])
            # the definition, over the request's whole input so far
            padded = np.concatenate(
                [np.zeros((kernel - 1, c), np.float32), whole[s]])
            acc = np.broadcast_to(b, (n, c)).astype(np.float32)
            for back in range(kernel):
                lo = kernel - 1 + done - back
                acc = acc + padded[lo:lo + n] * w[kernel - 1 - back]
            want = np.asarray(jax.nn.silu(jnp.asarray(acc)))
            np.testing.assert_array_equal(
                y[at:at + n].view(np.uint32), want.view(np.uint32),
                err_msg=f"slot {s} from position {done}")
        left = np.asarray(tails.astype(jnp.float32))
        for s in range(slots + 1):
            if s in whole:
                want = np.concatenate(
                    [np.zeros((kernel - 1, c), np.float32),
                     whole[s]])[-(kernel - 1):]
                np.testing.assert_array_equal(left[s], want,
                                              err_msg=f"tail of slot {s}")
                assert not np.signbit(left[s][want == 0]).any()
            else:
                np.testing.assert_array_equal(
                    np.asarray(tails[s]).view(np.uint16),
                    np.asarray(first_tails[s]).view(np.uint16),
                    err_msg=f"slot {s} was nobody's to write")
    assert len(whole) >= 1 and slots not in whole


# ---- what the manager, the allocator and the planner make of it -------------
def test_bytes_per_slot_and_the_plan_against_the_hand_formula():
    """Two M layers: 8 heads x 8 x 16 float32 of state and a conv tail of
    3 x (64 + 2 x 2 x 16) each, whatever ``max_seq_len``; one ``*`` layer:
    K and V of 2 heads x 16 at every position — plain K/V planes BESIDE
    slot state in one graph.  The planner counts the held experts' weights
    and the state; the cost card prices the experts a pass can visit."""
    from flexflow_tpu.obs.profiler import plan_cost_card
    from flexflow_tpu.search.simulator import plan_memory_parts
    from flexflow_tpu.serve.kv_allocator import params_nbytes

    def bytes_at(seq):
        im = build(seq=seq)
        im.allocate_kv_cache()
        return im, im.kv.bytes_per_slot(), im.kv.bytes_per_token()

    _, short, tok_short = bytes_at(256)
    im, long, tok_long = bytes_at(2048)
    spread = (SLOTS + 1) / SLOTS       # the scratch row, over the real slots
    entry = 2 * 16 * 4
    assert short["kv_full"] == 256 * 2 * entry * spread
    assert long["kv_full"] == 2048 * 2 * entry * spread
    assert short["ssd_state"] == long["ssd_state"] == \
        2 * 8 * 8 * 16 * 4 * spread
    assert short["recurrent"] == long["recurrent"] == \
        2 * 3 * (64 + 64) * 4 * spread
    assert short["kv_window"] == short["linear_state"] == 0
    assert tok_short == tok_long == 2 * entry * spread
    im.init_operators_inference()
    parts = plan_memory_parts(im.plan, training=False)
    assert parts["weights"] == params_nbytes(im.params)
    assert parts["kv_state"] == sum(
        a.nbytes for bufs in im.state.values() for a in bufs.values())
    card = plan_cost_card(im)
    routed = E_LAYERS * 4 * 2 * 64 * 32 * 4
    assert card.routed_weight_bytes == routed
    assert (card.routed_experts, card.routed_top_k) == (4, 3)
    assert card.weight_bytes_for(16) == card.weight_bytes
    assert card.weight_bytes_for(1) == card.weight_bytes - routed / 4


@pytest.mark.parametrize("kw,needs", [
    (dict(kv_page_size=32), "state-space"),
    (dict(kv_dtype="int8"), "float32 matrix state"),
    (dict(max_spec_tokens=7), "snapshot per"),
    (dict(tp=2), "exchange of rows"),
    (dict(pp=2), "load"),
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


def test_the_scans_load_counters_reach_the_span_the_journal_and_the_trace():
    """Through ``RequestManager.generate``: what the decode scans counted
    on the device — held experts visited, pairs on them, the fullest
    expert's pairs, per step and routed layer — rides on the ``commit``
    span, in the tick journal's records and in the trace counters, and
    equals a count made from the REFERENCE's routing of the same tokens."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.serve import GenerationConfig, RequestManager

    im = deployment()
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=tel)
    prompts = [tokens(20, salt=31), tokens(9, salt=32)]
    try:
        im._paths_counted = 0
        im.take_expert_load()   # earlier tests' scans, which no scheduler read
        outs = rm.generate(prompts, 40)
        assert [len(o) for o in outs] == [40, 40]
        commits = [e["args"] for e in tel.trace.trace_events()
                   if e["name"] == "commit" and "expert_steps" in e["args"]]
        assert commits
        total = {k: sum(c[k] for c in commits) for k in (
            "experts_visited", "expert_pairs", "expert_pairs_max",
            "expert_steps", "scan_tokens")}
        records = rm.journal.records()
        for k, v in total.items():
            assert sum(r[k] for r in records) == v, k
        counters = tel.metrics.snapshot()
        assert counters["moe.experts_visited"] == total["experts_visited"]
        assert counters["moe.pairs"] == total["expert_pairs"]
        assert counters["moe.pairs_max"] == total["expert_pairs_max"]
        assert any(e["name"] == "moe.pairs" and e["ph"] == "C"
                   for e in tel.trace.trace_events())
        assert counters["attention_path.mamba2_scan.slot_order"] >= 1
        assert counters["attention_path.causal_conv1d.slot_order"] >= 1
        assert counters["attention_path.causal_conv1d.rows"] >= 1
        assert counters["attention_path.moe_experts.ragged_dot"] >= 1
        # every scan step ran both rows (no row ends before the other);
        # the first token of each answer is the prefill's
        # (the scans run in powers of two: the steps past a row's budget
        # run it frozen, and a frozen row routes nothing)
        steps = total["scan_tokens"] // 2
        assert total["expert_steps"] >= steps * E_LAYERS
        assert total["expert_steps"] % E_LAYERS == 0
        assert total["expert_pairs"] <= 2 * 3 * total["expert_steps"]
        # the reference's routing of the same sequences, at the rows the
        # scans decoded: the input of every E layer, routed
        key = sw.base_key(SEED)
        visited = pairs = fullest = 0
        per_step = {}
        for prompt, out in zip(prompts, outs):
            full = prompt + out[:-1]
            _, _, hidden = reference_forward(full)
            for i, kind in enumerate(ref.layer_kinds(HF)):
                if kind != ref.EXPERTS:
                    continue
                w = sw.draw_table(key, i, ref.LAYER, HF, "float32")
                n = ref.rms_norm(hidden[i][None], w["norm.weight"], 1e-5)
                ids, _ = ref.route(HF, w, n)
                ids = np.asarray(ids)[0]
                # scan step s decodes the row at position len(prompt) + s
                for s in range(steps):
                    row = ids[len(prompt) + s]
                    per_step.setdefault((i, s), []).extend(
                        int(e) for e in row if e < 4)
        for held in per_step.values():
            count = np.bincount(held, minlength=4)
            visited += int((count > 0).sum())
            pairs += int(count.sum())
            fullest += int(count.max())
        assert (total["experts_visited"], total["expert_pairs"],
                total["expert_pairs_max"]) == (visited, pairs, fullest)
    finally:
        im.telemetry = type(im).telemetry


CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_published_config_builds_the_published_model():
    """``from_hf_config`` on the published keys: 23 Mamba-2 layers of
    38.74 M parameters, 6 attention layers of 23.40 M and 23 expert layers
    where ``hybrid_override_pattern`` says; the benchmark's configuration
    file holds the catalog row's keys unchanged but the four it lists as
    reduced, its pattern is the published one's first nine entries, and the
    cut graph holds 3.166 B parameters."""
    import math

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-nano-d9-e64.json")) as f:
        conf = json.load(f)
    reduced = conf["benchmark"]["reduced"]
    assert set(reduced) == {"num_hidden_layers", "hybrid_override_pattern",
                            "n_routed_experts", "vocab_size"}
    published = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert [published.count(k) for k in "ME*"] == [23, 23, 6]
    assert conf["hybrid_override_pattern"] == published[:9]
    assert (conf["n_routed_experts"], conf["router_num_experts"],
            conf["expert_share_index"], conf["expert_share_count"]) == \
        (64, 128, 0, 2)
    if os.path.exists(CATALOG_FILE):
        with open(CATALOG_FILE) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert row["config"]["hybrid_override_pattern"] == published
        assert {k: conf[k] for k in row["config"] if k not in reduced} == \
            {k: v for k, v in row["config"].items() if k not in reduced}
        assert row["config"]["n_routed_experts"] == \
            conf["router_num_experts"]
    hf = {k: v for k, v in conf.items() if k != "benchmark"}
    cfg = ServeModelConfig.from_hf_config(hf)
    assert cfg.layer_norm_eps == 1e-5 and cfg.sliding_window is None
    assert [builder.layer_kind(cfg, i) for i in range(9)] == list("MEMEM*EME")
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, cfg, 16)
    size = lambda names: sum(math.prod(p.spec.shape) for n in ff.graph.nodes
                             for p in n.op.params() if names(n.name))
    layer = lambda i: size(lambda name: f".layers.{i}." in name)
    d = 2688
    assert layer(0) == d * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * d + d
    assert layer(5) == d * (4096 + 512) + 4096 * d + d
    assert layer(1) == 64 * 2 * d * 1856 + 2 * d * 3712 + d * 128 + 128 + d
    total = size(lambda name: True)
    assert total == 4 * layer(0) + layer(5) + 4 * layer(1) \
        + 2 * 65536 * d + d
    assert round(total / 1e6) == 3166
    # the reference's tables draw the same tree
    tree = jax.eval_shape(
        lambda key: ref.program_tree(hf, sw.draw_table(
            key, sw.GLOBAL_ID, ref.GLOBAL, hf, "bfloat16"), [
                sw.draw_table(key, i, ref.LAYER, hf, "bfloat16")
                for i in range(9)]), sw.base_key(1))
    assert sum(math.prod(a.shape) for a in jax.tree.leaves(tree)) == total


def test_row_write_kernel_on_and_off_serves_the_same(row_write_on_and_off):
    """The decode scan's K/V rows by ``kv_row_write`` and by the chain it
    replaced — the one attention layer's cache: the same tokens, the same caches."""
    row_write_on_and_off(lambda: seeded(build(use_pallas=True)),
                         [tokens(40, salt=51), tokens(9, salt=52)])
