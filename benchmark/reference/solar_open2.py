"""Plain reference for ``model_type: solar_open2`` (Solar-Open2-250B: Kimi
Delta Attention with ``beta`` in (0, 2) in three layers of four, GATED
softmax grouped-query attention WITHOUT a positional term in the first of
each four, a mixture of gated experts scored by sigmoid beside one shared
expert in EVERY layer).  float32, ``HIGHEST`` precision; the delta rule as
its RECURRENCE, token by token (``lax.scan``), the attention as plain causal
softmax with the queries in blocks so that the scores fit — NO kernel, NO
cache, NO chunked form, NO sort, NO grouped GEMM: the experts a loop over the
held ones under a 0 / weight mask, one expert upcast at a time.  Tensors in
kernel form (``[in, out]``; a depthwise conv ``[taps, channels]``), see
seeded_weights.py.

Block i (0-BASED, as ``gqa_layers`` counts), rows ``x [T, d]``, position t;
``RMS(x, g) = x / sqrt(mean(x^2) + rms_norm_eps) g``:

  n = RMS(x, g1);  x <- x + Mix_i(n);  n = RMS(x, g2);  x <- x + FFN_i(n)

  Mix_i, i in gqa_layers (H = num_attention_heads query heads of D =
  head_dim on KV = num_key_value_heads K/V heads; query head h reads K/V
  head h // (H / KV)):
      q = n W_q [H D], k = n W_k [KV D], v = n W_v [KV D]    (NO rotation)
      o_h(t) = sum_{j <= t} softmax_j(q_h(t) . k(j) / sqrt(D)) v(j)
      y = o * sigmoid(n W_g)           (use_gqa_gate: W_g [d, H D], one gate
                                        a channel of every head)
      a = y W_o
  Mix_i otherwise — Kimi Delta Attention exactly as
      benchmark/reference/kimi_linear.py writes it (three projections, each
      through its OWN bias-free depthwise causal conv with SiLU, L2 norms,
      the per-channel decay through a low-rank pair, the sigmoid-gated head
      norm, W_o), at linear_attn_config's num_heads x head_dim, with
      beta_t = 2 sigmoid((n W_beta)_h)        (kda_allow_neg_eigval: in (0, 2))
      S' = Diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t
  FFN_i, i < first_k_dense_replace (0: none):  W_down (silu(W_gate n) * W_up n)
  else:  s = sigmoid(n W_r) in float32 over ALL router_num_experts; chosen =
      the num_experts_per_tok largest of s + e_score_correction_bias (ties to
      the lower id); weights s[chosen] / (sum s[chosen] + 1e-20)
      (norm_topk_prob) x routed_scaling_factor;
      FFN = sum over chosen AND HELD e of w_e E_e(n) + shared(n)  (ONE gated
      MLP of width n_shared_experts x moe_intermediate_size, unweighted)
  logits = RMS(x_T, g_f) W_head                                  (untied)

A chip may hold a SHARE of the routed experts: ``n_routed_experts`` of them,
published ids from ``expert_share_index x n_routed_experts``; the router
scores ``router_num_experts``.  A pair routed to an absent expert adds
nothing.

ASSUMED (the catalog's ``config`` does not print them; the configuration's
file lists each): bf16; the KDA parameterisation beyond the sizes
``linear_attn_config`` prints, as ``kimi_linear``'s reference assumes it,
with ``kda_use_full_proj: false`` read as "the decay's and the gate's
projections are the low-rank pairs" and ``num_kv_heads: null`` as "k and v
have num_heads heads"; ``kda_allow_neg_eigval`` as the published delta-rule
kernels' ``beta x 2``; the attention gate's FORM (elementwise sigmoid from a
projection of its own, ``g_proj``, before ``W_o``); no q/k norm; sigmoid
scoring with a zero correction bias and no group limit; the 0-based reading
of ``gqa_layers``; the tensor names below; the draw
(:func:`published_init`: kimi's for the KDA tensors and the router).
"""

import math

import jax
import jax.numpy as jnp

from . import kimi_linear as kimi
from .common import HI, f32, mm
from .deepseek_v2 import gated_mlp, rms_norm
from .nemotron_h import Stream  # hidden states + the next layer's index

_E = lambda hf: hf["hidden_size"]
_V = lambda hf: hf["vocab_size"]
_KW = kimi._KW
_KH = kimi._KH
_KD = kimi._KD
_K = kimi._K
_LR = kimi._LR
_H = lambda hf: hf["num_attention_heads"]
_KV = lambda hf: hf.get("num_key_value_heads") or hf["num_attention_heads"]
_D = lambda hf: hf.get("head_dim") or hf["hidden_size"] // _H(hf)
_I = lambda hf: hf["intermediate_size"]
_F = lambda hf: hf["moe_intermediate_size"]
_HELD = lambda hf: hf["n_routed_experts"]
_SCORED = lambda hf: hf.get("router_num_experts") or hf["n_routed_experts"]
_S = lambda hf: hf.get("n_shared_experts", 0)
_EPS = lambda hf: hf.get("rms_norm_eps", 1e-5)
_DENSE = lambda hf: min(hf.get("first_k_dense_replace", 0),
                        hf["num_hidden_layers"])
GQA = "@gqa"     # marks the attention layers' tensors whose names collide


def num_layers(hf):
    return hf["num_hidden_layers"]


def layer_kinds(hf):
    """``"gqa"`` or ``"kda"`` per layer, from the 0-BASED ``gqa_layers``."""
    full = set(hf["gqa_layers"])
    assert all(0 <= i < num_layers(hf) for i in full), "gqa_layers is 0-based"
    return ["gqa" if i in full else "kda" for i in range(num_layers(hf))]


def is_dense(hf, i):
    return i < _DENSE(hf)


def held_experts(hf):
    """``(first published id, count)`` of the experts this chip holds."""
    return hf.get("expert_share_index", 0) * _HELD(hf), _HELD(hf)


def attention_shape(hf):
    """For ``headroom.py`` alone, which prices a cached position at ``2 x kv
    heads x head size`` elements and ``4 x query heads x head size``
    operations a visible key in EVERY layer: only the attention layers keep
    anything by position, so ``(H x gqa layers // layers, KV x gqa layers //
    layers, D)`` — at the cut (1 of 4) 16 query heads on 2 K/V heads of 128:
    exactly a quarter of both, the one attention layer's spread over the
    four.  It then UNDER-counts what empties a queue: the delta rule's 8
    operations a state element a row (headroom knows no state of fixed
    size)."""
    full, n = layer_kinds(hf).count("gqa"), num_layers(hf)
    return _H(hf) * full // n, max(_KV(hf) * full // n, 1), _D(hf)


# the published names (ASSUMED: the family's convention — deepseek_v3's for
# the mixture, whose key names the config carries, kimi_linear's for the
# delta rule — no checkpoint is on this machine): ``model.layers.<l>.<name>``;
# the routed experts ``mlp.experts.<e>.{gate,up,down}_proj`` stacked on a
# leading axis here; the shared experts ONE module.  An attention layer's
# projections carry the KDA layers' names with other shapes: the union
# table marks them ``@gqa``
GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
    ("lm_head", lambda hf: (_E(hf), _V(hf)), "matrix"),
]
# the UNION of the two mixers', the dense and the expert layer's tensors
# (``seeded_weights`` draws by table row; what a layer does not use is never
# computed).  headroom.py's parameter count therefore counts BOTH mixers AND
# every held expert in every layer: benchmark/tests/test_solar_open2.py
# holds the cell's queue to a count by hand instead.
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("self_attn.q_proj", lambda hf: (_E(hf), _KW(hf)), "matrix"),
    ("self_attn.k_proj", lambda hf: (_E(hf), _KW(hf)), "matrix"),
    ("self_attn.v_proj", lambda hf: (_E(hf), _KW(hf)), "matrix"),
    ("self_attn.q_conv1d.weight", lambda hf: (_K(hf), _KW(hf)), "matrix"),
    ("self_attn.k_conv1d.weight", lambda hf: (_K(hf), _KW(hf)), "matrix"),
    ("self_attn.v_conv1d.weight", lambda hf: (_K(hf), _KW(hf)), "matrix"),
    ("self_attn.A_log", lambda hf: (_KH(hf),), "bias"),
    ("self_attn.dt_bias", lambda hf: (_KW(hf),), "bias"),
    ("self_attn.f_a_proj", lambda hf: (_E(hf), _LR(hf)), "matrix"),
    ("self_attn.f_b_proj", lambda hf: (_LR(hf), _KW(hf)), "matrix"),
    ("self_attn.b_proj", lambda hf: (_E(hf), _KH(hf)), "matrix"),
    ("self_attn.g_a_proj", lambda hf: (_E(hf), _LR(hf)), "matrix"),
    ("self_attn.g_b_proj", lambda hf: (_LR(hf), _KW(hf)), "matrix"),
    ("self_attn.o_norm.weight", lambda hf: (_KD(hf),), "gain"),
    ("self_attn.o_proj", lambda hf: (_KW(hf), _E(hf)), "matrix"),
    ("self_attn.q_proj" + GQA, lambda hf: (_E(hf), _H(hf) * _D(hf)),
     "matrix"),
    ("self_attn.k_proj" + GQA, lambda hf: (_E(hf), _KV(hf) * _D(hf)),
     "matrix"),
    ("self_attn.v_proj" + GQA, lambda hf: (_E(hf), _KV(hf) * _D(hf)),
     "matrix"),
    ("self_attn.g_proj" + GQA, lambda hf: (_E(hf), _H(hf) * _D(hf)),
     "matrix"),
    ("self_attn.o_proj" + GQA, lambda hf: (_H(hf) * _D(hf), _E(hf)),
     "matrix"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    # a dense layer's three (first_k_dense_replace 0 as published: none, and
    # the tables then give them no columns)
    ("mlp.gate_proj", lambda hf: (_E(hf), _I(hf) * bool(_DENSE(hf))),
     "matrix"),
    ("mlp.up_proj", lambda hf: (_E(hf), _I(hf) * bool(_DENSE(hf))),
     "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf) * bool(_DENSE(hf)), _E(hf)),
     "matrix"),
    ("mlp.gate.weight", lambda hf: (_E(hf), _SCORED(hf)), "matrix"),
    ("mlp.gate.e_score_correction_bias", lambda hf: (_SCORED(hf),), "bias"),
    ("mlp.experts.gate_proj", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mlp.experts.up_proj", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mlp.experts.down_proj", lambda hf: (_HELD(hf), _F(hf), _E(hf)),
     "matrix"),
    ("mlp.shared_experts.gate_proj", lambda hf: (_E(hf), _S(hf) * _F(hf)),
     "matrix"),
    ("mlp.shared_experts.up_proj", lambda hf: (_E(hf), _S(hf) * _F(hf)),
     "matrix"),
    ("mlp.shared_experts.down_proj", lambda hf: (_S(hf) * _F(hf), _E(hf)),
     "matrix"),
]
CONVS = kimi.CONVS
ROUTER, ROUTER_BIAS = "mlp.gate.weight", "mlp.gate.e_score_correction_bias"
EXPERTS = tuple(f"mlp.experts.{m}_proj" for m in ("gate", "up", "down"))


def published_init(hf, w):
    """The drawn tensors that the family initialises otherwise, mapped onto
    that initialisation — ``kimi_linear``'s map for the delta rule's (the
    convs' taps brought to one norm, the KDA ``W_o`` centered, ``A_log``
    spread over [1, 16] by head, ``dt_bias`` log-uniform BY CHANNEL) and the
    router's (float32, times ``router_gain``; the correction bias zero).
    ``beta``'s projection stays as drawn: ``2 sigmoid`` of a centred draw
    puts about HALF the rows' heads above 1 (the share
    benchmark/tests/test_solar_open2.py counts), which is what makes a
    dropped ``x 2`` — or a solve that fails past 1 — visible."""
    as_kimi = dict(w)
    as_kimi["block_sparse_moe.gate.weight"] = w[ROUTER]
    as_kimi["block_sparse_moe.gate.e_score_correction_bias"] = w[ROUTER_BIAS]
    out = kimi.published_init(hf, as_kimi)
    out[ROUTER] = out.pop("block_sparse_moe.gate.weight")
    out[ROUTER_BIAS] = out.pop(
        "block_sparse_moe.gate.e_score_correction_bias")
    return out


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (``serve/models/solar_open2.py``): a
    KDA layer's three projections side by side as ONE ``qkv_proj`` and its
    three convs as ONE depthwise conv over ``q | k | v``; an attention
    layer's q, k and v fused per K/V head as ``IncMultiHeadSelfAttention``
    holds them (``[d, KV, H / KV + 2, D]``: a group's query heads, its key,
    its value) beside ``g_proj`` and ``o_proj``; the router's matrix and
    bias in float32."""
    h, kv, d = _H(hf), _KV(hf), _D(hf)
    kinds = layer_kinds(hf)
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.norm": {"gamma": g["norm.weight"]},
        "lm_head": {"kernel": g["lm_head"]},
    }
    for i, w in enumerate(layers):
        p = f"model.layers.{i}"
        init = published_init(hf, w)
        tree[f"{p}.input_layernorm"] = {"gamma": w["input_layernorm.weight"]}
        tree[f"{p}.post_attention_layernorm"] = {
            "gamma": w["post_attention_layernorm.weight"]}
        if kinds[i] == "kda":
            tree[f"{p}.self_attn.qkv_proj"] = {"kernel": jnp.concatenate(
                [w[f"self_attn.{n}_proj"] for n in "qkv"], axis=1)}
            tree[f"{p}.self_attn.qkv_conv1d"] = {"weight": jnp.concatenate(
                [init[n] for n in CONVS], axis=1)}
            tree[f"{p}.self_attn"] = {
                "f_a": w["self_attn.f_a_proj"], "f_b": w["self_attn.f_b_proj"],
                "dt_bias": init["self_attn.dt_bias"],
                "A_log": init["self_attn.A_log"],
                "b_proj": w["self_attn.b_proj"],
                "g_a": w["self_attn.g_a_proj"], "g_b": w["self_attn.g_b_proj"],
                "o_norm": w["self_attn.o_norm.weight"],
                "o_proj": init["self_attn.o_proj"]}
        else:
            e = _E(hf)
            q = w["self_attn.q_proj" + GQA].reshape(e, kv, h // kv, d)
            k = w["self_attn.k_proj" + GQA].reshape(e, kv, 1, d)
            v = w["self_attn.v_proj" + GQA].reshape(e, kv, 1, d)
            tree[f"{p}.self_attn"] = {
                "qkv": jnp.concatenate([q, k, v], axis=2),
                "o_proj": w["self_attn.o_proj" + GQA]}
            if hf.get("use_gqa_gate"):
                tree[f"{p}.self_attn"]["g_proj"] = w["self_attn.g_proj" + GQA]
        if is_dense(hf, i):
            for n in ("gate", "up", "down"):
                tree[f"{p}.mlp.{n}_proj"] = {"kernel": w[f"mlp.{n}_proj"]}
            continue
        tree[f"{p}.mlp.gate"] = {
            "weight": init[ROUTER],
            "e_score_correction_bias": init[ROUTER_BIAS]}
        tree[f"{p}.mlp.experts"] = {
            n: w[f"mlp.experts.{n}_proj"] for n in ("gate", "up", "down")}
        if _S(hf):
            for n in ("gate", "up", "down"):
                tree[f"{p}.mlp.shared_experts.{n}_proj"] = {
                    "kernel": w[f"mlp.shared_experts.{n}_proj"]}
    return tree


def share(hf, w, index, count):
    """Share ``index`` of ``count`` of one layer's tensors ``w`` drawn for
    the fields ``hf``, as the chips that share a layer divide it: the routed
    experts by id; both mixers, the norms, the router and the shared expert
    whole.  Returns ``(hf of the share, its tensors)``."""
    held = _HELD(hf)
    assert held % count == 0
    es = held // count
    cut = dict(w)
    for m in EXPERTS:
        cut[m] = w[m][index * es:(index + 1) * es]
    return {**hf, "n_routed_experts": es, "router_num_experts": _SCORED(hf),
            "expert_share_index": index, "expert_share_count": count}, cut


def beta_of(hf, w, n):
    """``beta [B, T, H]``: ``sigmoid(n W_beta)``, times 2 where
    ``kda_allow_neg_eigval`` says so."""
    beta = jax.nn.sigmoid(mm(n, w["self_attn.b_proj"].astype(jnp.float32)))
    return 2.0 * beta if hf.get("kda_allow_neg_eigval") else beta


HEAD_GROUPS = 8      # the delta rule goes a group of heads at a time


def kda(hf, w, n):
    """Kimi Delta Attention on the normed rows ``n [B, T, d]``
    (``kimi_linear.kda``'s arithmetic with this family's ``beta``).  The
    heads are independent up to ``W_o``, so they go ``HEAD_GROUPS`` groups
    one after another (``lax.scan``, the groups' parts of ``W_o`` summed):
    at 64 heads of 128 a prompt of 24k positions is 0.8 GB a float32 ``[T,
    8192]`` array, and a dozen of them whole do not fit beside a
    deployment."""
    init = published_init(hf, w)
    beta = beta_of(hf, w, n)                                    # [B, T, H]
    w = f32({m: w[m] for m in w if m.startswith("self_attn.")
             and not m.endswith(GQA)})
    b, t, _ = n.shape
    h, d = _KH(hf), _KD(hf)
    groups = math.gcd(h, HEAD_GROUPS)
    hg = h // groups
    # per group: the columns (rows, for W_o) of its heads, leading axis first
    cols = lambda a: jnp.moveaxis(a.reshape(a.shape[0], groups, hg * d), 1, 0)
    unit = lambda a: a / jnp.maximum(
        jnp.sqrt(jnp.sum(a * a, -1, keepdims=True)), kimi.NORM_EPS)
    low_f = mm(n, w["self_attn.f_a_proj"])                      # [B, T, r]
    low_g = mm(n, w["self_attn.g_a_proj"])
    each = dict(
        proj=tuple(cols(w[f"self_attn.{m}_proj"]) for m in "qkv"),
        taps=tuple(cols(init[f"self_attn.{m}_conv1d.weight"]
                        .astype(jnp.float32)) for m in "qkv"),
        f_b=cols(w["self_attn.f_b_proj"]), g_b=cols(w["self_attn.g_b_proj"]),
        dt_bias=init["self_attn.dt_bias"].reshape(groups, hg * d),
        a_log=init["self_attn.A_log"].reshape(groups, hg),
        beta=jnp.moveaxis(beta.reshape(b, t, groups, hg), 2, 0),
        o_proj=init["self_attn.o_proj"].astype(jnp.float32).reshape(
            groups, hg * d, -1))
    heads = lambda a: a.reshape(b, t, hg, d)

    def group(acc, g):
        q, k, v = (heads(kimi.short_conv(mm(n, p), c))
                   for p, c in zip(g["proj"], g["taps"]))
        q, k = unit(q) * d ** -0.5, unit(k)
        decay = -jnp.exp(g["a_log"])[:, None] * heads(
            jax.nn.softplus(mm(low_f, g["f_b"]) + g["dt_bias"]))
        o = kimi.delta_rule(q, k, v, decay, g["beta"])
        y = rms_norm(o, w["self_attn.o_norm.weight"], _EPS(hf)) \
            * jax.nn.sigmoid(heads(mm(low_g, g["g_b"])))
        return acc + mm(y.reshape(b, t, hg * d), g["o_proj"]), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(n), each)
    return out


Q_BLOCK = 128    # queries a block: 64 heads' scores of a 24k prefix are 0.8 GB


def causal_attention(q, k, v):
    """``q [B, T, H, D]``, ``k``, ``v`` ``[B, T, KV, D]``; query head h reads
    K/V head ``h // (H / KV)``; float32 softmax over the whole prefix.  The
    queries go a block at a time (``lax.map``: ONE shape whatever the
    length, each block against all ``T`` keys under the causal mask) so that
    the scores of a prompt of tens of thousands of positions fit beside a
    deployment; the arithmetic is the plain one."""
    b, t, h, d = q.shape
    kv = k.shape[2]
    block = next(c for c in (Q_BLOCK, 64, 32, 16, 8, 4, 2, 1) if t % c == 0)
    qb = jnp.moveaxis(q.reshape(b, t // block, block, kv, h // kv, d), 1, 0)
    at = jnp.arange(t // block) * block

    def one(args):
        q_i, lo = args                                   # [B, blk, KV, G, D]
        s = jnp.einsum("btkgd,bskd->bkgts", q_i, k, precision=HI) \
            / jnp.sqrt(jnp.float32(d))
        seen = (lo + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkgts,bskd->btkgd", p, v, precision=HI)

    out = jax.lax.map(one, (qb, at))                  # [n, B, blk, KV, G, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h * d)


def gated_attention(hf, w, n):
    """Softmax grouped-query attention WITHOUT a positional term on the
    normed rows ``n [B, T, d]``, its output gated before ``W_o``."""
    up = lambda name: w[f"self_attn.{name}_proj" + GQA].astype(jnp.float32)
    b, t, _ = n.shape
    h, kv, d = _H(hf), _KV(hf), _D(hf)
    q = mm(n, up("q")).reshape(b, t, h, d)
    k = mm(n, up("k")).reshape(b, t, kv, d)
    v = mm(n, up("v")).reshape(b, t, kv, d)
    o = causal_attention(q, k, v)                     # [B, T, H D]
    if hf.get("use_gqa_gate"):
        o = o * jax.nn.sigmoid(mm(n, up("g")))
    return mm(o, up("o"))


def route(hf, w, n):
    """``(ids [B, T, k], weights [B, T, k])`` over ALL the scored experts."""
    init = published_init(hf, w)
    s = jax.nn.sigmoid(mm(n, init[ROUTER]))
    _, ids = jax.lax.top_k(s + init[ROUTER_BIAS], hf["num_experts_per_tok"])
    wts = jnp.take_along_axis(s, ids, axis=-1)
    if hf.get("norm_topk_prob", True):
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return ids, wts * hf.get("routed_scaling_factor", 1.0)


def routed_experts(hf, w, n, ids, wts):
    """``sum over chosen and held e of w_e E_e(n)``: every held expert on
    every row, times the row's weight for it or 0; one expert upcast at a
    time."""
    lo, count = held_experts(hf)
    each = jnp.arange(lo, lo + count)
    dense = jnp.sum(jnp.where(ids[None] == each[:, None, None, None],
                              wts[None], 0.0), axis=-1)     # [E_held, B, T]

    def one(acc, at):
        gate, up, down, weight = at
        y = gated_mlp(n, *(a.astype(jnp.float32) for a in (gate, up, down)))
        return acc + weight[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (*(w[m] for m in EXPERTS), dense))
    return out


def shared_experts(hf, w, n):
    return gated_mlp(n, *(
        w[f"mlp.shared_experts.{m}_proj"].astype(jnp.float32)
        for m in ("gate", "up", "down")))


def mixture(hf, w, n):
    ids, wts = route(hf, w, n)
    out = routed_experts(hf, w, n, ids, wts)
    return out + shared_experts(hf, w, n) if _S(hf) else out


def dense_mlp(hf, w, n):
    return gated_mlp(n, *(w[f"mlp.{m}_proj"].astype(jnp.float32)
                          for m in ("gate", "up", "down")))


def embed(hf, g, ids):
    return Stream(g["embed_tokens"][ids].astype(jnp.float32), jnp.int32(0))


def layer(hf, w, x):
    up32 = lambda name: w[name].astype(jnp.float32)
    n = rms_norm(x.h, up32("input_layernorm.weight"), _EPS(hf))
    is_kda = jnp.asarray([k == "kda" for k in layer_kinds(hf)])
    h = x.h + jax.lax.cond(is_kda[x.layer], lambda: kda(hf, w, n),
                           lambda: gated_attention(hf, w, n))
    n = rms_norm(h, up32("post_attention_layernorm.weight"), _EPS(hf))
    if _DENSE(hf):
        ffn = jax.lax.cond(x.layer < _DENSE(hf),
                           lambda: dense_mlp(hf, w, n),
                           lambda: mixture(hf, w, n))
    else:
        ffn = mixture(hf, w, n)
    return Stream(h + ffn, x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    return mm(rms_norm(x, g["norm.weight"], _EPS(hf)), g["lm_head"])
