"""Plain reference for ``model_type: kimi_linear`` (Kimi-Linear-48B-A3B: Kimi
Delta Attention in three layers of four, latent attention WITHOUT a
positional term in the fourth, one leading dense layer, then mixtures of
gated experts scored by sigmoid beside one shared expert).  float32,
``HIGHEST`` precision; the delta rule as its RECURRENCE, token by token
(``lax.scan``), the latent attention in the MATERIALISED form only — NO
kernel, NO cache, NO chunking, NO absorption, NO sort, NO grouped GEMM: the
experts a loop over the held ones under a 0 / weight mask, one expert upcast
at a time.  Tensors in kernel form (``[in, out]``; a depthwise conv ``[taps,
channels]``), see seeded_weights.py.

Block l (1-based, as the config counts), rows ``x [T, d]``, position t;
``RMS(x, g) = x / sqrt(mean(x^2) + rms_norm_eps) g``:

  n = RMS(x, g1);  x <- x + Mix_l(n);  n = RMS(x, g2);  x <- x + FFN_l(n)

  Mix_l, l in linear_attn_config.kda_layers (heads h < num_heads, D =
  head_dim, K = short_conv_kernel_size):
      q' = n W_q, k' = n W_k, v' = n W_v                     (each [H D])
      q~ = silu(conv_q(q')), k~, v~ likewise: each its OWN depthwise causal
           conv of K taps over the sequence's positions, no bias (positions
           before the first read zero)
      q_t = q~_h / max(|q~_h|, 1e-6) D^-1/2;  k_t = k~_h / max(|k~_h|, 1e-6)
      g_t = -exp(A_log_h) softplus((n W_fa W_fb)_h + dt_bias_h)   [D] a head
      beta_t = sigmoid((n W_beta)_h)                          a scalar a head
      S' = Diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t                      (S [key channel, value channel],
                                            zero before position 0)
      y_h = RMS(o_t, g_o) * sigmoid((n W_ga W_gb)_h);  a = concat_h(y_h) W_o
  Mix_l, l in linear_attn_config.full_attn_layers: latent attention exactly
      as benchmark/reference/deepseek_v2.py writes it — [q_n | q_r] = n W_q
      per head, [c' | k_r] = n W_kv_a, c = RMS(c', g_kv), [k_n,i | v_i] = c
      W_kv_b, s = (nope + rope)^-1/2 (q_n,i . k_n,i(j) + q_r,i . k_r(j)), j
      <= t — with NO rotation of either part (mla_use_nope) and no mscale
  FFN_l, l <= first_k_dense_replace:  W_down (silu(W_gate n) * W_up n), width
      intermediate_size
  else:  s = sigmoid(n W_r) in float32 over ALL router_num_experts; chosen =
      the num_experts_per_token largest of s + e_score_correction_bias (ties
      to the lower id); weights s[chosen] / (sum s[chosen] + 1e-20)
      (moe_renormalize) x routed_scaling_factor;
      E_e(n) = W_down,e (silu(W_gate,e n) * W_up,e n), width
      moe_intermediate_size;
      FFN = sum over chosen AND HELD e of w_e E_e(n) + shared(n)  (ONE gated
      MLP of width num_shared_experts x moe_intermediate_size, unweighted)
  logits = RMS(x_T, g_f) W_head                                  (untied)

A chip may hold a SHARE of the routed experts: ``num_experts`` of them,
published ids from ``expert_share_index x num_experts``; the router scores
``router_num_experts``.  A pair routed to an absent expert adds nothing.

ASSUMED (the catalog's ``config`` does not print them; the configuration's
file lists each): bf16; the KDA parameterisation above beyond the four sizes
``linear_attn_config`` prints (the low-rank width = head_dim, the softplus /
A_log / dt_bias form of the decay, the L2 norms with D^-1/2 on q, the
sigmoid-gated head norm, no conv bias, no bias on W_gb — the family's
published modeling code, from memory); the 1-based reading of the two layer
lists; the tensor names below; the draw.

``seeded_weights`` draws every matrix normal at std 0.02 (``init_std``),
gains 1 + 0.1 n.  :func:`published_init` maps what the family initialises
otherwise onto that initialisation (the decay would else be ~ exp(-0.7) a
step in EVERY channel: nothing kept past a dozen positions, and a scalar
decay a head would serve as well).  It also takes out what a seeded draw
adds to EVERY row alike: SiLU behind the convs is mostly positive, so ``q``,
``k`` and ``v`` — and with them ``o`` — carry a mean of one sign in every
channel, ``W_o`` maps that into ONE direction of the stream whatever the
token, and the routers behind then like some experts seven times as much
as others whatever the row (at the published widths, as drawn: a third of a
normed row's size is the same in every row, and a twelfth of the 256
experts get no row in 512) — a seeded draw has no trained
``e_score_correction_bias`` to balance that.  Every channel's four taps are
brought to ONE norm (so that every channel's SiLU has the same mean) and the
mean row is taken out of the KDA ``W_o`` (:func:`centered`, as
``nemotron_h`` does for its ``relu^2`` experts): the shared part of a row
falls to a fourteenth and every expert's load to 0.55 .. 1.7 of the mean (my
count on the CPU, 4 096 rows of one sequence at the published widths).
"""

import math

import jax
import jax.numpy as jnp

from .common import f32, mm
from .deepseek_v2 import Q_BLOCK, gated_mlp, rms_norm
from .nemotron_h import Stream  # hidden states + the next layer's index

DT_STRIDE = 37       # channel c takes step (37 c) mod channels: see published_init
NORM_EPS = 1e-6      # the L2 norms' floor
DT_MIN, DT_MAX = 1e-3, 1e-1

_E = lambda hf: hf["hidden_size"]
_V = lambda hf: hf["vocab_size"]
_LA = lambda hf: hf["linear_attn_config"]
_KH = lambda hf: _LA(hf)["num_heads"]
_KD = lambda hf: _LA(hf)["head_dim"]
_KW = lambda hf: _KH(hf) * _KD(hf)
_K = lambda hf: _LA(hf)["short_conv_kernel_size"]
_LR = lambda hf: _KD(hf)                       # the low-rank pairs' width
_H = lambda hf: hf["num_attention_heads"]
_R = lambda hf: hf["kv_lora_rank"]
_DN = lambda hf: hf["qk_nope_head_dim"]
_DR = lambda hf: hf["qk_rope_head_dim"]
_DV = lambda hf: hf["v_head_dim"]
_I = lambda hf: hf["intermediate_size"]
_F = lambda hf: hf["moe_intermediate_size"]
_HELD = lambda hf: hf["num_experts"]
_SCORED = lambda hf: hf.get("router_num_experts") or hf["num_experts"]
_S = lambda hf: hf.get("num_shared_experts", 0)
_EPS = lambda hf: hf.get("rms_norm_eps", 1e-5)
LATENT = "@latent"   # marks the latent layers' tensors whose names collide


def num_layers(hf):
    return hf["num_hidden_layers"]


def layer_kinds(hf):
    """``"kda"`` or ``"latent"`` per layer, from the two 1-BASED lists."""
    kda, full = set(_LA(hf)["kda_layers"]), set(_LA(hf)["full_attn_layers"])
    kinds = []
    for l in range(1, num_layers(hf) + 1):
        assert (l in kda) != (l in full), f"layer {l} is in one list"
        kinds.append("kda" if l in kda else "latent")
    return kinds


def is_dense(hf, i):
    return not (_HELD(hf) and i >= hf.get("first_k_dense_replace", 0)
                and i % hf.get("moe_layer_freq", 1) == 0)


def held_experts(hf):
    """``(first published id, count)`` of the experts this chip holds."""
    return hf.get("expert_share_index", 0) * _HELD(hf), _HELD(hf)


def attention_shape(hf):
    """For ``headroom.py`` alone, which prices a cached position at ``2 x kv
    heads x head size`` elements in EVERY layer: only the latent layers keep
    anything by position (``kv_lora_rank + rope`` values), so ``(heads, 1,
    (rank + rope) x latent layers // (2 x layers))`` — 576 x 1 // 10 = 57 ->
    228 B a position and layer, 1 140 B over the five where the one latent
    layer holds 1 152 B: never over the cache.  It then UNDER-counts what
    empties a queue here: the delta states' 2 x 2 MB a row, KDA layer and
    step (headroom knows no state of fixed size), attention's operations
    and the queries' bytes."""
    latent = layer_kinds(hf).count("latent")
    return _H(hf), 1, (_R(hf) + _DR(hf)) * latent // (2 * num_layers(hf))


# the published names (ASSUMED: the family's convention, no checkpoint is on
# this machine): ``model.layers.<l>.<name>`` (``.weight`` on projections,
# norms and convs); the routed experts ``block_sparse_moe.experts.<e>.{w1,
# w3, w2}`` (gate, up, down) stacked on a leading axis here; the shared
# experts ONE module.  The latent layers' ``self_attn.q_proj`` and
# ``self_attn.o_proj`` carry the KDA layers' names with other shapes: the
# union table marks them ``@latent`` (flexflow_tpu/serve/weights.py lists
# all of them for an importer)
GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
    ("lm_head", lambda hf: (_E(hf), _V(hf)), "matrix"),
]
# the UNION of the two mixers', the dense and the expert layer's tensors
# (``seeded_weights`` draws by table row; what a layer does not use is never
# computed).  headroom.py's parameter count therefore counts BOTH mixers, the
# dense FFN AND every held expert in every layer: PERF.md section 7.
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
    ("self_attn.q_proj" + LATENT,
     lambda hf: (_E(hf), _H(hf) * (_DN(hf) + _DR(hf))), "matrix"),
    ("self_attn.kv_a_proj_with_mqa", lambda hf: (_E(hf), _R(hf) + _DR(hf)),
     "matrix"),
    ("self_attn.kv_a_layernorm.weight", lambda hf: (_R(hf),), "gain"),
    ("self_attn.kv_b_proj", lambda hf: (_R(hf), _H(hf) * (_DN(hf) + _DV(hf))),
     "matrix"),
    ("self_attn.o_proj" + LATENT, lambda hf: (_H(hf) * _DV(hf), _E(hf)),
     "matrix"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("mlp.gate_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.up_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
    ("block_sparse_moe.gate.weight", lambda hf: (_E(hf), _SCORED(hf)),
     "matrix"),
    ("block_sparse_moe.gate.e_score_correction_bias",
     lambda hf: (_SCORED(hf),), "bias"),
    ("block_sparse_moe.experts.w1", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("block_sparse_moe.experts.w3", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("block_sparse_moe.experts.w2", lambda hf: (_HELD(hf), _F(hf), _E(hf)),
     "matrix"),
    ("block_sparse_moe.shared_experts.gate_proj",
     lambda hf: (_E(hf), _S(hf) * _F(hf)), "matrix"),
    ("block_sparse_moe.shared_experts.up_proj",
     lambda hf: (_E(hf), _S(hf) * _F(hf)), "matrix"),
    ("block_sparse_moe.shared_experts.down_proj",
     lambda hf: (_S(hf) * _F(hf), _E(hf)), "matrix"),
]
CONVS = tuple(f"self_attn.{n}_conv1d.weight" for n in "qkv")


def centered(proj):
    """``proj [in, out]`` without its mean row: an input that is the same in
    every channel then adds nothing to any row."""
    p = proj.astype(jnp.float32)
    return (p - jnp.mean(p, axis=0, keepdims=True)).astype(proj.dtype)


def published_init(hf, w):
    """The drawn tensors (normal, std ``init``) that the family initialises
    otherwise, mapped onto that initialisation — the ONE place, for
    ``program_tree`` and ``layer`` alike:

      *_conv1d.weight  torch's default for a depthwise conv of K taps,
                       U(+-1/sqrt(K)): each channel's K drawn taps rescaled
                       to that init's root-mean-square norm, sqrt(K x
                       1/(3K)) = 1/sqrt(3) — ONE norm for every channel (the
                       module's docstring says why)
      o_proj           the KDA output projection :func:`centered`: its mean
                       row taken out
      A_log            log of A spread evenly over [1, 16] by head, + the
                       draw (the family draws A uniform in [1, 16])
      dt_bias          softplus^-1(delta0) + the draw, delta0 spread
                       log-uniformly over [1e-3, 1e-1] BY CHANNEL (the
                       family draws it so at random; here channel c takes
                       step (37 c) mod channels, so that every head holds
                       the whole range): a head's 128 decays then run from
                       exp(-A 1e-3) to exp(-A 1e-1) a step — 0.999 .. 0.905
                       in head 0, 0.984 .. 0.202 in the last —, the vector
                       the mechanism is about
      e_score_correction_bias  zero, its initial value
      gate.weight      float32, times ``router_gain`` (1: as drawn)
    ``A_log``, ``dt_bias``, the bias and the router come out float32, as the
    program holds them; a conv weight in the type it was drawn in."""
    up = lambda a: a.astype(jnp.float32)
    h, width = _KH(hf), _KW(hf)
    delta0 = jnp.exp(jnp.linspace(math.log(DT_MIN), math.log(DT_MAX), width))
    delta0 = delta0[(DT_STRIDE * jnp.arange(width)) % width]
    taps = lambda a: a * jax.lax.rsqrt(3.0 * jnp.sum(a * a, axis=0,
                                                     keepdims=True))
    out = {n: taps(up(w[n])).astype(w[n].dtype) for n in CONVS}
    out["self_attn.o_proj"] = centered(w["self_attn.o_proj"])
    out["self_attn.A_log"] = jnp.log(jnp.linspace(1.0, 16.0, h)) \
        + up(w["self_attn.A_log"])
    out["self_attn.dt_bias"] = jnp.log(jnp.expm1(delta0)) \
        + up(w["self_attn.dt_bias"])
    bias = "block_sparse_moe.gate.e_score_correction_bias"
    out[bias] = jnp.zeros(w[bias].shape, jnp.float32)
    out["block_sparse_moe.gate.weight"] = \
        up(w["block_sparse_moe.gate.weight"]) \
        * float(hf.get("router_gain", 1.0))
    return out


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (``serve/models/kimi_linear.py``): a
    KDA layer's three projections side by side as ONE ``qkv_proj`` and its
    three convs as ONE depthwise conv over ``q | k | v``; a latent layer's
    ``q_proj [d, H, nope + rope]`` and ``kv_b [r, H, nope + v]`` with the
    heads apart; the router's matrix and bias in float32."""
    h, dn, dr, dv = _H(hf), _DN(hf), _DR(hf), _DV(hf)
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
            tree[f"{p}.self_attn"] = {
                "q_proj": w["self_attn.q_proj" + LATENT].reshape(
                    _E(hf), h, dn + dr),
                "kv_a": w["self_attn.kv_a_proj_with_mqa"],
                "kv_norm": w["self_attn.kv_a_layernorm.weight"],
                "kv_b": w["self_attn.kv_b_proj"].reshape(_R(hf), h, dn + dv),
                "o_proj": w["self_attn.o_proj" + LATENT]}
        if is_dense(hf, i):
            for n in ("gate", "up", "down"):
                tree[f"{p}.mlp.{n}_proj"] = {"kernel": w[f"mlp.{n}_proj"]}
            continue
        moe = f"{p}.block_sparse_moe"
        tree[f"{moe}.gate"] = {
            "weight": init["block_sparse_moe.gate.weight"],
            "e_score_correction_bias":
                init["block_sparse_moe.gate.e_score_correction_bias"]}
        tree[f"{moe}.experts"] = {
            n: w[f"block_sparse_moe.experts.{m}"]
            for n, m in (("gate", "w1"), ("up", "w3"), ("down", "w2"))}
        if _S(hf):
            for n in ("gate", "up", "down"):
                tree[f"{moe}.shared_experts.{n}_proj"] = {
                    "kernel": w[f"block_sparse_moe.shared_experts.{n}_proj"]}
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
    for m in ("w1", "w3", "w2"):
        cut[f"block_sparse_moe.experts.{m}"] = \
            w[f"block_sparse_moe.experts.{m}"][index * es:(index + 1) * es]
    return {**hf, "num_experts": es, "router_num_experts": _SCORED(hf),
            "expert_share_index": index, "expert_share_count": count}, cut


def short_conv(x, weight):
    """``x [B, T, C]`` through a depthwise causal conv ``weight [K, C]``
    (tap ``K - 1`` on the row itself), no bias, then SiLU."""
    k, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(padded[:, j:j + t] * weight[j] for j in range(k))
    return jax.nn.silu(y)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: ``q, k, v, g [B, T, H, D]``, ``beta
    [B, T, H]``; returns ``o [B, T, H, D]``.  (No matmul: sums of products
    in float32.)"""
    def step(s, at):
        q_t, k_t, v_t, g_t, b_t = at                   # [B, H, D], [B, H]
        s = s * jnp.exp(g_t)[..., None]                # S' [B, H, key, value]
        u = v_t - jnp.sum(s * k_t[..., None], axis=-2)
        s = s + (b_t[..., None] * k_t)[..., None] * u[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    b, _, h, d = q.shape
    seq = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), jnp.float32),
                        tuple(seq(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda(hf, w, n):
    """Kimi Delta Attention on the normed rows ``n [B, T, d]``."""
    init = published_init(hf, w)
    w = f32({m: w[m] for m in w if m.startswith("self_attn.")})
    b, t, _ = n.shape
    h, d = _KH(hf), _KD(hf)
    heads = lambda a: a.reshape(b, t, h, d)
    q, k, v = (heads(short_conv(mm(n, w[f"self_attn.{m}_proj"]),
                                init[f"self_attn.{m}_conv1d.weight"]
                                .astype(jnp.float32))) for m in "qkv")
    unit = lambda a: a / jnp.maximum(
        jnp.sqrt(jnp.sum(a * a, -1, keepdims=True)), NORM_EPS)
    q, k = unit(q) * d ** -0.5, unit(k)
    raw = mm(mm(n, w["self_attn.f_a_proj"]), w["self_attn.f_b_proj"])
    g = -jnp.exp(init["self_attn.A_log"])[:, None] * heads(
        jax.nn.softplus(raw + init["self_attn.dt_bias"]))
    beta = jax.nn.sigmoid(mm(n, w["self_attn.b_proj"]))
    o = delta_rule(q, k, v, g, beta)
    gate = mm(mm(n, w["self_attn.g_a_proj"]), w["self_attn.g_b_proj"])
    y = rms_norm(o, w["self_attn.o_norm.weight"], _EPS(hf)) \
        * jax.nn.sigmoid(heads(gate))
    return mm(y.reshape(b, t, h * d),
              init["self_attn.o_proj"].astype(jnp.float32))


def latent_attention(hf, w, n):
    """Latent attention, materialised, NOTHING rotated, on the normed rows
    ``n [B, T, d]``."""
    w = f32({m: w[m] for m in w if m.startswith("self_attn.")})
    b, t, _ = n.shape
    h, r, dn, dr, dv = _H(hf), _R(hf), _DN(hf), _DR(hf), _DV(hf)
    q = mm(n, w["self_attn.q_proj" + LATENT]).reshape(b, t, h, dn + dr)
    q_n, q_r = q[..., :dn], q[..., dn:]
    ckr = mm(n, w["self_attn.kv_a_proj_with_mqa"])
    c = rms_norm(ckr[..., :r], w["self_attn.kv_a_layernorm.weight"],
                 _EPS(hf))
    k_r = ckr[..., r:]                                        # [B, T, rope]
    kv = mm(c, w["self_attn.kv_b_proj"]).reshape(b, t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    hi = jax.lax.Precision.HIGHEST
    out = []
    for lo in range(0, t, Q_BLOCK):
        top = min(lo + Q_BLOCK, t)
        s = (jnp.einsum("bthn,bshn->bhts", q_n[:, lo:top], k_n[:, :top],
                        precision=hi)
             + jnp.einsum("bthr,bsr->bhts", q_r[:, lo:top], k_r[:, :top],
                          precision=hi)) / math.sqrt(dn + dr)
        seen = jnp.arange(lo, top)[:, None] >= jnp.arange(top)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhts,bshv->bthv", p, v[:, :top], precision=hi))
    heads = jnp.concatenate(out, axis=1).reshape(b, t, h * dv)
    return mm(heads, w["self_attn.o_proj" + LATENT])


def route(hf, w, n):
    """``(ids [B, T, k], weights [B, T, k])`` over ALL the scored experts."""
    init = published_init(hf, w)
    s = jax.nn.sigmoid(mm(n, init["block_sparse_moe.gate.weight"]))
    _, ids = jax.lax.top_k(
        s + init["block_sparse_moe.gate.e_score_correction_bias"],
        hf["num_experts_per_token"])
    wts = jnp.take_along_axis(s, ids, axis=-1)
    if hf.get("moe_renormalize", True):
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

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        w["block_sparse_moe.experts.w1"], w["block_sparse_moe.experts.w3"],
        w["block_sparse_moe.experts.w2"], dense))
    return out


def shared_experts(hf, w, n):
    return gated_mlp(n, *(
        w[f"block_sparse_moe.shared_experts.{m}_proj"].astype(jnp.float32)
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
                           lambda: latent_attention(hf, w, n))
    n = rms_norm(h, up32("post_attention_layernorm.weight"), _EPS(hf))
    dense = jnp.asarray([is_dense(hf, i) for i in range(num_layers(hf))])
    ffn = jax.lax.cond(dense[x.layer], lambda: dense_mlp(hf, w, n),
                       lambda: mixture(hf, w, n))
    return Stream(h + ffn, x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    return mm(rms_norm(x, g["norm.weight"], _EPS(hf)), g["lm_head"])
