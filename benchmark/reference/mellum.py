"""Plain reference for ``model_type: mellum`` (JetBrains Mellum 2: a
sequential pre-norm block of grouped-query attention and a mixture of small
gated experts in every layer; three sliding-window layers to one full layer,
rotary in BOTH kinds under two parameter sets).  float32, ``HIGHEST``
precision; NO cache, NO ring, NO sort, NO grouped GEMM, NO kernel: attention
is the masked softmax over the whole sequence (queries in blocks so that it
fits at the timed sizes), the experts a loop over all of them under a 0 /
weight mask, one expert upcast to float32 at a time.  Tensors in kernel form
(``[in, out]``), see seeded_weights.py.

Layer l, rows ``x [T, d]``, position t; ``RMS(x, g) = x / sqrt(mean(x^2) +
rms_norm_eps) g``:

  n = RMS(x, g1);  q = n W_q -> H heads of hd;  k = n W_k, v = n W_v -> KV
      heads of hd; no bias, no q/k norm; query head i reads K/V head
      i // (H / KV)
  rotary over the whole head on the pairs (j, j + hd / 2), j < hd / 2, under
  the set ``rope_parameters[layer_types[l]]``:
    "sliding_attention" (rope_type default): pair j turned by
        t theta^(-2j / hd); key s visible to query t iff
        t - sliding_window < s <= t
    "full_attention" (rope_type yarn): f_j = theta^(-2j / hd);
        corr(r) = hd ln(L / (2 pi r)) / (2 ln theta), L the original
        context; low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)),
        clamped to [0, hd - 1]; r_j = clip((j - low) / (high - low), 0, 1);
        pair j turned by t f_j ((1 - r_j) + r_j / factor); cos AND sin times
        ``attention_factor`` (on q and on k: a score grows by its square);
        key s visible iff s <= t
  a = concat_i(softmax_s(q_i . k(s) / sqrt(hd)) v(s)) W_o;  x <- x + a
  m = RMS(x, g2)
  ``mlp_layer_types[l] == "sparse"``: p = softmax(m W_r) in float32 over ALL
      ``num_experts``; chosen = the ``num_experts_per_tok`` largest (ties to
      the lower id); w = p[chosen] / sum p[chosen]   (``norm_topk_prob``);
      E_e(m) = W_down,e (silu(W_gate,e m) * W_up,e m), width
      ``moe_intermediate_size``; FFN = sum over chosen e of w_e E_e(m) — no
      shared expert, no scaling factor, no router bias
  ``"dense"``: FFN = W_down (silu(W_gate m) * W_up m), width
      ``intermediate_size`` (the published list has no such layer: the
      tables then give its tensors no columns and nothing is drawn)
  x <- x + FFN;   logits = RMS(x_T, g_f) W_head                (untied)

ASSUMED (the catalog's ``config`` does not print them; the configuration's
file lists each): the pre-norm sequential block and the tensor names below
(the family's convention); NO q/k norm (the config has no key for one);
softmax scoring (the config names none; ``norm_topk_prob`` is the softmax
family's key); bf16.  LEFT OUT: the multi-token-prediction head the model
card mentions — ``config`` has no key for it; ``max_window_layers`` and
``use_sliding_window`` are inert with ``layer_types`` given.

``seeded_weights`` draws every matrix normal at std 0.02, gains 1 + 0.1 n.
:func:`published_init` scales the ROUTER's draw by ``ROUTER_GAIN`` = 2, as
``deepseek_v2``'s reference does and for its reason: at std 0.02 over a
normed row of 2304 the 64 scores have spread 0.96 and the softmax is nearly
flat — rank 8 and rank 9 of 64 lie a rounding apart in a large share of the
rows, and each flip trades an eighth of the routed sum, which here IS the
whole FFN (no shared expert stands beside it).  A trained router is more
decided; times 2 the scores' spread is 1.9.  No equation changes; both sides
draw through the one function.
"""

import math

import jax
import jax.numpy as jnp

from .common import HI, f32, mm
from .nemotron_h import Stream  # hidden states + the next layer's index

SLIDING, FULL = "sliding_attention", "full_attention"
SPARSE, DENSE = "sparse", "dense"
Q_BLOCK = 512      # queries at a time, so that the scores fit beside a deployment
ROUTER_GAIN = 2.0  # on the router's drawn matrix: see the module docstring

_E = lambda hf: hf["hidden_size"]
_V = lambda hf: hf["vocab_size"]
_H = lambda hf: hf["num_attention_heads"]
_KV = lambda hf: hf["num_key_value_heads"]
_HD = lambda hf: hf.get("head_dim") or _E(hf) // _H(hf)
_F = lambda hf: hf["moe_intermediate_size"]
_N = lambda hf: hf["num_experts"]
_EPS = lambda hf: hf.get("rms_norm_eps", 1e-6)
# a dense layer's width; 0 (no columns, nothing drawn or counted) where the
# configuration has no dense layer
_I = lambda hf: hf["intermediate_size"] if DENSE in mlp_kinds(hf) else 0


def num_layers(hf):
    return hf["num_hidden_layers"]


def layer_kinds(hf):
    kinds = list(hf["layer_types"])
    assert len(kinds) == num_layers(hf) and set(kinds) <= {SLIDING, FULL}
    return kinds


def mlp_kinds(hf):
    kinds = list(hf.get("mlp_layer_types") or [SPARSE] * num_layers(hf))
    assert len(kinds) == num_layers(hf) and set(kinds) <= {SPARSE, DENSE}
    return kinds


def attention_shape(hf):
    """``(query heads, key/value heads, head size)``."""
    return _H(hf), _KV(hf), _HD(hf)


# the published names are ASSUMED from the family's convention (no checkpoint
# is on this machine): ``model.layers.<l>.<name>.weight`` with the routed
# experts ``mlp.experts.<e>.{gate,up,down}_proj`` stacked on a leading axis
# here (flexflow_tpu/serve/weights.py lists them for an importer)
GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
    ("lm_head", lambda hf: (_E(hf), _V(hf)), "matrix"),
]
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("self_attn.q_proj", lambda hf: (_E(hf), _H(hf) * _HD(hf)), "matrix"),
    ("self_attn.k_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("self_attn.v_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("self_attn.o_proj", lambda hf: (_H(hf) * _HD(hf), _E(hf)), "matrix"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("mlp.gate.weight", lambda hf: (_E(hf), _N(hf)), "matrix"),
    ("mlp.experts.gate_proj", lambda hf: (_N(hf), _E(hf), _F(hf)), "matrix"),
    ("mlp.experts.up_proj", lambda hf: (_N(hf), _E(hf), _F(hf)), "matrix"),
    ("mlp.experts.down_proj", lambda hf: (_N(hf), _F(hf), _E(hf)), "matrix"),
    ("mlp.gate_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.up_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
]


def published_init(hf, w):
    """The drawn tensors that a trained model holds otherwise — the ONE
    place, for ``program_tree`` and ``layer`` alike: the router's matrix in
    float32, times ``ROUTER_GAIN`` (the module's docstring says why)."""
    return {"mlp.gate.weight":
            w["mlp.gate.weight"].astype(jnp.float32)
            * float(hf.get("router_gain", ROUTER_GAIN))}


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (``serve/models/mellum.py``): q, k
    and v side by side per K/V head, the router's matrix in float32."""
    e, h, kv, hd = _E(hf), _H(hf), _KV(hf), _HD(hf)
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.norm": {"gamma": g["norm.weight"]},
        "lm_head": {"kernel": g["lm_head"]},
    }
    for i, (w, mlp) in enumerate(zip(layers, mlp_kinds(hf))):
        p = f"model.layers.{i}"
        tree[f"{p}.input_layernorm"] = {"gamma": w["input_layernorm.weight"]}
        tree[f"{p}.post_attention_layernorm"] = {
            "gamma": w["post_attention_layernorm.weight"]}
        tree[f"{p}.self_attn"] = {
            "qkv": jnp.concatenate(
                [w["self_attn.q_proj"].reshape(e, kv, h // kv, hd),
                 w["self_attn.k_proj"].reshape(e, kv, 1, hd),
                 w["self_attn.v_proj"].reshape(e, kv, 1, hd)], axis=2),
            "o_proj": w["self_attn.o_proj"]}
        if mlp == DENSE:
            for n in ("gate", "up", "down"):
                tree[f"{p}.mlp.{n}_proj"] = {"kernel": w[f"mlp.{n}_proj"]}
            continue
        tree[f"{p}.mlp.gate"] = {
            "weight": published_init(hf, w)["mlp.gate.weight"]}
        tree[f"{p}.mlp.experts"] = {
            n: w[f"mlp.experts.{n}_proj"] for n in ("gate", "up", "down")}
    return tree


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_set(hf, kind):
    """``rope_parameters[kind]``: mellum nests its sets by layer type."""
    return hf["rope_parameters"][kind]


def turns(hf, kind):
    """``(turns per position of the hd / 2 pairs, amplitude)`` of the layers
    of ``kind``: plain ``theta^(-2j / hd)`` and 1 for ``rope_type`` default,
    YaRN's blend and the stated ``attention_factor`` for ``yarn``."""
    ps, hd = rope_set(hf, kind), _HD(hf)
    theta = float(ps["rope_theta"])
    f = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if ps.get("rope_type", "default") == "default":
        return f, 1.0
    assert ps["rope_type"] == "yarn", ps
    corr = lambda r: hd * math.log(
        ps["original_max_position_embeddings"] / (2 * math.pi * r)) / (
            2 * math.log(theta))
    low = max(math.floor(corr(ps["beta_fast"])), 0)
    high = min(math.ceil(corr(ps["beta_slow"])), hd - 1)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    amp = ps.get("attention_factor")
    if amp is None:
        amp = 0.1 * math.log(ps["factor"]) + 1.0
    return f * (1.0 - ramp) + f / ps["factor"] * ramp, float(amp)


def rope(hf, kind, x):
    """``x [B, T, heads, hd]`` at positions ``0 .. T - 1``: the pair ``(j, j
    + hd / 2)`` turned under ``kind``'s set."""
    f, amp = turns(hf, kind)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * f
    cos, sin = amp * jnp.cos(ang)[:, None], amp * jnp.sin(ang)[:, None]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(hf, w, n, sliding):
    """The attention on the normed rows ``n [B, T, d]``; ``sliding`` (a
    traced boolean): the window and the ring layers' rotary set, or
    everything before the query and the full layers' set."""
    w = f32({m: w[m] for m in w if m.startswith("self_attn.")})
    b, t, _ = n.shape
    h, kv, hd = attention_shape(hf)
    q = mm(n, w["self_attn.q_proj"]).reshape(b, t, h, hd)
    k = mm(n, w["self_attn.k_proj"]).reshape(b, t, kv, hd)
    v = mm(n, w["self_attn.v_proj"]).reshape(b, t, kv, hd)
    kinds = set(layer_kinds(hf))
    turned = lambda x: (
        rope(hf, SLIDING, x) if kinds == {SLIDING}
        else rope(hf, FULL, x) if kinds == {FULL}
        else jnp.where(sliding, rope(hf, SLIDING, x), rope(hf, FULL, x)))
    q, k = turned(q), turned(k)
    window = hf.get("sliding_window") or t
    q = q.reshape(b, t, kv, h // kv, hd)
    out = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        s = jnp.einsum("btkgd,bskd->bkgts", q[:, lo:hi], k[:, :hi],
                       precision=HI) / jnp.sqrt(jnp.float32(hd))
        back = jnp.arange(lo, hi)[:, None] - jnp.arange(hi)[None, :]
        seen = (back >= 0) & (~sliding | (back < window))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bkgts,bskd->btkgd", p, v[:, :hi],
                              precision=HI))
    heads = jnp.concatenate(out, axis=1).reshape(b, t, h * hd)
    return mm(heads, w["self_attn.o_proj"])


def gated_mlp(n, gate, up, down):
    return mm(jax.nn.silu(mm(n, gate)) * mm(n, up), down)


def route(hf, w, n):
    """``(ids [B, T, k], weights [B, T, k])`` over all the experts."""
    p = jax.nn.softmax(mm(n, published_init(hf, w)["mlp.gate.weight"]),
                       axis=-1)
    wts, ids = jax.lax.top_k(p, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob", True):
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    return ids, wts


def routed_experts(hf, w, n, ids, wts):
    """Every expert on every row, times the row's weight for it or 0; one
    expert upcast at a time."""
    each = jnp.arange(_N(hf))
    dense = jnp.sum(jnp.where(ids[None] == each[:, None, None, None],
                              wts[None], 0.0), axis=-1)     # [E, B, T]

    def one(acc, at):
        gate, up, down, weight = at
        y = gated_mlp(n, *(a.astype(jnp.float32) for a in (gate, up, down)))
        return acc + weight[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], dense))
    return out


def mixture(hf, w, n):
    return routed_experts(hf, w, n, *route(hf, w, n))


def dense_mlp(hf, w, n):
    return gated_mlp(n, *(w[f"mlp.{m}_proj"].astype(jnp.float32)
                          for m in ("gate", "up", "down")))


def embed(hf, g, ids):
    return Stream(g["embed_tokens"][ids].astype(jnp.float32), jnp.int32(0))


def layer(hf, w, x):
    up32 = lambda name: w[name].astype(jnp.float32)
    sliding = jnp.asarray([k == SLIDING for k in layer_kinds(hf)])[x.layer]
    h = x.h + attention(hf, w, rms_norm(
        x.h, up32("input_layernorm.weight"), _EPS(hf)), sliding)
    n = rms_norm(h, up32("post_attention_layernorm.weight"), _EPS(hf))
    mlps = mlp_kinds(hf)
    if DENSE not in mlps:
        ffn = mixture(hf, w, n)
    else:
        dense = jnp.asarray([m == DENSE for m in mlps])[x.layer]
        ffn = jax.lax.cond(dense, lambda: dense_mlp(hf, w, n),
                           lambda: mixture(hf, w, n))
    return Stream(h + ffn, x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    return mm(rms_norm(x, g["norm.weight"], _EPS(hf)), g["lm_head"])
