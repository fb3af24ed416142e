"""Plain reference for ``model_type: cohere2_moe`` (Cohere Command A+: a
parallel block of attention and a mixture of gated experts in every layer,
three sliding-window rotary layers to one full layer without a positional
term).  float32, ``HIGHEST`` precision; NO cache, NO ring, NO sort, NO
grouped GEMM, NO kernel: attention is the masked softmax over the whole
sequence, the experts a loop over the held experts with a 0 / weight mask,
one expert upcast to float32 at a time.  Tensors in kernel form (``[in,
out]``), see seeded_weights.py.

Layer l, rows ``x [T, d]`` (eps ``layer_norm_eps``):

  n = LN(x) = (x - mean) / sqrt(var + eps) * g            (no bias)
  q = n W_q, k = n W_k, v = n W_v   (no bias, no q/k norm; H query heads on
      KV key/value heads of hd: query head i reads K/V head i // (H / KV))
  ``layer_types[l] == "sliding_attention"``: rotary on q and k over the whole
      head at ``rope_theta``, pair i = entries (2 i, 2 i + 1) turned by
      ``t * theta^(-2 i / hd)`` (``rope_gptj``: interleaved); key j visible
      to query t iff  t - sliding_window < j <= t
  ``"full_attention"``: NO positional term; causal over everything
  a = softmax(q k' / sqrt(hd)) v  W_o
  s = sigmoid(n W_r) in float32 over ALL ``router_num_experts``; chosen =
      the ``num_experts_per_tok`` largest (ties to the lower id);
      w = s[chosen] / sum s[chosen]                      (``norm_topk_prob``)
  E_e(n) = W_down,e (silu(W_gate,e n) * W_up,e n)        (width
      ``intermediate_size``)
  m = sum over chosen AND held e of w_e E_e(n)  +  1/S sum_{j<S} S_j(n)
      (the S = ``num_shared_experts`` shared experts, the same form and
      width; ``shared_expert_combination_strategy: "average"``)
  x' = x + a + m                                         (ONE norm feeds both)
  logits = logit_scale * LN_f(x_T) E'                    (E the embedding)

This chip holds a SHARE of each layer and the reference is given the same:
``num_experts`` experts (published ids from ``expert_share_index x
num_experts``) of the ``router_num_experts`` scored, the
``num_attention_heads`` / ``num_key_value_heads`` it holds, ``vocab_size``
rows of the embedding.  :func:`share_of` cuts an uncut layer's tensors into
one share's (``tests/test_cohere2_moe.py`` adds the shares up to the uncut
layer).

ASSUMED (the catalog's ``config`` does not print them; the configuration's
file lists each): ``intermediate_size`` is each routed and each shared
expert's width; "average" = the mean of the shared experts' outputs, added
unweighted; ``rope_gptj`` = interleaved pairs; full layers carry no
position; ``prefix_dense_*`` inert at ``first_k_dense_replace`` 0; no
correction bias and no scaling factor in the router; the tensor names
below.

``seeded_weights`` draws every matrix normal at std 0.02 (the catalog's
config prints no ``initializer_range``), gains 1 + 0.1 n, and NOTHING is
recentred: ``silu(gate n) * up n`` has mean zero in every hidden unit (``up
n`` is symmetric about 0 and independent of ``gate n``), so no expert's
down projection adds a vector common to all rows — what ``relu^2`` did to
nemotron_h's draw (PERF.md section 6, PR 48) cannot arise — and the router
sees rows without a common direction.
"""

import jax
import jax.numpy as jnp

from . import common
from .common import HI, f32, mm
from .nemotron_h import Stream  # hidden states + the next layer's index

SLIDING, FULL = "sliding_attention", "full_attention"
Q_BLOCK = 512   # queries at a time, so that the scores fit beside a deployment

_E = lambda hf: hf["hidden_size"]
_V = lambda hf: hf["vocab_size"]
_H = lambda hf: hf["num_attention_heads"]
_KV = lambda hf: hf["num_key_value_heads"]
_HD = lambda hf: hf.get("head_dim") or _E(hf) // _H(hf)
_F = lambda hf: hf["intermediate_size"]
_HELD = lambda hf: hf["num_experts"]
_SCORED = lambda hf: hf.get("router_num_experts") or _HELD(hf)
_S = lambda hf: hf.get("num_shared_experts", 0)
_EPS = lambda hf: hf.get("layer_norm_eps", 1e-5)


def num_layers(hf):
    return hf["num_hidden_layers"]


def layer_kinds(hf):
    kinds = list(hf["layer_types"])
    assert len(kinds) == num_layers(hf) and set(kinds) <= {SLIDING, FULL}
    return kinds


def attention_shape(hf):
    """``(query heads, key/value heads, head size)`` held here."""
    return _H(hf), _KV(hf), _HD(hf)


def held_experts(hf):
    """``(first published id, count)`` of the experts this chip holds."""
    return hf.get("expert_share_index", 0) * _HELD(hf), _HELD(hf)


# the published names are ASSUMED from the family's convention (no checkpoint
# is on this machine): ``model.layers.<l>.<name>.weight`` with the routed
# experts ``mlp.experts.<e>.{gate,up,down}_proj`` and the shared ones
# ``mlp.shared_experts.<j>.{gate,up,down}_proj`` stacked on a leading axis
# here (flexflow_tpu/serve/weights.py lists them for an importer)
GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
]
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("self_attn.q_proj", lambda hf: (_E(hf), _H(hf) * _HD(hf)), "matrix"),
    ("self_attn.k_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("self_attn.v_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("self_attn.o_proj", lambda hf: (_H(hf) * _HD(hf), _E(hf)), "matrix"),
    ("mlp.gate.weight", lambda hf: (_E(hf), _SCORED(hf)), "matrix"),
    ("mlp.experts.gate_proj", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mlp.experts.up_proj", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mlp.experts.down_proj", lambda hf: (_HELD(hf), _F(hf), _E(hf)),
     "matrix"),
    ("mlp.shared_experts.gate_proj", lambda hf: (_S(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mlp.shared_experts.up_proj", lambda hf: (_S(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mlp.shared_experts.down_proj", lambda hf: (_S(hf), _F(hf), _E(hf)),
     "matrix"),
]


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (``serve/models/cohere2_moe.py``):
    q, k and v side by side per K/V head; the router's matrix in float32;
    the shared experts side by side along the width (gate and up ``[d, S
    f]``, down ``[S f, d]``: expert j's columns, then rows, ``j f .. (j +
    1) f``); the head the embedding transposed."""
    e, h, kv, hd = _E(hf), _H(hf), _KV(hf), _HD(hf)
    s, f = _S(hf), _F(hf)
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.norm": {"gamma": g["norm.weight"]},
        "lm_head": {"kernel": g["embed_tokens"].T},
    }
    for i, w in enumerate(layers):
        p = f"model.layers.{i}"
        tree[f"{p}.input_layernorm"] = {"gamma": w["input_layernorm.weight"]}
        tree[f"{p}.self_attn"] = {
            "qkv": jnp.concatenate(
                [w["self_attn.q_proj"].reshape(e, kv, h // kv, hd),
                 w["self_attn.k_proj"].reshape(e, kv, 1, hd),
                 w["self_attn.v_proj"].reshape(e, kv, 1, hd)], axis=2),
            "o_proj": w["self_attn.o_proj"]}
        tree[f"{p}.mlp.gate"] = {
            "weight": w["mlp.gate.weight"].astype(jnp.float32)}
        tree[f"{p}.mlp.experts"] = {
            n: w[f"mlp.experts.{n}_proj"] for n in ("gate", "up", "down")}
        if s:
            side_by_side = lambda a: jnp.moveaxis(a, 0, 1).reshape(e, s * f)
            m = f"{p}.mlp.shared_experts"
            tree[f"{m}.gate_proj"] = {
                "kernel": side_by_side(w["mlp.shared_experts.gate_proj"])}
            tree[f"{m}.up_proj"] = {
                "kernel": side_by_side(w["mlp.shared_experts.up_proj"])}
            tree[f"{m}.down_proj"] = {
                "kernel": w["mlp.shared_experts.down_proj"].reshape(s * f, e)}
    return tree


def share_of(hf, w, index, count):
    """Share ``index`` of ``count`` of an UNCUT layer's tensors ``w`` and
    fields ``hf``, as a stage's chips divide a layer: the K/V groups (each
    with its query heads: columns of W_q, W_k, W_v, rows of W_o) and the
    routed experts by id; the norm, the router and the shared experts
    whole.  Returns ``(hf of the share, its tensors)``."""
    h, kv, hd = attention_shape(hf)
    held = _HELD(hf)
    assert kv % count == 0 and held % count == 0
    qs, ks, es = h // count * hd, kv // count * hd, held // count
    cut = dict(w)
    cut["self_attn.q_proj"] = w["self_attn.q_proj"][:, index * qs:
                                                    (index + 1) * qs]
    cut["self_attn.o_proj"] = w["self_attn.o_proj"][index * qs:
                                                    (index + 1) * qs]
    for n in ("k", "v"):
        cut[f"self_attn.{n}_proj"] = w[f"self_attn.{n}_proj"][
            :, index * ks:(index + 1) * ks]
    for n in ("gate", "up", "down"):
        cut[f"mlp.experts.{n}_proj"] = w[f"mlp.experts.{n}_proj"][
            index * es:(index + 1) * es]
    return {**hf, "num_attention_heads": h // count,
            "num_key_value_heads": kv // count, "num_experts": es,
            "router_num_experts": _SCORED(hf), "expert_share_index": index,
            "expert_share_count": count}, cut


def layer_norm(x, gain, eps):
    return common.layer_norm(x, gain, 0.0, eps)     # no bias


def rope_interleaved(x, theta):
    """``x [B, T, heads, hd]`` at positions ``0 .. T - 1``: the pair
    ``(2 i, 2 i + 1)`` turned by ``t * theta^(-2 i / hd)``."""
    t, hd = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(hf, w, n, sliding):
    """The attention on the normed rows ``n [B, T, d]``; ``sliding`` (a
    traced boolean): rotary and the window, or neither."""
    w = f32({m: w[m] for m in w if m.startswith("self_attn.")})
    b, t, _ = n.shape
    h, kv, hd = attention_shape(hf)
    q = mm(n, w["self_attn.q_proj"]).reshape(b, t, h, hd)
    k = mm(n, w["self_attn.k_proj"]).reshape(b, t, kv, hd)
    v = mm(n, w["self_attn.v_proj"]).reshape(b, t, kv, hd)
    theta = float(hf.get("rope_theta", 10000.0))
    q = jnp.where(sliding, rope_interleaved(q, theta), q)
    k = jnp.where(sliding, rope_interleaved(k, theta), k)
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
    """The router on the normed rows: ``(ids [B, T, k], weights [B, T, k])``
    over ALL the scored experts."""
    s = jax.nn.sigmoid(mm(n, w["mlp.gate.weight"].astype(jnp.float32)))
    _, ids = jax.lax.top_k(s, hf["num_experts_per_tok"])
    wts = jnp.take_along_axis(s, ids, axis=-1)
    if hf.get("norm_topk_prob", True):
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    return ids, wts


def routed_experts(hf, w, n, ids, wts):
    """``sum over chosen and held e of w_e E_e(n)``: every held expert on
    every row, times the row's weight for it or 0; one expert upcast at a
    time."""
    lo, count = held_experts(hf)
    each = jnp.arange(lo, lo + count)
    # [E_held, B, T]: the weight of expert e on row t, 0 where not chosen
    dense = jnp.sum(jnp.where(ids[None] == each[:, None, None, None],
                              wts[None], 0.0), axis=-1)

    def one(acc, at):
        gate, up, down, weight = at
        y = gated_mlp(n, *(a.astype(jnp.float32) for a in (gate, up, down)))
        return acc + weight[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), (
        w["mlp.experts.gate_proj"], w["mlp.experts.up_proj"],
        w["mlp.experts.down_proj"], dense))
    return out


def shared_experts(hf, w, n):
    """The shared experts' outputs combined: their mean (``average``)."""
    def one(acc, at):
        return acc + gated_mlp(n, *(a.astype(jnp.float32) for a in at)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n), tuple(
        w[f"mlp.shared_experts.{m}_proj"] for m in ("gate", "up", "down")))
    if hf.get("shared_expert_combination_strategy", "average") == "average":
        out = out / _S(hf)
    return out


def mixture(hf, w, n):
    ids, wts = route(hf, w, n)
    out = routed_experts(hf, w, n, ids, wts)
    return out + shared_experts(hf, w, n) if _S(hf) else out


def embed(hf, g, ids):
    return Stream(g["embed_tokens"][ids].astype(jnp.float32), jnp.int32(0))


def layer(hf, w, x):
    n = layer_norm(x.h, w["input_layernorm.weight"].astype(jnp.float32),
                   _EPS(hf))
    sliding = jnp.asarray([k == SLIDING for k in layer_kinds(hf)])[x.layer]
    return Stream(x.h + attention(hf, w, n, sliding) + mixture(hf, w, n),
                  x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    n = layer_norm(x, g["norm.weight"], _EPS(hf))
    return hf.get("logit_scale", 1.0) * mm(n, g["embed_tokens"].T)
