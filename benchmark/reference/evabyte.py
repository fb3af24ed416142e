"""Plain reference for ``model_type: evabyte`` (EvaByte 6.5B; EVA attention:
Zheng et al., "Efficient Attention via Control Variates", ICLR 2023, as
EvaByte's release runs it).  float32, ``HIGHEST`` precision, the two-set
softmax written out with masks over ALL positions — no compact cache, no
kernel, no batching: it shares nothing with the program's trick of storing
the attended set as a contiguous prefix.  Tensors in kernel form
(``[in, out]``), see seeded_weights.py.

The layer (d hidden, H heads of size hd, window W, chunk C, per head learned
``phi, mu`` of size hd, s = 1 / sqrt(hd)):

  h_t = RMSNorm_{1+g}(x_t);  q, k = RoPE_t(h_t Wq), RoPE_t(h_t Wk);  v = h_t Wv
  chunk c = positions [C c, C c + C):   a_j = softmax_{j in c}(phi . k_j)
        kbar_c = sum_j a_j k_j + mu        vbar_c = sum_j a_j v_j
  query t, w = t // W:   LOCAL = {j : W w <= j <= t}
                         REMOTE = {c : C (c + 1) <= W w}
        o_t = softmax over LOCAL and REMOTE together of
              (s q_t . k_j | s q_t . kbar_c), times (v_j | vbar_c)
  x' = x + o Wo;   x'' = x' + Wd(silu(Wg n) * Wu n),  n = RMSNorm_{1+g}(x')
  logits = RMSNorm_{1+g}(x_T) W_head[:, :V]      (head 0 of num_pred_heads)

What the catalog's ``config`` does not print, and is therefore ASSUMED (the
configuration's file lists each): windows that do not overlap; the summary's
form above — per-head ``phi`` weighting a chunk's keys by a softmax, ``mu``
added to the pooled key, nothing added to the pooled value; summaries built
from ROTATED keys; the same scale ``s`` for both sets; RoPE in the half-split
("rotate_half") layout; the prediction heads laid side by side in ``lm_head``
(head p = columns ``[p V, (p + 1) V)``).  A departure found later is a
one-line change here and in ``serve/hybrid_ops.py`` ``EvaAttention`` alike.

``seeded_weights`` draws normal tensors of std ``init_std`` (0.01275).
:func:`published_init` maps three kinds of them elsewhere, for the program's
tree and for ``layer`` alike, so that a program that dropped one would fail:

  norm gains  stored as the distance from one (``norm_add_unit_offset``):
              rescaled to std ``GAIN_STD`` 0.1 (factor 7.84), the spread the
              other configurations' gains have; at 0.01275 a program that
              ignored the gain would be 1 % off
  phi         rescaled to std ``PHI_STD`` 0.18 (factor 14.1): a key channel
              has std sqrt(4096) x 0.01275 = 0.82, so phi . k spreads by
              0.82 x 0.18 x sqrt(128) = 1.7 within a chunk and the softmax
              over its 16 keys is far from uniform (at 0.01275 it spreads
              by 0.12: uniform weights would pass)
  mu          rescaled to std ``MU_STD`` 0.8 (factor 62.7), a key channel's
              own: s q . mu then spreads by 0.59 over queries, beside 0.67
              for the scores s q . k themselves (at 0.01275: 0.009,
              invisible)
"""

import math

import jax
import jax.numpy as jnp

from .common import HI, f32, mm

QUERY_BLOCK = 512
GAIN_STD, PHI_STD, MU_STD = 0.1, 0.18, 0.8

_E = lambda hf: hf["hidden_size"]
_I = lambda hf: hf["intermediate_size"]
_H = lambda hf: hf["num_attention_heads"]
_HD = lambda hf: _E(hf) // _H(hf)
_V = lambda hf: hf["vocab_size"]


def num_layers(hf):
    return hf["num_hidden_layers"]


def attention_shape(hf):
    """``(query heads, key/value heads, head size)``."""
    return _H(hf), _H(hf), _HD(hf)


# gains, phi and mu are no matrices of a GEMM: kind ``bias`` gives them the
# plain normal draw and keeps them out of headroom.py's parameter count
GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "bias"),
    ("lm_head", lambda hf: (_E(hf), hf.get("num_pred_heads", 1) * _V(hf)),
     "matrix"),
]
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "bias"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "bias"),
    ("self_attn.q_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("self_attn.k_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("self_attn.v_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("self_attn.o_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("self_attn.adaptive_phi", lambda hf: (_H(hf), _HD(hf)), "bias"),
    ("self_attn.adaptive_mu_k", lambda hf: (_H(hf), _HD(hf)), "bias"),
    ("mlp.gate_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.up_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
]
_RESCALED = {"norm.weight": GAIN_STD, "input_layernorm.weight": GAIN_STD,
             "post_attention_layernorm.weight": GAIN_STD,
             "self_attn.adaptive_phi": PHI_STD,
             "self_attn.adaptive_mu_k": MU_STD}


def published_init(hf, w):
    """The drawn tensors of ``w`` (a layer's, or the global ones) that are
    rescaled (the module docstring says which and why), in the type they
    were drawn in — the ONE place, for ``program_tree`` and the reference's
    own ``layer`` / ``head`` alike."""
    std = float(hf.get("init_std", hf.get("initializer_range", 0.02)))
    return {name: (w[name].astype(jnp.float32) * (to / std)
                   ).astype(w[name].dtype)
            for name, to in _RESCALED.items() if name in w}


def program_tree(hf, g, layers):
    """The serve graph's parameter tree: q, k, v fused head-major
    ``[E, H, 3, hd]``; head 0 of the prediction heads alone."""
    e, h, hd = _E(hf), _H(hf), _HD(hf)
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.norm": {"gamma": published_init(hf, g)["norm.weight"]},
        "lm_head": {"kernel": g["lm_head"][:, :_V(hf)]},
    }
    for i, w in enumerate(layers):
        p = f"model.layers.{i}"
        init = published_init(hf, w)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            tree[f"{p}.{norm}"] = {"gamma": init[f"{norm}.weight"]}
        tree[f"{p}.self_attn"] = {
            "qkv": jnp.stack([w[f"self_attn.{n}_proj"].reshape(e, h, hd)
                              for n in "qkv"], axis=2),
            "o_proj": w["self_attn.o_proj"],
            "phi": init["self_attn.adaptive_phi"],
            "mu": init["self_attn.adaptive_mu_k"]}
        for n in ("gate_proj", "up_proj", "down_proj"):
            tree[f"{p}.mlp.{n}"] = {"kernel": w[f"mlp.{n}"]}
    return tree


def rms_norm(x, gain, eps):
    """``norm_add_unit_offset``: the stored gain is its distance from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def rope(x, theta):
    """x ``[B, T, H, hd]`` at positions 0 .. T - 1, half-split layout."""
    t, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def chunk_summaries(k, v, phi, mu, chunk):
    """``k, v [B, T, H, hd]`` (T whole chunks) to one ``kbar, vbar
    [B, T / C, H, hd]`` per chunk."""
    b, t, h, hd = k.shape
    kc = k.reshape(b, t // chunk, chunk, h, hd)
    vc = v.reshape(kc.shape)
    a = jax.nn.softmax(jnp.einsum("hd,bnchd->bnch", phi, kc, precision=HI),
                       axis=2)
    return (jnp.einsum("bnch,bnchd->bnhd", a, kc, precision=HI) + mu,
            jnp.einsum("bnch,bnchd->bnhd", a, vc, precision=HI))


def eva_attention(q, k, v, phi, mu, window, chunk):
    """q, k (rotated) and v ``[B, T, H, hd]`` to ``[B, T, H hd]``: the
    docstring's two sets under one softmax, both written as masks over every
    position and every chunk.  Queries go ``QUERY_BLOCK`` at a time, one
    block after the other, so that the scores fit beside a deployment."""
    b, t, h, hd = q.shape
    unit = math.lcm(QUERY_BLOCK, chunk)
    pad = -t % unit
    q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
               for a in (q, k, v))
    kbar, vbar = chunk_summaries(k, v, phi, mu, chunk)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    key_at = jnp.arange(t + pad)
    chunk_end = chunk * (jnp.arange((t + pad) // chunk) + 1)

    def block(lo):
        at = lo + jnp.arange(QUERY_BLOCK)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, QUERY_BLOCK, axis=1)
        opened = window * (at // window)        # its window's first position
        local = (key_at[None] >= opened[:, None]) & (key_at[None]
                                                     <= at[:, None])
        remote = chunk_end[None] <= opened[:, None]
        s = jnp.concatenate([
            jnp.where(local[None, None], jnp.einsum(
                "bthd,bshd->bhts", qb, k, precision=HI) * scale, -jnp.inf),
            jnp.where(remote[None, None], jnp.einsum(
                "bthd,bnhd->bhtn", qb, kbar, precision=HI) * scale,
                -jnp.inf)], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        return (jnp.einsum("bhts,bshd->bthd", p[..., :t + pad], v,
                           precision=HI)
                + jnp.einsum("bhtn,bnhd->bthd", p[..., t + pad:], vbar,
                             precision=HI))

    out = jax.lax.map(block, jnp.arange(0, t + pad, QUERY_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h * hd)[:, :t]


def embed(hf, g, ids):
    return g["embed_tokens"][ids].astype(jnp.float32)


def layer(hf, w, x):
    init, w = f32(published_init(hf, w)), f32(w)
    b, t, e = x.shape
    h, hd, eps = _H(hf), _HD(hf), hf["rms_norm_eps"]
    a = rms_norm(x, init["input_layernorm.weight"], eps)
    q, k, v = (mm(a, w[f"self_attn.{n}_proj"]).reshape(b, t, h, hd)
               for n in "qkv")
    o = eva_attention(rope(q, hf["rope_theta"]), rope(k, hf["rope_theta"]),
                      v, init["self_attn.adaptive_phi"],
                      init["self_attn.adaptive_mu_k"], hf["window_size"],
                      hf["chunk_size"])
    x = x + mm(o, w["self_attn.o_proj"])
    a = rms_norm(x, init["post_attention_layernorm.weight"], eps)
    return x + mm(jax.nn.silu(mm(a, w["mlp.gate_proj"]))
                  * mm(a, w["mlp.up_proj"]), w["mlp.down_proj"])


def head(hf, g, x):
    gain = f32(published_init(hf, g))["norm.weight"]
    return mm(rms_norm(x, gain, hf["rms_norm_eps"]),
              f32(g)["lm_head"][:, :_V(hf)])
