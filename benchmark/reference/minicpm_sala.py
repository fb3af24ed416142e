"""Plain reference for ``model_type: minicpm_sala`` (MiniCPM-SALA 9B: sparse
InfLLM-v2 attention — MiniCPM4 report, arXiv:2506.07900 — in the layers
``mixer_types`` calls ``minicpm4``, Lightning linear attention in those it
calls ``lightning-attn``).  float32, ``HIGHEST`` precision; NO state, NO cache,
NO block list, NO kernel: the linear attention is the literal sum over earlier
positions, the sparse attention a dense softmax under a mask per (query, K/V
group, block) built from the scores.  Queries go ``QUERY_BLOCK`` at a time,
one block after the other, so that 14k positions fit beside a deployment.
Tensors in kernel form (``[in, out]``), see seeded_weights.py.

With ``a = scale_depth / sqrt(mup_denominator)`` (the PUBLISHED depth 32,
whatever depth is run), ``n_t = RMSNorm(x_t)``, ``s = 1 / sqrt(hd)``:

  every layer   x' = x + a Mixer(n);  x'' = x' + a Wd(silu(Wg m) * Wu m),
                m = RMSNorm(x')
  embedding     x = scale_emb E[token];
  logits        (RMSNorm(x_T) / (hidden_size / dim_model_base)) W_head

  lightning-attn (H heads of hd; slope_h = 2 ** (-8 (h + 1) / H)):
     q_t = RoPE_t(RMSNorm_hd(n_t Wq)), k_t = RoPE_t(RMSNorm_hd(n_t Wk)),
     v_t = n_t Wv
     o_t = s sum_{j <= t} exp(-slope_h (t - j)) (q_t . k_j) v_j
     Mixer = (RMSNorm_hd(o_t) * sigmoid(n_t Wz)) Wo

  minicpm4 (QH query heads in KV groups of QH / KV on one K/V head each; no
  RoPE; kernel K, stride S, block B, top-k, window W, init blocks, dense_len):
     q_t = n_t Wq, k_t = n_t Wk, v_t = n_t Wv
     compressed key c = mean of k_j over [S c, S c + K); exists at t once
                        t >= S c + K - 1
     per head   p_{t,c} = softmax over the compressed keys that exist at t
                          of s q_t . kbar_c
     per group  r_{t,c} = sum over the group's heads of p_{t,c}
                score_{t,b} = max of r_{t,c} over the kernels c that overlap
                block b = positions [B b, B b + B)
     attended   A_g(t) = {blocks < init} + {the W / B newest blocks up to
                t // B} + {the top-k highest-scoring of the other blocks},
                per group; every block when t < dense_len
     o_t = softmax over {j <= t, j // B in A_g(t)} of s q_t . k_j, times v_j
     Mixer = (o_t * sigmoid(n_t Wz)) Wo

What the catalog's ``config`` does not print, and is therefore ASSUMED (the
configuration's file lists each): the seven sparse sizes; the selection's
form; ``dense_len`` read per query position; QK-norm (a gain per head
channel), the output norm (per head, gain ``[H hd]``) and gate on lightning
layers, the gate alone on sparse layers; the decay slopes, the same in every
lightning layer; RoPE in the half-split layout.  A departure found later is
a one-line change here and in ``serve/hybrid_ops.py`` alike.

``seeded_weights`` draws every matrix normal at std 0.02 (no
``initializer_range`` in the catalog's config).  :func:`published_init` maps
one kind elsewhere, for the program's tree and for ``layer`` alike: the
sparse layers' QUERY and KEY projections are scaled by ``SPARSE_QK_GAIN``
each.  Why: at 0.02 a key channel has std 1.28 and ``s q . k`` spreads by
1.6 over positions, the indexer's logits (against a mean of 32 keys) by 0.3:
every block's score is within a few percent of every other's, the attention
is an average of thousands of values, and a program that read the wrong
blocks, or the newest ones, would move the logits by less than bf16 does.
With the gain ``s q . k`` spreads by ~SPARSE_QK_GAIN^2 x 1.6 and the
attention rests on tens of keys, so WHICH blocks are read shows.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

from .common import HI, f32, mm

QUERY_BLOCK = 256
SPARSE_QK_GAIN = 1.25
SPARSE, LINEAR = "minicpm4", "lightning-attn"

_E = lambda hf: hf["hidden_size"]
_I = lambda hf: hf["intermediate_size"]
_H = lambda hf: hf["num_attention_heads"]
_KV = lambda hf: hf["num_key_value_heads"]
_HD = lambda hf: hf.get("head_dim") or _E(hf) // _H(hf)
_LH = lambda hf: hf.get("lightning_nh") or _H(hf)
_LD = lambda hf: hf.get("lightning_head_dim") or _HD(hf)
_V = lambda hf: hf["vocab_size"]


def sparse_sizes(hf):
    """``(kernel, stride, block, topk, window, init blocks, dense_len)``:
    MiniCPM4's InfLLM-v2 convention where the configuration does not say."""
    return (hf.get("sparse_kernel_size", 32),
            hf.get("sparse_kernel_stride", 16),
            hf.get("sparse_block_size", 64), hf.get("sparse_topk", 64),
            hf.get("sparse_window_size", 2048),
            hf.get("sparse_init_blocks", 1),
            hf.get("sparse_dense_len", 8192))


def num_layers(hf):
    return hf["num_hidden_layers"]


def layer_kinds(hf):
    kinds = list(hf["mixer_types"])
    assert len(kinds) == num_layers(hf) and set(kinds) <= {SPARSE, LINEAR}
    return kinds


def attention_shape(hf):
    """``(query heads, key/value heads, head size)`` of the layers that keep
    a cache that grows with the context: the sparse ones."""
    return _H(hf), _KV(hf), _HD(hf)


GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
    ("lm_head", lambda hf: (_E(hf), _V(hf)), "matrix"),
]
# the union of the two kinds' tensors.  q, the gate z and o have one shape in
# both kinds (32 x 128 = 4096 columns) and are one row each; k and v differ
# (2 heads against 32) and have a row per kind, so headroom.py's parameter
# count is a lightning layer's plus the sparse k and v (2 M of 287 M).
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("mlp.gate_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.up_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
    ("self_attn.q_proj", lambda hf: (_E(hf), _H(hf) * _HD(hf)), "matrix"),
    ("self_attn.z_proj", lambda hf: (_E(hf), _H(hf) * _HD(hf)), "matrix"),
    ("self_attn.o_proj", lambda hf: (_H(hf) * _HD(hf), _E(hf)), "matrix"),
    ("self_attn.k_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("self_attn.v_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("self_attn.lightning_k_proj", lambda hf: (_E(hf), _LH(hf) * _LD(hf)),
     "matrix"),
    ("self_attn.lightning_v_proj", lambda hf: (_E(hf), _LH(hf) * _LD(hf)),
     "matrix"),
    ("self_attn.q_norm.weight", lambda hf: (_LD(hf),), "gain"),
    ("self_attn.k_norm.weight", lambda hf: (_LD(hf),), "gain"),
    ("self_attn.o_norm.weight", lambda hf: (_LH(hf) * _LD(hf),), "gain"),
]


def published_init(hf, w):
    """The drawn tensors of a layer that are rescaled (the module docstring
    says why), in the type they were drawn in — the ONE place, for
    ``program_tree`` and the reference's own ``layer`` alike."""
    gain = lambda a: (a.astype(jnp.float32) * SPARSE_QK_GAIN).astype(a.dtype)
    return {"self_attn.sparse_q_proj": gain(w["self_attn.q_proj"]),
            "self_attn.sparse_k_proj": gain(w["self_attn.k_proj"])}


def program_tree(hf, g, layers):
    """The serve graph's parameter tree: a mixer's projections side by side
    in one matrix, ``q | k | v | gate``."""
    assert _LH(hf) * _LD(hf) == _H(hf) * _HD(hf), \
        "q, the gate and o are one table row for both kinds of layer"
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.norm": {"gamma": g["norm.weight"]},
        "lm_head": {"kernel": g["lm_head"]},
    }
    for i, (kind, w) in enumerate(zip(layer_kinds(hf), layers)):
        p = f"model.layers.{i}"
        for norm in ("input_layernorm", "post_attention_layernorm"):
            tree[f"{p}.{norm}"] = {"gamma": w[f"{norm}.weight"]}
        for n in ("gate_proj", "up_proj", "down_proj"):
            tree[f"{p}.mlp.{n}"] = {"kernel": w[f"mlp.{n}"]}
        a = "self_attn."
        if kind == SPARSE:
            init = published_init(hf, w)
            tree[f"{p}.self_attn"] = {
                "qkv": jnp.concatenate(
                    [init[a + "sparse_q_proj"], init[a + "sparse_k_proj"],
                     w[a + "v_proj"], w[a + "z_proj"]], axis=1),
                "o_proj": w[a + "o_proj"]}
        else:
            tree[f"{p}.self_attn"] = {
                "qkv": jnp.concatenate(
                    [w[a + "q_proj"], w[a + "lightning_k_proj"],
                     w[a + "lightning_v_proj"], w[a + "z_proj"]], axis=1),
                "o_proj": w[a + "o_proj"],
                "q_norm": w[a + "q_norm.weight"],
                "k_norm": w[a + "k_norm.weight"],
                "o_norm": w[a + "o_norm.weight"]}
    return tree


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x ``[B, T, H, hd]`` at positions 0 .. T - 1, half-split layout."""
    t, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _query_blocks(t):
    pad = -t % QUERY_BLOCK
    return pad, jnp.arange(0, t + pad, QUERY_BLOCK)


def lightning_attention(q, k, v):
    """``o_t = s sum_{j <= t} lambda_h^(t - j) (q_t . k_j) v_j`` as written:
    q, k, v ``[B, T, H, hd]`` to ``[B, T, H, hd]``."""
    b, t, h, hd = q.shape
    pad, starts = _query_blocks(t)
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    slope = jnp.exp2(-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    key_at = jnp.arange(t)

    def block(lo):
        at = lo + jnp.arange(QUERY_BLOCK)
        back = (at[:, None] - key_at[None, :]).astype(jnp.float32)
        decay = jnp.where(back >= 0, jnp.exp(
            -slope[:, None, None] * jnp.maximum(back, 0.0)), 0.0)
        qb = jax.lax.dynamic_slice_in_dim(q, lo, QUERY_BLOCK, axis=1)
        a = jnp.einsum("bthd,bshd->bhts", qb, k, precision=HI) * decay[None]
        return jnp.einsum("bhts,bshd->bthd", a, v, precision=HI)

    out = jax.lax.map(block, starts)
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, hd)[:, :t]
    return out / jnp.sqrt(jnp.float32(hd))


def compressed_keys(k, sizes):
    """``[B, NC, KV, hd]``: the mean of every whole kernel of keys."""
    kernel, stride = sizes[:2]
    nc = max((k.shape[1] - kernel) // stride + 1, 0)
    return jnp.stack([k[:, stride * c:stride * c + kernel].mean(axis=1)
                      for c in range(nc)], axis=1) if nc else None


def attended_blocks(q, at, kbar, nb, sizes):
    """The selection: ``[B, Q, KV, nb]`` bool — which of the ``nb`` blocks
    each query attends, per K/V group — for queries ``q [B, Q, H, hd]`` at
    positions ``at [Q]`` against the compressed keys ``kbar``."""
    kernel, stride, size, topk, window, init, dense_len = sizes
    b, nq, h, hd = q.shape
    last = (at // size)[:, None]
    blocks = jnp.arange(nb)[None, :]
    have = blocks <= last                                   # [Q, nb]
    if kbar is None:
        return jnp.broadcast_to(have[None, :, None], (b, nq, 1, nb))
    nc, kv = kbar.shape[1:3]
    first = stride * jnp.arange(nc)
    exists = (first + kernel - 1)[None, :] <= at[:, None]   # [Q, NC]
    logit = jnp.einsum("btkgd,bckd->btkgc",
                       q.reshape(b, nq, kv, h // kv, hd), kbar,
                       precision=HI) / jnp.sqrt(jnp.float32(hd))
    seen = exists[None, :, None, None, :]
    p = jax.nn.softmax(jnp.where(seen, logit, -jnp.inf), axis=-1)
    p = jnp.where(seen & ~jnp.isnan(p), p, 0.0)  # a row may see no kernel
    r = jnp.where(exists[None, :, None, :], p.sum(axis=3), -1.0)
    # block b is overlapped by kernel c iff S c < B (b + 1) and S c + K > B b
    over = (first[None, :] < size * (jnp.arange(nb) + 1)[:, None]) \
        & ((first + kernel)[None, :] > size * jnp.arange(nb)[:, None])
    score = jnp.max(jnp.where(over[None, None, None], r[:, :, :, None, :],
                              -1.0), axis=-1)               # [B, Q, KV, nb]
    forced = have & ((blocks < init) | (blocks > last - window // size))
    free = have & ~forced
    top, ids = jax.lax.top_k(jnp.where(free[None, :, None], score, -2.0),
                             min(topk, nb))
    chosen = jnp.any((ids[..., None] == jnp.arange(nb))
                     & (top > -2.0)[..., None], axis=-2)
    sparse = forced[None, :, None] | chosen
    return jnp.where((at < dense_len)[None, :, None, None],
                     have[None, :, None], sparse)


def sparse_attention(q, k, v, sizes):
    """q ``[B, T, H, hd]``, k / v ``[B, T, KV, hd]`` to ``([B, T, H, hd],
    the selection [B, T, KV, blocks])``: one softmax over the exact keys of
    the attended blocks."""
    b, t, h, hd = q.shape
    kv, size = k.shape[2], sizes[2]
    pad, starts = _query_blocks(t)
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kbar = compressed_keys(k, sizes)
    nb = -(-t // size)
    key_at = jnp.arange(t)

    def block(lo):
        qb = jax.lax.dynamic_slice_in_dim(qp, lo, QUERY_BLOCK, axis=1)
        at = lo + jnp.arange(QUERY_BLOCK)
        sel = attended_blocks(qb, at, kbar, nb, sizes)    # [B, Q, KV, nb]
        sel = jnp.broadcast_to(sel, (b, QUERY_BLOCK, kv, nb))
        seen = sel[..., key_at // size] \
            & (key_at[None, :] <= at[:, None])[None, :, None]
        sc = jnp.einsum("btkgd,bskd->btkgs",
                        qb.reshape(b, QUERY_BLOCK, kv, h // kv, hd), k,
                        precision=HI) / jnp.sqrt(jnp.float32(hd))
        w = jax.nn.softmax(jnp.where(seen[:, :, :, None], sc, -jnp.inf),
                           axis=-1)
        w = jnp.where(jnp.isnan(w), 0.0, w)       # the padded queries
        return (jnp.einsum("btkgs,bskd->btkgd", w, v, precision=HI)
                .reshape(b, QUERY_BLOCK, h, hd), sel)

    out, sel = jax.lax.map(block, starts)
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, h, hd)[:, :t]
    sel = jnp.moveaxis(sel, 0, 1).reshape(b, t + pad, kv, nb)[:, :t]
    return out, sel


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Stream:
    """What the layers pass along: the hidden states ``h [B, T, d]`` and the
    index of the layer that comes next (``check.reference_logits`` calls
    ``layer`` with no index).  Indexing it indexes the hidden states, which
    is all the harness does with it."""

    h: jax.Array
    layer: jax.Array

    def __getitem__(self, idx):
        return self.h[idx]


def embed(hf, g, ids):
    x = g["embed_tokens"][ids].astype(jnp.float32)
    return Stream(x * hf.get("scale_emb", 1.0), jnp.int32(0))


def mixer(hf, w, n, kind):
    """One mixer on the normed rows ``n [B, T, d]``: ``(output, the sparse
    selection or None)``."""
    w = f32(w)
    b, t, _ = n.shape
    a = "self_attn."
    gate = jax.nn.sigmoid(mm(n, w[a + "z_proj"]))
    if kind == SPARSE:
        init = f32(published_init(hf, w))
        h, kv, hd = attention_shape(hf)
        q = mm(n, init[a + "sparse_q_proj"]).reshape(b, t, h, hd)
        k = mm(n, init[a + "sparse_k_proj"]).reshape(b, t, kv, hd)
        v = mm(n, w[a + "v_proj"]).reshape(b, t, kv, hd)
        o, sel = sparse_attention(q, k, v, sparse_sizes(hf))
        if not hf.get("attn_use_output_gate", True):
            gate = 1.0
        return mm(o.reshape(b, t, h * hd) * gate, w[a + "o_proj"]), sel
    h, hd, eps = _LH(hf), _LD(hf), hf["rms_norm_eps"]
    q, k, v = (mm(n, w[a + name]).reshape(b, t, h, hd) for name in
               ("q_proj", "lightning_k_proj", "lightning_v_proj"))
    if hf.get("qk_norm", True):
        q = rms_norm(q, w[a + "q_norm.weight"], eps)
        k = rms_norm(k, w[a + "k_norm.weight"], eps)
    if hf.get("lightning_use_rope", True):
        q, k = rope(q, hf["rope_theta"]), rope(k, hf["rope_theta"])
    o = lightning_attention(q, k, v)
    if hf.get("use_output_norm", True):
        o = rms_norm(o, w[a + "o_norm.weight"].reshape(h, hd), eps)
    if not hf.get("use_output_gate", True):
        gate = 1.0
    return mm(o.reshape(b, t, h * hd) * gate, w[a + "o_proj"]), None


def residual_scale(hf):
    return hf.get("scale_depth", 1.0) / math.sqrt(
        hf.get("mup_denominator") or num_layers(hf))


def layer(hf, w, x):
    eps, a = hf["rms_norm_eps"], residual_scale(hf)
    n = rms_norm(x.h, f32(w)["input_layernorm.weight"], eps)
    kinds = jnp.asarray([k == SPARSE for k in layer_kinds(hf)], jnp.int32)
    out = jax.lax.switch(
        kinds[x.layer], (lambda: mixer(hf, w, n, LINEAR)[0],
                         lambda: mixer(hf, w, n, SPARSE)[0]))
    h = x.h + a * out
    w = f32(w)
    m = rms_norm(h, w["post_attention_layernorm.weight"], eps)
    h = h + a * mm(jax.nn.silu(mm(m, w["mlp.gate_proj"]))
                   * mm(m, w["mlp.up_proj"]), w["mlp.down_proj"])
    return Stream(h, x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    x = rms_norm(x, g["norm.weight"], hf["rms_norm_eps"])
    return mm(x / (_E(hf) / hf.get("dim_model_base", _E(hf))), g["lm_head"])
