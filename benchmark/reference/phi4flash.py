"""Plain reference for ``model_type: phi4flash`` (Phi-4-mini-flash-reasoning;
SambaY: Ren et al. 2025, "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation").  float32, ``HIGHEST`` precision, no cache,
no kernels; the scan runs position by position and attention in query blocks
of 256, one after the other.  Tensors in kernel form (``[in, out]``), see seeded_weights.py.

The layers (n of them, d hidden, H query / KV key-value heads of size hd,
window W; Mamba-1 with inner width d_i, state N, conv K, step rank R):

  every layer l   x += Mixer_l(LN(x)); x += down(silu(g) * u), [g|u] = LN'(x) W_gu
  l <= n/2, l % mb_per_layer == 0    Mamba-1:  [xs|z] = a W_in;
        xs = silu(conv_K(xs) + b_c) (causal, depthwise); [r|B|C] = xs W_x;
        delta = softplus(r W_dt + b_dt); A = -exp(A_log);
        h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * xs_t) (x) B_t;
        y_t = h_t . C_t + D * xs_t; out = (y * silu(z)) W_out.
        The LAST of them (l = n/2) exports m = y.
  l < n/2, the other layers          attention over positions t-W+1 .. t
  l = n/2 + 1                        attention over all positions <= t; ITS
                                     keys and values are what the
                                     cross-attention layers read
  l > n/2 + 1, l % mb_per_layer == 0 gated memory unit:
                                     out = (m * silu(a W_1)) W_2
  l > n/2 + 1, the other layers      cross-attention: q = a W_q + b_q only;
                                     K and V are layer n/2 + 1's
  after the last: LayerNorm, logits = h E' (E the token embedding, tied).

All attention is DIFFERENTIAL: heads pair up in order — (q1, q2) of query
pair p are heads 2p, 2p + 1, (k1, k2) and (v1, v2) of K/V pair j are heads
2j, 2j + 1, query pair p reads K/V pair p // (H / KV);
A1 = softmax(q1 k1' / sqrt(hd)), A2 = softmax(q2 k2' / sqrt(hd)), the same
mask; lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0, lam0 = 0.8 - 0.6
exp(-0.3 l); o = RMSNorm_{2hd}((A1 - lam A2) [v1|v2]) (1 - lam0) (a gain per
channel, eps 1e-5); the pairs' outputs concatenate.  No positional encoding
anywhere.

Departures from the published model, each because the catalog's ``config``
does not give it and there is no network here (the configuration's file lists
them under ``assumed``): the Mamba sizes (N 16, K 4, expand 2, R = ceil(d/16)),
which projections carry a bias, the pairing order of the heads, and that the
reference shares one file with nothing: the program's ``layer_kind`` is not
imported, the pattern is written out again below.

What the harness asks of this module, and how a model whose layers differ
meets it with no edit there (PERF.md section 7 lists the four limits):
``check.reference_logits`` calls ``layer(hf, w, x)`` with no layer index, one
``LAYER`` table, and only ever indexes what the layers pass along.  So
``embed`` returns a :class:`Stream` — the hidden states, layer n/2's ``m``,
layer n/2 + 1's K and V, and a traced layer counter — whose ``__getitem__``
indexes the hidden states; ``LAYER`` is the UNION of the four kinds' tensors
(a layer uses its own and the MLP's); and ``layer`` picks the kind by
``lax.switch`` on the counter.  ``seeded_weights`` draws normal tensors of
std 0.02; :func:`published_init` maps those of them that Mamba and the
differential attention initialise otherwise onto that initialisation, for the
program's tree and for ``layer`` alike — with N(0, 0.02) everywhere the scan
state forgets within ~10 tokens and a state dropped at a chunk boundary
would pass ``correct``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import HI, f32, layer_norm, mm

QUERY_BLOCK = 256
MAMBA, WINDOW, FULL, GMU, CROSS = range(5)

_E = lambda hf: hf["hidden_size"]
_I = lambda hf: hf["intermediate_size"]
_H = lambda hf: hf["num_attention_heads"]
_KV = lambda hf: hf["num_key_value_heads"]
_HD = lambda hf: _E(hf) // _H(hf)
_DI = lambda hf: hf.get("mamba_expand", 2) * _E(hf)
_N = lambda hf: hf.get("mamba_d_state", 16)
_K = lambda hf: hf.get("mamba_d_conv", 4)
_R = lambda hf: hf.get("mamba_dt_rank") or -(-_E(hf) // 16)


def num_layers(hf):
    return hf["num_hidden_layers"]


def attention_shape(hf):
    """``(query heads, key/value heads, head size)``."""
    return _H(hf), _KV(hf), _HD(hf)


def layer_kinds(hf):
    """What each layer is (the pattern of the docstring)."""
    n, every = num_layers(hf), hf.get("mb_per_layer", 2)
    half = n // 2
    kinds = []
    for l in range(n):
        if l <= half:
            kinds.append(MAMBA if l % every == 0 else WINDOW)
        elif l == half + 1:
            kinds.append(FULL)
        else:
            kinds.append(GMU if l % every == 0 else CROSS)
    return kinds


GLOBAL = [
    ("embed_tokens", lambda hf: (hf["vocab_size"], _E(hf)), "matrix"),
    ("final_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("final_layernorm.bias", lambda hf: (_E(hf),), "bias"),
]
# the union of the four kinds' tensors; a layer reads its kind's and the
# MLP's.  The attention tensors serve the window, full and cross layers (a
# cross layer reads q and out alone).  ``A_log``, ``D``, the conv and the
# lambdas are no matrices of a GEMM: kind ``bias`` / ``gain`` gives them the
# same draw and keeps them out of headroom.py's parameter count.
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("input_layernorm.bias", lambda hf: (_E(hf),), "bias"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("post_attention_layernorm.bias", lambda hf: (_E(hf),), "bias"),
    ("mlp.gate_up_proj", lambda hf: (_E(hf), 2 * _I(hf)), "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
    ("mixer.in_proj", lambda hf: (_E(hf), 2 * _DI(hf)), "matrix"),
    ("mixer.conv1d.weight", lambda hf: (_K(hf), _DI(hf)), "bias"),
    ("mixer.conv1d.bias", lambda hf: (_DI(hf),), "bias"),
    ("mixer.x_proj", lambda hf: (_DI(hf), _R(hf) + 2 * _N(hf)), "matrix"),
    ("mixer.dt_proj", lambda hf: (_R(hf), _DI(hf)), "matrix"),
    ("mixer.dt_proj.bias", lambda hf: (_DI(hf),), "bias"),
    ("mixer.A_log", lambda hf: (_DI(hf), _N(hf)), "bias"),
    ("mixer.D", lambda hf: (_DI(hf),), "gain"),
    ("mixer.out_proj", lambda hf: (_DI(hf), _E(hf)), "matrix"),
    ("attn.q_proj", lambda hf: (_E(hf), _H(hf) * _HD(hf)), "matrix"),
    ("attn.k_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("attn.v_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("attn.q_proj.bias", lambda hf: (_H(hf) * _HD(hf),), "bias"),
    ("attn.k_proj.bias", lambda hf: (_KV(hf), _HD(hf)), "key_bias"),
    ("attn.v_proj.bias", lambda hf: (_KV(hf) * _HD(hf),), "bias"),
    ("attn.out_proj", lambda hf: (_H(hf) * _HD(hf), _E(hf)), "matrix"),
    ("attn.out_proj.bias", lambda hf: (_E(hf),), "bias"),
    ("attn.subln.weight", lambda hf: (2 * _HD(hf),), "gain"),
    ("attn.lambda_q1", lambda hf: (_HD(hf),), "bias"),
    ("attn.lambda_k1", lambda hf: (_HD(hf),), "bias"),
    ("attn.lambda_q2", lambda hf: (_HD(hf),), "bias"),
    ("attn.lambda_k2", lambda hf: (_HD(hf),), "bias"),
    ("gmu.in_proj", lambda hf: (_E(hf), _DI(hf)), "matrix"),
    ("gmu.out_proj", lambda hf: (_DI(hf), _E(hf)), "matrix"),
]

DT_MIN, DT_MAX = 1e-3, 1e-1   # Mamba's dt_min, dt_max
LAMBDA_STD = 0.1              # the differential attention's lambda vectors
BC_GAIN = 4.0                 # see published_init


def published_init(hf, w):
    """The drawn tensors (normal, std ``init``) that the published model
    initialises otherwise, mapped onto that initialisation — the ONE place,
    for ``program_tree`` and ``layer`` alike:

      conv1d.weight  torch's default for a depthwise conv of K taps,
                     U(+-1/sqrt(K)): the draw rescaled to its std 1/sqrt(3K)
      A_log          log(1 .. N) per channel, + the draw
      dt_proj.bias   softplus^-1(delta0) + the draw, delta0 spread
                     log-uniformly over the channels in [dt_min, dt_max]
                     (Mamba draws it so at random; here by channel index)
      D              ones (the ``gain`` draw: 1 + 0.1 n)
      lambda_*       N(0, 0.1): the draw rescaled
      x_proj         its B and C columns times ``BC_GAIN``.  NOT Mamba's
                     initialisation, and on purpose: at any random
                     initialisation the state's term ``h . C`` is a few
                     percent of the skip ``D * xs`` beside it (trained
                     models are not like that), and a comparison of logits
                     in bf16 would not see a state that was dropped.  With
                     B and C four times larger the state carries a third to
                     as much as the skip does (measured: PERF.md section 6).
    ``A_log``, ``dt_proj.bias``, ``D`` and the lambdas come out float32, as
    the program holds them; the conv weight in the type it was drawn in.
    """
    std = float(hf.get("init_std", hf.get("initializer_range", 0.02)))
    up = lambda a: a.astype(jnp.float32)
    d_i, n, k = _DI(hf), _N(hf), _K(hf)
    delta0 = jnp.exp(jnp.linspace(math.log(DT_MIN), math.log(DT_MAX), d_i))
    conv = w["mixer.conv1d.weight"]
    x_proj = w["mixer.x_proj"]
    out = {
        "mixer.x_proj": x_proj.at[:, _R(hf):].multiply(
            jnp.asarray(BC_GAIN, x_proj.dtype)),
        "mixer.conv1d.weight":
            (up(conv) * (1.0 / math.sqrt(3 * k) / std)).astype(conv.dtype),
        "mixer.A_log": jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None]
                       + up(w["mixer.A_log"]),
        "mixer.dt_proj.bias": jnp.log(jnp.expm1(delta0))
                              + up(w["mixer.dt_proj.bias"]),
        "mixer.D": up(w["mixer.D"]),
    }
    for name in ("q1", "k1", "q2", "k2"):
        out[f"attn.lambda_{name}"] = up(w[f"attn.lambda_{name}"]) \
            * (LAMBDA_STD / std)
    return out


def program_tree(hf, g, layers):
    """The serve graph's parameter tree.  The fused published projections are
    cut where the graph has two nodes (``in_proj`` -> x | z, ``gate_up_proj``
    -> gate | up); q, k, v are laid out per K/V pair — its query heads, then
    ``[k1|k2]``, then ``[v1|v2]`` — which, with heads paired in order, is a
    reshape of each."""
    e, i_, d_i = _E(hf), _I(hf), _DI(hf)
    pairs, hd = _KV(hf) // 2, _HD(hf)
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.final_layernorm": {"gamma": g["final_layernorm.weight"],
                                  "beta": g["final_layernorm.bias"]},
        "lm_head": {"kernel": g["embed_tokens"].T},
    }
    for l, (kind, w) in enumerate(zip(layer_kinds(hf), layers)):
        p = f"model.layers.{l}"
        init = published_init(hf, w)
        for norm in ("input_layernorm", "post_attention_layernorm"):
            tree[f"{p}.{norm}"] = {"gamma": w[f"{norm}.weight"],
                                   "beta": w[f"{norm}.bias"]}
        tree[f"{p}.mlp.gate_proj"] = {"kernel": w["mlp.gate_up_proj"][:, :i_]}
        tree[f"{p}.mlp.up_proj"] = {"kernel": w["mlp.gate_up_proj"][:, i_:]}
        tree[f"{p}.mlp.down_proj"] = {"kernel": w["mlp.down_proj"]}
        if kind == MAMBA:
            m = f"{p}.mixer"
            tree[f"{m}.in_proj_x"] = {"kernel": w["mixer.in_proj"][:, :d_i]}
            tree[f"{m}.in_proj_z"] = {"kernel": w["mixer.in_proj"][:, d_i:]}
            tree[f"{m}.conv1d"] = {"weight": init["mixer.conv1d.weight"],
                                   "bias": w["mixer.conv1d.bias"]}
            tree[f"{m}.x_proj"] = {"kernel": init["mixer.x_proj"]}
            tree[f"{m}.dt_proj"] = {"kernel": w["mixer.dt_proj"]}
            tree[f"{m}.scan"] = {"A_log": init["mixer.A_log"],
                                 "D": init["mixer.D"],
                                 "dt_bias": init["mixer.dt_proj.bias"]}
            tree[f"{m}.out_proj"] = {"kernel": w["mixer.out_proj"]}
        elif kind == GMU:
            tree[f"{p}.mixer.in_proj"] = {"kernel": w["gmu.in_proj"]}
            tree[f"{p}.mixer.out_proj"] = {"kernel": w["gmu.out_proj"]}
        else:
            per_pair = lambda a: a.reshape(a.shape[:-1] + (pairs, -1)) \
                if a.ndim == 2 else a.reshape(pairs, -1)
            names = ("q",) if kind == CROSS else ("q", "k", "v")
            proj = "q" if kind == CROSS else "qkv"
            node = {
                proj: jnp.concatenate(
                    [per_pair(w[f"attn.{n}_proj"]) for n in names], axis=-1),
                f"{proj}_bias": jnp.concatenate(
                    [per_pair(w[f"attn.{n}_proj.bias"].reshape(-1))
                     for n in names], axis=-1),
                "o_proj": w["attn.out_proj"],
                "o_bias": w["attn.out_proj.bias"],
                "subln": w["attn.subln.weight"],
            }
            for name in ("q1", "k1", "q2", "k2"):
                node[f"lambda_{name}"] = init[f"attn.lambda_{name}"]
            tree[f"{p}.attn"] = node
    return tree


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Stream:
    """What the layers pass along: the hidden states ``h [B, T, d]``, the
    exported scan output ``m [B, T, d_i]``, the full-attention layer's keys
    and values ``k``, ``v`` ``[B, T, KV, hd]``, and the index of the layer
    that comes next.  Indexing it indexes the hidden states, which is all the
    harness does with it."""

    h: jax.Array
    m: jax.Array
    k: jax.Array
    v: jax.Array
    layer: jax.Array

    def __getitem__(self, idx):
        return self.h[idx]


def embed(hf, g, ids):
    b, t = ids.shape
    zeros = lambda *tail: jnp.zeros((b, t) + tail, jnp.float32)
    # rows first, float32 after: the table has 200 064 rows
    return Stream(g["embed_tokens"][ids].astype(jnp.float32), zeros(_DI(hf)),
                  zeros(_KV(hf), _HD(hf)), zeros(_KV(hf), _HD(hf)),
                  jnp.int32(0))


def diff_attention(q, k, v, lam, lam0, gain, window=0, eps=1e-5):
    """q ``[B, T, H, hd]``, k/v ``[B, T, KV, hd]``; ``window`` 0 = all
    positions <= t.  Queries go ``QUERY_BLOCK`` at a time, ONE BLOCK AFTER
    THE OTHER (``lax.map``: unrolled, the device kept several blocks' scores
    at once, 2.4 GB at 4 224 positions beside a deployment), each against
    the keys a block can see: all of them, or the ``QUERY_BLOCK + window - 1``
    that end with the block's last query."""
    b, t, h, hd = q.shape
    pairs = k.shape[2] // 2
    blocks = -(-t // QUERY_BLOCK)
    q = jnp.pad(q.reshape(b, t, pairs, h // 2 // pairs, 2, hd),
                ((0, 0), (0, blocks * QUERY_BLOCK - t)) + ((0, 0),) * 4)
    k = k.reshape(b, t, pairs, 2, hd)
    v = v.reshape(b, t, pairs, 2 * hd)
    span = min(t, QUERY_BLOCK + window - 1) if window else t
    cut = jax.lax.dynamic_slice_in_dim

    def one(lo):
        first = jnp.clip(lo + QUERY_BLOCK - span, 0, t - span)
        s = jnp.einsum("btpghd,bsphd->bpghts", cut(q, lo, QUERY_BLOCK, 1),
                       cut(k, first, span, 1),
                       precision=HI) / jnp.sqrt(jnp.float32(hd))
        # a padded query row sees what the last position sees
        at = jnp.minimum(lo + jnp.arange(QUERY_BLOCK), t - 1)
        back = at[:, None] - (first + jnp.arange(span))[None, :]
        mask = (back >= 0) & (back < window) if window else back >= 0
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = a[:, :, :, 0] - lam * a[:, :, :, 1]
        return jnp.einsum("bpgts,bspd->btpgd", a, cut(v, first, span, 1),
                          precision=HI)

    o = jax.lax.map(one, jnp.arange(blocks) * QUERY_BLOCK)
    o = jnp.moveaxis(o, 0, 1).reshape((b, blocks * QUERY_BLOCK)
                                      + o.shape[3:])[:, :t]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return (o * gain * (1.0 - lam0)).reshape(b, t, h * hd)


def mamba(hf, w, init, a):
    """The Mamba-1 mixer on ``a [B, T, d]``: ``(out, y)``."""
    d_i, n, k, r = _DI(hf), _N(hf), _K(hf), _R(hf)
    t = a.shape[1]
    xz = mm(a, w["mixer.in_proj"])
    xs, z = xz[..., :d_i], xz[..., d_i:]
    padded = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
    conv = f32(init["mixer.conv1d.weight"])
    xs = jax.nn.silu(sum(padded[:, j:j + t] * conv[j] for j in range(k))
                     + w["mixer.conv1d.bias"])
    dbc = mm(xs, init["mixer.x_proj"])
    delta = jax.nn.softplus(mm(dbc[..., :r], w["mixer.dt_proj"])
                            + init["mixer.dt_proj.bias"])
    b_in, c_in = dbc[..., r:r + n], dbc[..., r + n:]
    a_neg = -jnp.exp(init["mixer.A_log"])

    def step(h, at):
        delta_t, x_t, b_t, c_t = at
        h = (jnp.exp(delta_t[..., None] * a_neg) * h
             + (delta_t * x_t)[..., None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    by_time = lambda x: jnp.moveaxis(x, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((a.shape[0], d_i, n), jnp.float32),
                        tuple(by_time(x) for x in (delta, xs, b_in, c_in)))
    y = by_time(y) + init["mixer.D"] * xs
    return mm(y * jax.nn.silu(z), w["mixer.out_proj"]), y


def layer(hf, w, x):
    init = f32(published_init(hf, w))
    w = f32(w)
    b, t, _ = x.h.shape
    h_, kv, hd = attention_shape(hf)
    eps = hf.get("layer_norm_eps", 1e-5)
    a = layer_norm(x.h, w["input_layernorm.weight"],
                   w["input_layernorm.bias"], eps)
    l = x.layer.astype(jnp.float32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(init["attn.lambda_q1"] * init["attn.lambda_k1"]))
           - jnp.exp(jnp.sum(init["attn.lambda_q2"] * init["attn.lambda_k2"]))
           + lam0)

    def project(n, heads):
        return (mm(a, w[f"attn.{n}_proj"])
                + w[f"attn.{n}_proj.bias"].reshape(-1)).reshape(b, t, heads,
                                                                hd)

    def attend(k, v, window):
        o = diff_attention(project("q", h_), k, v, lam, lam0,
                           w["attn.subln.weight"], window)
        return mm(o, w["attn.out_proj"]) + w["attn.out_proj.bias"]

    def as_mamba(_):
        out, y = mamba(hf, w, init, a)
        return out, y, x.k, x.v      # a later Mamba layer's y replaces m

    def as_window(_):
        return (attend(project("k", kv), project("v", kv),
                       hf["sliding_window"]), x.m, x.k, x.v)

    def as_full(_):
        k, v = project("k", kv), project("v", kv)
        return attend(k, v, 0), x.m, k, v

    def as_gmu(_):
        gate = jax.nn.silu(mm(a, w["gmu.in_proj"]))
        return mm(x.m * gate, w["gmu.out_proj"]), x.m, x.k, x.v

    def as_cross(_):
        return attend(x.k, x.v, 0), x.m, x.k, x.v

    kind = jnp.asarray(layer_kinds(hf), jnp.int32)[x.layer]
    out, m, k, v = jax.lax.switch(
        kind, (as_mamba, as_window, as_full, as_gmu, as_cross), None)
    h = x.h + out
    a = layer_norm(h, w["post_attention_layernorm.weight"],
                   w["post_attention_layernorm.bias"], eps)
    gu = mm(a, w["mlp.gate_up_proj"])
    i_ = _I(hf)
    h = h + mm(jax.nn.silu(gu[..., :i_]) * gu[..., i_:], w["mlp.down_proj"])
    return Stream(h, m, k, v, x.layer + 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Logits:
    """``head``'s result, one ``[T, vocab]`` array per sequence of the batch.
    The harness takes ``head(...)[0]`` of one sequence after another and
    keeps them all; at 200 064 columns a served request's rows are 1.5 GB,
    beside an 11.6 GB deployment.  Indexing this hands the rows to the HOST:
    the device's copy goes with this object, and the comparison brings one
    sequence's rows back at a time."""

    rows: tuple

    def __getitem__(self, i):
        return np.asarray(self.rows[i])


LANE = 128          # the last dimension's tile on the device
VOCAB_BLOCK = 512   # columns of logits made at a time, at most


def head(hf, g, x):
    """Logits over the tied embedding, a block of the table's rows at a time
    (a float32 copy of the whole table is 2 GB).  The block is a whole number
    of ``LANE``s where the vocabulary has such a divisor (200 064 = 521 x
    384): the device then writes each block's columns in place; with any
    other width it builds a second copy of the logits (AOT for a v5e,
    1 920 rows: 1.54 GB of temporaries against none)."""
    x = layer_norm(x, g["final_layernorm.weight"].astype(jnp.float32),
                   g["final_layernorm.bias"].astype(jnp.float32),
                   hf.get("layer_norm_eps", 1e-5))
    table = g["embed_tokens"]
    v, d = table.shape
    block = max((n for n in range(LANE, VOCAB_BLOCK + 1, LANE) if v % n == 0),
                default=v)
    blocks = table.reshape(v // block, block, d)

    def rows_of(seq):
        def put(out, at):
            part = mm(seq, blocks[at].astype(jnp.float32).T)
            return jax.lax.dynamic_update_slice(out, part, (0, at * block)), 0

        out, _ = jax.lax.scan(put, jnp.zeros((seq.shape[0], v), jnp.float32),
                              jnp.arange(v // block))
        return out

    return Logits(tuple(rows_of(seq) for seq in x))
