"""Plain reference for ``model_type: nemotron_h`` (NVIDIA Nemotron-H /
Nemotron-3: Mamba-2 layers — Dao & Gu 2024, "Transformers are SSMs" —,
grouped-query attention layers and mixture-of-experts layers, ONE mixer a
block).  float32, ``HIGHEST`` precision; NO state, NO cache, NO sort, NO
grouped GEMM, NO kernel: Mamba-2 is the plain recurrence position by
position, the experts a loop over the held experts with a 0 / weight mask,
one expert upcast to float32 at a time.  Tensors in kernel form (``[in,
out]``), see seeded_weights.py.

Block i, by ``hybrid_override_pattern[i]`` (eps ``layer_norm_epsilon``):

  every block   x' = x + Mixer_i(n),  n = RMSNorm_i(x)
  logits        RMSNorm_f(x_T) W_head           (untied)

  M  Mamba-2 (H heads of P, G groups, state N, conv K; d_inner = H P,
     conv_dim = H P + 2 G N):
     [z | xBC | dt] = n W_in                      (d_inner | conv_dim | H)
     xBC_t = silu(sum_j w_c[j] xBC_{t-(K-1-j)} + b_c)   (causal, depthwise)
     [x | B | C] = xBC          x [H, P], B [G, N], C [G, N]; head h reads
                                group h // (H / G)
     delta_h = softplus(dt_h + dt_bias_h), A_h = -exp(A_log_h)
     S_h,t = exp(delta_h,t A_h) S_h,t-1 + delta_h,t x_h,t (x) B_g,t
     y_h,t = S_h,t C_g,t + D_h x_h,t
     Mixer = (RMSNorm_groups(y * silu(z)) * gamma) W_out   (the mean square
             over each of the G groups of d_inner / G channels)
  *  attention (QH query heads on KV key/value heads of hd, no positional
     term, no bias): causal softmax(q k' / sqrt(hd)) v, then W_o
  E  mixture of experts (R scored experts, top k, scaling c; this chip holds
     ``n_routed_experts`` of them, published ids from ``expert_share_index x
     n_routed_experts``):
     s = sigmoid(n W_g) in float32; chosen = the k largest of s + b (ties to
     the lower id); w = s[chosen] / (sum s[chosen] + 1e-20) * c
     Mixer = sum over chosen AND held e of w_e down_e(relu(up_e n)^2)
             + down_s(relu(up_s n)^2)              (the shared expert)
  -  dense MLP: down(relu(up n)^2)

What the catalog's ``config`` does not print, and is therefore ASSUMED (the
configuration's file lists each): d_inner = H P (``expand`` is inert);
the gated norm's grouping; no positional term in attention; the router's
bias ``b`` zero; float32 ``A_log``, ``D``, ``dt_bias`` and state;
``chunk_size`` a tile of the training kernel and no part of the result.

``seeded_weights`` draws every matrix normal at std 0.02 (the catalog's
config prints no ``initializer_range``).  :func:`published_init` maps those
that Mamba-2 initialises otherwise onto that initialisation, for the
program's tree and for ``layer`` alike: at 0.02 a conv of four taps would
shrink ``x``, ``B`` and ``C`` fifty-fold and the state would carry nothing.
It also takes the mean row out of every expert's down projection
(:func:`centered`): ``relu^2`` is never negative, so an uncentred draw adds
ONE direction to every row alike and the router then likes some experts
three times as much as others, whatever the row — a seeded draw has no
trained ``e_score_correction_bias`` to balance that.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

from .common import HI, causal_attention, f32, mm

MAMBA, ATTENTION, EXPERTS, MLP = "M", "*", "E", "-"
KINDS = (MAMBA, ATTENTION, EXPERTS, MLP)

_E = lambda hf: hf["hidden_size"]
_V = lambda hf: hf["vocab_size"]
_H = lambda hf: hf["num_attention_heads"]
_KV = lambda hf: hf["num_key_value_heads"]
_HD = lambda hf: hf.get("head_dim") or _E(hf) // _H(hf)
_MH = lambda hf: hf["mamba_num_heads"]
_MP = lambda hf: hf["mamba_head_dim"]
_G = lambda hf: hf["n_groups"]
_N = lambda hf: hf["ssm_state_size"]
_K = lambda hf: hf.get("conv_kernel", 4)
_DI = lambda hf: _MH(hf) * _MP(hf)
_CD = lambda hf: _DI(hf) + 2 * _G(hf) * _N(hf)
_HELD = lambda hf: hf["n_routed_experts"]
_SCORED = lambda hf: hf.get("router_num_experts") or _HELD(hf)
_F = lambda hf: hf["moe_intermediate_size"]
_FS = lambda hf: hf.get("n_shared_experts", 0) * hf.get(
    "moe_shared_expert_intermediate_size", 0)
_I = lambda hf: hf["intermediate_size"]
_EPS = lambda hf: hf.get("layer_norm_epsilon", 1e-5)


def num_layers(hf):
    return hf["num_hidden_layers"]


def layer_kinds(hf):
    kinds = list(hf["hybrid_override_pattern"])
    assert len(kinds) == num_layers(hf) and set(kinds) <= set(KINDS)
    return kinds


def attention_shape(hf):
    """``(query heads, key/value heads, head size)`` of the layers that keep
    a cache that grows with the context: the ``*`` ones."""
    return _H(hf), _KV(hf), _HD(hf)


def held_experts(hf):
    """``(first published id, count)`` of the experts this chip holds."""
    return hf.get("expert_share_index", 0) * _HELD(hf), _HELD(hf)


GLOBAL = [
    ("embeddings", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm_f.weight", lambda hf: (_E(hf),), "gain"),
    ("lm_head", lambda hf: (_E(hf), _V(hf)), "matrix"),
]
# the UNION of the kinds' tensors: a layer uses its own kind's and the norm
# (``seeded_weights`` draws by table row; what a layer does not use is never
# computed).  headroom.py's parameter count therefore overcounts this model
# (every layer all four kinds): PERF.md section 7.
LAYER = [
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
    ("mixer.in_proj", lambda hf: (_E(hf), _DI(hf) + _CD(hf) + _MH(hf)),
     "matrix"),
    ("mixer.conv1d.weight", lambda hf: (_K(hf), _CD(hf)), "matrix"),
    ("mixer.conv1d.bias", lambda hf: (_CD(hf),), "bias"),
    ("mixer.A_log", lambda hf: (_MH(hf),), "bias"),
    ("mixer.D", lambda hf: (_MH(hf),), "gain"),
    ("mixer.dt_bias", lambda hf: (_MH(hf),), "bias"),
    ("mixer.norm.weight", lambda hf: (_DI(hf),), "gain"),
    ("mixer.out_proj", lambda hf: (_DI(hf), _E(hf)), "matrix"),
    ("mixer.q_proj", lambda hf: (_E(hf), _H(hf) * _HD(hf)), "matrix"),
    ("mixer.k_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("mixer.v_proj", lambda hf: (_E(hf), _KV(hf) * _HD(hf)), "matrix"),
    ("mixer.o_proj", lambda hf: (_H(hf) * _HD(hf), _E(hf)), "matrix"),
    ("mixer.gate.weight", lambda hf: (_E(hf), _SCORED(hf)), "matrix"),
    ("mixer.experts.up_proj", lambda hf: (_HELD(hf), _E(hf), _F(hf)),
     "matrix"),
    ("mixer.experts.down_proj", lambda hf: (_HELD(hf), _F(hf), _E(hf)),
     "matrix"),
    ("mixer.shared_experts.up_proj", lambda hf: (_E(hf), _FS(hf)), "matrix"),
    ("mixer.shared_experts.down_proj", lambda hf: (_FS(hf), _E(hf)),
     "matrix"),
    ("mixer.up_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mixer.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
]
DT_STRIDE = 37  # odd: head h takes step (37 h) mod H of the spread


def centered(down):
    """A ``relu^2`` MLP's down projection ``[.., f, d]`` less the mean of its
    ``f`` rows, in the type it was drawn in.

    Why: ``h = relu(up n)^2 >= 0`` has the SAME positive mean in every
    hidden unit (``sigma^2 / 2``), so ``down' h`` holds ``sum_i down[i, :]``
    times that mean — one vector, added to every row of the batch alike,
    whatever the row is.  At the published widths the shared expert's
    output is the largest term of the stream, and that vector comes to ~10 %
    of the normed stream's power: every expert's score gets an offset of a
    third of its spread over rows, the most liked held expert takes 35-70 of
    256 rows where 12 is the mean, and the least liked is left out of a step
    (counted on the CPU at a quarter of the widths with ``init_std`` 0.04, so
    that ``std sqrt(d)`` is the published 1.04; the chip read 63.6 of 64 held
    experts visited a step and 37 rows on the fullest: PERF.md section 6,
    PR 48).  A trained router's ``e_score_correction_bias`` exists to balance
    the experts' load; a seeded draw has none, so the draw takes the common
    term out where it arises.  With it gone the same count reads 1 % of the
    power in common, 19-31 rows on the fullest expert and every held expert
    visited.  No equation of the layer changes."""
    a = down.astype(jnp.float32)
    return (a - jnp.mean(a, axis=-2, keepdims=True)).astype(down.dtype)


def published_init(hf, w):
    """The drawn tensors (normal, std ``init``) that Mamba-2 initialises
    otherwise, mapped onto that initialisation, and the experts' down
    projections :func:`centered` — the ONE place, for ``program_tree`` and
    ``layer`` alike (``layer`` centres the held experts one at a time, as it
    upcasts them):

      conv1d.weight  torch's default for a depthwise conv of K taps,
                     U(+-1/sqrt(K)): the draw rescaled to its std 1/sqrt(3K)
      A_log          log of A spread evenly over [1, 16] by head, + the draw
                     (Mamba-2 draws A uniform in [1, 16])
      dt_bias        softplus^-1(delta0) + the draw, delta0 spread
                     log-uniformly over [time_step_min, time_step_max],
                     floored at time_step_floor (Mamba-2 draws it so at
                     random; here head h takes step (37 h) mod H, so that a
                     head's step and its A are not ordered alike)
      D              ones (the ``gain`` draw: 1 + 0.1 n)
    ``A_log``, ``dt_bias`` and ``D`` come out float32, as the program holds
    them; the conv weight in the type it was drawn in."""
    std = float(hf.get("init_std", hf.get("initializer_range", 0.02)))
    up = lambda a: a.astype(jnp.float32)
    h, k = _MH(hf), _K(hf)
    lo, hi = hf.get("time_step_min", 1e-3), hf.get("time_step_max", 1e-1)
    delta0 = jnp.maximum(jnp.exp(jnp.linspace(math.log(lo), math.log(hi), h)),
                         hf.get("time_step_floor", 1e-4))
    delta0 = delta0[(DT_STRIDE * jnp.arange(h)) % h]
    conv = w["mixer.conv1d.weight"]
    return {
        "mixer.conv1d.weight":
            (up(conv) * (1.0 / math.sqrt(3 * k) / std)).astype(conv.dtype),
        "mixer.A_log": jnp.log(jnp.linspace(1.0, 16.0, h))
                       + up(w["mixer.A_log"]),
        "mixer.dt_bias": jnp.log(jnp.expm1(delta0)) + up(w["mixer.dt_bias"]),
        "mixer.D": up(w["mixer.D"]),
        "mixer.experts.down_proj": centered(w["mixer.experts.down_proj"]),
        "mixer.shared_experts.down_proj":
            centered(w["mixer.shared_experts.down_proj"]),
    }


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (``serve/models/nemotron_h.py``):
    q, k and v side by side per K/V head, the router's matrix and bias in
    float32 (the bias zero)."""
    e, h, kv, hd = _E(hf), _H(hf), _KV(hf), _HD(hf)
    tree = {
        "backbone.embeddings": {"weight": g["embeddings"]},
        "backbone.norm_f": {"gamma": g["norm_f.weight"]},
        "lm_head": {"kernel": g["lm_head"]},
    }
    for i, (kind, w) in enumerate(zip(layer_kinds(hf), layers)):
        p = f"backbone.layers.{i}"
        m = f"{p}.mixer"
        tree[f"{p}.norm"] = {"gamma": w["norm.weight"]}
        if kind == MAMBA:
            init = published_init(hf, w)
            tree[f"{m}.in_proj"] = {"kernel": w["mixer.in_proj"]}
            tree[f"{m}.conv1d"] = {"weight": init["mixer.conv1d.weight"],
                                   "bias": w["mixer.conv1d.bias"]}
            tree[f"{m}.scan"] = {"A_log": init["mixer.A_log"],
                                 "D": init["mixer.D"],
                                 "dt_bias": init["mixer.dt_bias"]}
            tree[f"{m}.norm"] = {"gamma": w["mixer.norm.weight"]}
            tree[f"{m}.out_proj"] = {"kernel": w["mixer.out_proj"]}
        elif kind == ATTENTION:
            tree[m] = {
                "qkv": jnp.concatenate(
                    [w["mixer.q_proj"].reshape(e, kv, h // kv, hd),
                     w["mixer.k_proj"].reshape(e, kv, 1, hd),
                     w["mixer.v_proj"].reshape(e, kv, 1, hd)], axis=2),
                "o_proj": w["mixer.o_proj"]}
        elif kind == EXPERTS:
            tree[f"{m}.gate"] = {
                "weight": w["mixer.gate.weight"].astype(jnp.float32),
                "e_score_correction_bias": jnp.zeros((_SCORED(hf),),
                                                     jnp.float32)}
            init = published_init(hf, w)
            tree[f"{m}.experts"] = {"up": w["mixer.experts.up_proj"],
                                    "down": init["mixer.experts.down_proj"]}
            if _FS(hf):
                tree[f"{m}.shared_experts.up_proj"] = {
                    "kernel": w["mixer.shared_experts.up_proj"]}
                tree[f"{m}.shared_experts.down_proj"] = {
                    "kernel": init["mixer.shared_experts.down_proj"]}
        else:
            for n in ("up_proj", "down_proj"):
                tree[f"{m}.{n}"] = {"kernel": w[f"mixer.{n}"]}
    return tree


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def relu2_mlp(n, up, down):
    return mm(jnp.square(jnp.maximum(mm(n, up), 0.0)), down)


def mamba2(hf, w, n):
    """The Mamba-2 mixer on the normed rows ``n [B, T, d]``."""
    w = dict(f32(w), **published_init(hf, w))
    w["mixer.conv1d.weight"] = w["mixer.conv1d.weight"].astype(jnp.float32)
    b, t, _ = n.shape
    h, p, g, ns, k = _MH(hf), _MP(hf), _G(hf), _N(hf), _K(hf)
    di, cd = _DI(hf), _CD(hf)
    zxd = mm(n, w["mixer.in_proj"])
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + cd], zxd[..., di + cd:]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(w["mixer.conv1d.weight"][j] * padded[:, j:j + t]
            for j in range(k)) + w["mixer.conv1d.bias"])
    x = xbc[..., :di].reshape(b, t, h, p)
    per_head = lambda a: jnp.repeat(a.reshape(b, t, g, ns), h // g, axis=2)
    bm = per_head(xbc[..., di:di + g * ns])
    cm = per_head(xbc[..., di + g * ns:])
    delta = jax.nn.softplus(dt + w["mixer.dt_bias"])          # [B, T, H]
    decay = jnp.exp(-delta * jnp.exp(w["mixer.A_log"]))

    def step(s, at):
        x_t, b_t, c_t, delta_t, decay_t = at
        s = decay_t[..., None, None] * s \
            + (delta_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

    by_t = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, ns), jnp.float32),
                        tuple(by_t(a) for a in (x, bm, cm, delta, decay)))
    y = jnp.moveaxis(y, 0, 1) + w["mixer.D"][:, None] * x
    v = (y.reshape(b, t, di) * jax.nn.silu(z)).reshape(b, t, g, di // g)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + _EPS(hf))
    return mm(v.reshape(b, t, di) * w["mixer.norm.weight"],
              w["mixer.out_proj"])


def attention(hf, w, n):
    w = f32(w)
    b, t, _ = n.shape
    h, kv, hd = attention_shape(hf)
    q = mm(n, w["mixer.q_proj"]).reshape(b, t, h, hd)
    k = mm(n, w["mixer.k_proj"]).reshape(b, t, kv, hd)
    v = mm(n, w["mixer.v_proj"]).reshape(b, t, kv, hd)
    return mm(causal_attention(q, k, v), w["mixer.o_proj"])


def route(hf, w, n):
    """The router on the normed rows: ``(ids [B, T, k], weights [B, T, k])``
    over ALL the scored experts."""
    s = jax.nn.sigmoid(mm(n, w["mixer.gate.weight"].astype(jnp.float32)))
    _, ids = jax.lax.top_k(s, hf["num_experts_per_tok"])   # b = 0
    wts = jnp.take_along_axis(s, ids, axis=-1)
    if hf.get("norm_topk_prob", True):
        wts = wts / (jnp.sum(wts, -1, keepdims=True) + 1e-20)
    return ids, wts * hf.get("routed_scaling_factor", 1.0)


def routed_experts(hf, w, n, ids, wts):
    """``sum over chosen and held e of w_e expert_e(n)``: every held expert
    on every row, times the row's weight for it or 0; one expert upcast at a
    time."""
    lo, count = held_experts(hf)
    each = jnp.arange(lo, lo + count)
    # [E_held, B, T]: the weight of expert e on row t, 0 where not chosen
    dense = jnp.sum(jnp.where(ids[None] == each[:, None, None, None],
                              wts[None], 0.0), axis=-1)

    def one(acc, at):
        up, down, weight = at
        y = relu2_mlp(n, up.astype(jnp.float32),
                      centered(down).astype(jnp.float32))
        return acc + weight[..., None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(n),
                          (w["mixer.experts.up_proj"],
                           w["mixer.experts.down_proj"], dense))
    return out


def experts(hf, w, n):
    ids, wts = route(hf, w, n)
    out = routed_experts(hf, w, n, ids, wts)
    if _FS(hf):
        out = out + relu2_mlp(
            n, w["mixer.shared_experts.up_proj"].astype(jnp.float32),
            centered(w["mixer.shared_experts.down_proj"]).astype(jnp.float32))
    return out


def mlp(hf, w, n):
    w = f32(w)
    return relu2_mlp(n, w["mixer.up_proj"], w["mixer.down_proj"])


MIXERS = {MAMBA: mamba2, ATTENTION: attention, EXPERTS: experts, MLP: mlp}


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
    return Stream(g["embeddings"][ids].astype(jnp.float32), jnp.int32(0))


def layer(hf, w, x):
    n = rms_norm(x.h, w["norm.weight"].astype(jnp.float32), _EPS(hf))
    present = sorted(set(layer_kinds(hf)), key=KINDS.index)
    which = jnp.asarray([present.index(k) for k in layer_kinds(hf)],
                        jnp.int32)
    out = jax.lax.switch(
        which[x.layer],
        [lambda kind=kind: MIXERS[kind](hf, w, n) for kind in present])
    return Stream(x.h + out, x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    return mm(rms_norm(x, g["norm_f.weight"], _EPS(hf)), g["lm_head"])
