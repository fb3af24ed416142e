"""Plain reference for ``model_type: deepseek_v2`` (DeepSeek-V2-Lite: latent
attention with YaRN rotary, one leading dense layer, then mixtures of gated
experts scored by softmax beside summed shared experts).  float32,
``HIGHEST`` precision; the MATERIALISED form only — every head's keys and
values expanded from the latents of the whole sequence —, NO cache, NO
absorption, NO sort, NO grouped GEMM, NO kernel: attention is the masked
softmax over the whole sequence (queries in blocks so that it fits at the
timed sizes), the experts a loop over all of them under a 0 / weight mask,
one expert upcast to float32 at a time.  Tensors in kernel form (``[in,
out]``), see seeded_weights.py.

Layer l, rows ``x [T, d]``, position t; ``RMS(x, g) = x / sqrt(mean(x^2) +
rms_norm_eps) g``:

  n = RMS(x, g1);  q = n W_q -> per head [q_n (nope) | q_r (rope)]
  [c' (kv_lora_rank) | k_r' (rope)] = n W_kv_a;  c = RMS(c', g_kv)
  k_r = rope(k_r', t) — ONE per position, shared by all heads, not normed
  [k_n,i (nope) | v_i (v_head_dim)] = c W_kv_b  per head i;  q_r,i rotated
  s = sigma (q_n,i . k_n,i(j) + q_r,i . k_r(j)),  j <= t,
      sigma = (nope + rope)^-1/2 m^2,  m = 0.1 mscale_all_dim ln factor + 1
  a = concat_i(softmax_j(s) v_i(j)) W_o;  x <- x + a
  rope (YaRN): f_p = theta^(-2p / rope), p < rope / 2;
      corr(r) = rope ln(L / (2 pi r)) / (2 ln theta), L the original context;
      low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)), clamped to
      [0, rope - 1]; ramp_p = clip((p - low) / (high - low), 0, 1);
      inv_p = f_p (1 - ramp_p) + f_p / factor ramp_p; the pair (2p, 2p + 1)
      — INTERLEAVED — turned by t inv_p; cos and sin times
      yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim) (1
      as published)
  n = RMS(x, g2)
  l < first_k_dense_replace:  FFN = W_down (silu(W_gate n) * W_up n), width
      intermediate_size
  else:  p = softmax(n W_r) in float32 over ALL n_routed_experts; chosen = the
      num_experts_per_tok largest (ties to the lower id); weights p[chosen]
      AS THEY ARE (norm_topk_prob false) x routed_scaling_factor;
      E_e(n) = W_down,e (silu(W_gate,e n) * W_up,e n), width
      moe_intermediate_size;
      FFN = sum over chosen e of p_e E_e(n) + sum_{j < n_shared} S_j(n)
      (the shared experts, the same form and width, SUMMED, unweighted)
  x <- x + FFN;   logits = RMS(x_T, g_f) W_head                (untied)

ASSUMED (the catalog's ``config`` does not print them; the configuration's
file lists each): bf16; the rotary layout and the YaRN formulas above (the
family's published modeling code, from memory); ``c`` cached after its norm
and ``k_r`` after rotation (a cache is the program's; here it only fixes
WHAT is normed and rotated); the tensor names below; the draw.

``seeded_weights`` draws every matrix normal at std 0.02 (``init_std``),
gains 1 + 0.1 n.  :func:`published_init` scales the ROUTER's draw by
``ROUTER_GAIN`` = 2: at std 0.02 over a normed row of 2048 the 64 scores
have spread 0.9 and the softmax is nearly flat — the six chosen hold a third
of the mass (0.33; root sum of squares 0.145), so the routed sum enters the
stream at a tenth of the two shared experts' size (0.145 / sqrt 2) and a
fault in the routed path moves a logit by little more than bf16 resolves.  A
trained router is more decided.  Times 2 the scores' spread is 1.8, the
chosen six hold 0.63 of the mass (root sum of squares 0.33: a quarter of
the shared experts' size), rank 6 and rank 7 lie at 0.042 and 0.035, so a
flipped sixth choice moves a row by a hundredth of the routed sum (my count,
20 000 normal rows).  No equation changes; both sides draw through the one
function.
"""

import math

import jax
import jax.numpy as jnp

from .common import HI, f32, mm
from .nemotron_h import Stream  # hidden states + the next layer's index

Q_BLOCK = 512      # queries at a time, so that the scores fit beside a deployment
ROUTER_GAIN = 2.0  # on the router's drawn matrix: see the module docstring

_E = lambda hf: hf["hidden_size"]
_V = lambda hf: hf["vocab_size"]
_H = lambda hf: hf["num_attention_heads"]
_R = lambda hf: hf["kv_lora_rank"]
_DN = lambda hf: hf["qk_nope_head_dim"]
_DR = lambda hf: hf["qk_rope_head_dim"]
_DV = lambda hf: hf["v_head_dim"]
_I = lambda hf: hf["intermediate_size"]
_F = lambda hf: hf["moe_intermediate_size"]
_N = lambda hf: hf["n_routed_experts"]
_S = lambda hf: hf.get("n_shared_experts", 0)
_EPS = lambda hf: hf.get("rms_norm_eps", 1e-6)


def num_layers(hf):
    return hf["num_hidden_layers"]


def is_dense(hf, i):
    return not (_N(hf) and i >= hf.get("first_k_dense_replace", 0)
                and i % hf.get("moe_layer_freq", 1) == 0)


def attention_shape(hf):
    """For ``headroom.py`` alone, which prices a cached position at ``2 x kv
    heads x head size`` elements: ``(heads, 1, (kv_lora_rank + rope) / 2)``
    makes that the latent cache's own (16, 1, 288 -> 2 x 288 x 2 B = 1 152 B
    a position and layer).  It then UNDER-counts attention's operations (4 x
    16 x 288 a pair where the least of either form is 2 x 16 x (576 + 512))
    and the queries' bytes; the cache, which is what empties a queue at the
    roofline, is never over-counted."""
    return _H(hf), 1, (_R(hf) + _DR(hf)) // 2


# the published names (ASSUMED: the family's convention, no checkpoint is on
# this machine): ``model.layers.<l>.<name>.weight``; the routed experts
# ``mlp.experts.<e>.{gate,up,down}_proj`` stacked on a leading axis here; the
# shared experts ONE module ``mlp.shared_experts`` of width n_shared x f
# (flexflow_tpu/serve/weights.py lists them for an importer)
GLOBAL = [
    ("embed_tokens", lambda hf: (_V(hf), _E(hf)), "matrix"),
    ("norm.weight", lambda hf: (_E(hf),), "gain"),
    ("lm_head", lambda hf: (_E(hf), _V(hf)), "matrix"),
]
# the UNION of the dense and the expert layer's tensors (``seeded_weights``
# draws by table row; what a layer does not use is never computed).
# headroom.py's parameter count therefore counts the dense FFN AND every
# expert in every layer: PERF.md section 7.
LAYER = [
    ("input_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("self_attn.q_proj", lambda hf: (_E(hf), _H(hf) * (_DN(hf) + _DR(hf))),
     "matrix"),
    ("self_attn.kv_a_proj_with_mqa", lambda hf: (_E(hf), _R(hf) + _DR(hf)),
     "matrix"),
    ("self_attn.kv_a_layernorm.weight", lambda hf: (_R(hf),), "gain"),
    ("self_attn.kv_b_proj", lambda hf: (_R(hf), _H(hf) * (_DN(hf) + _DV(hf))),
     "matrix"),
    ("self_attn.o_proj", lambda hf: (_H(hf) * _DV(hf), _E(hf)), "matrix"),
    ("post_attention_layernorm.weight", lambda hf: (_E(hf),), "gain"),
    ("mlp.gate_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.up_proj", lambda hf: (_E(hf), _I(hf)), "matrix"),
    ("mlp.down_proj", lambda hf: (_I(hf), _E(hf)), "matrix"),
    ("mlp.gate.weight", lambda hf: (_E(hf), _N(hf)), "matrix"),
    ("mlp.experts.gate_proj", lambda hf: (_N(hf), _E(hf), _F(hf)), "matrix"),
    ("mlp.experts.up_proj", lambda hf: (_N(hf), _E(hf), _F(hf)), "matrix"),
    ("mlp.experts.down_proj", lambda hf: (_N(hf), _F(hf), _E(hf)), "matrix"),
    ("mlp.shared_experts.gate_proj", lambda hf: (_E(hf), _S(hf) * _F(hf)),
     "matrix"),
    ("mlp.shared_experts.up_proj", lambda hf: (_E(hf), _S(hf) * _F(hf)),
     "matrix"),
    ("mlp.shared_experts.down_proj", lambda hf: (_S(hf) * _F(hf), _E(hf)),
     "matrix"),
]


def published_init(hf, w):
    """The drawn tensors that a trained model holds otherwise — the ONE
    place, for ``program_tree`` and ``layer`` alike: the router's matrix in
    float32, times ``ROUTER_GAIN`` (the module's docstring says why)."""
    return {"mlp.gate.weight":
            w["mlp.gate.weight"].astype(jnp.float32)
            * float(hf.get("router_gain", ROUTER_GAIN))}


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (``serve/models/deepseek_v2.py``):
    ``q_proj [d, H, nope + rope]`` and ``kv_b [r, H, nope + v]`` with the
    heads apart (head i's ``U_k`` is ``kv_b[:, i, :nope]``, its ``U_v``
    ``kv_b[:, i, nope:]``), the router's matrix in float32."""
    h, dn, dr, dv = _H(hf), _DN(hf), _DR(hf), _DV(hf)
    tree = {
        "model.embed_tokens": {"weight": g["embed_tokens"]},
        "model.norm": {"gamma": g["norm.weight"]},
        "lm_head": {"kernel": g["lm_head"]},
    }
    for i, w in enumerate(layers):
        p = f"model.layers.{i}"
        tree[f"{p}.input_layernorm"] = {"gamma": w["input_layernorm.weight"]}
        tree[f"{p}.post_attention_layernorm"] = {
            "gamma": w["post_attention_layernorm.weight"]}
        tree[f"{p}.self_attn"] = {
            "q_proj": w["self_attn.q_proj"].reshape(_E(hf), h, dn + dr),
            "kv_a": w["self_attn.kv_a_proj_with_mqa"],
            "kv_norm": w["self_attn.kv_a_layernorm.weight"],
            "kv_b": w["self_attn.kv_b_proj"].reshape(_R(hf), h, dn + dv),
            "o_proj": w["self_attn.o_proj"]}
        if is_dense(hf, i):
            for n in ("gate", "up", "down"):
                tree[f"{p}.mlp.{n}_proj"] = {"kernel": w[f"mlp.{n}_proj"]}
            continue
        tree[f"{p}.mlp.gate"] = {
            "weight": published_init(hf, w)["mlp.gate.weight"]}
        tree[f"{p}.mlp.experts"] = {
            n: w[f"mlp.experts.{n}_proj"] for n in ("gate", "up", "down")}
        if _S(hf):
            for n in ("gate", "up", "down"):
                tree[f"{p}.mlp.shared_experts.{n}_proj"] = {
                    "kernel": w[f"mlp.shared_experts.{n}_proj"]}
    return tree


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(hf):
    """``inv_p`` for the ``rope / 2`` pairs."""
    dr, theta = _DR(hf), float(hf.get("rope_theta", 10000.0))
    f = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ys = hf.get("rope_scaling")
    if not ys:
        return f
    orig = ys["original_max_position_embeddings"]
    corr = lambda r: dr * math.log(orig / (2 * math.pi * r)) / (
        2 * math.log(theta))
    low = max(math.floor(corr(ys["beta_fast"])), 0)
    high = min(math.ceil(corr(ys["beta_slow"])), dr - 1)
    ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + f / ys["factor"] * ramp


def rope(hf, x):
    """``x [B, T, heads, rope]`` at positions ``0 .. T - 1``, interleaved
    pairs."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * yarn_frequencies(hf)
    ys = hf.get("rope_scaling") or {}
    amp = (yarn_mscale(ys.get("factor", 1), ys.get("mscale", 1))
           / yarn_mscale(ys.get("factor", 1), ys.get("mscale_all_dim", 0)))
    cos, sin = amp * jnp.cos(ang)[:, None], amp * jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def softmax_scale(hf):
    ys = hf.get("rope_scaling") or {}
    m = (yarn_mscale(ys["factor"], ys["mscale_all_dim"])
         if ys.get("mscale_all_dim") else 1.0)
    return m * m / math.sqrt(_DN(hf) + _DR(hf))


def attention(hf, w, n):
    """Latent attention, materialised, on the normed rows ``n [B, T, d]``."""
    w = f32({m: w[m] for m in w if m.startswith("self_attn.")})
    b, t, _ = n.shape
    h, r, dn, dr, dv = _H(hf), _R(hf), _DN(hf), _DR(hf), _DV(hf)
    q = mm(n, w["self_attn.q_proj"]).reshape(b, t, h, dn + dr)
    q_n, q_r = q[..., :dn], rope(hf, q[..., dn:])
    ckr = mm(n, w["self_attn.kv_a_proj_with_mqa"])
    c = rms_norm(ckr[..., :r], w["self_attn.kv_a_layernorm.weight"],
                 _EPS(hf))
    k_r = rope(hf, ckr[..., r:][:, :, None])[:, :, 0]         # [B, T, rope]
    kv = mm(c, w["self_attn.kv_b_proj"]).reshape(b, t, h, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    out = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        s = (jnp.einsum("bthn,bshn->bhts", q_n[:, lo:hi], k_n[:, :hi],
                        precision=HI)
             + jnp.einsum("bthr,bsr->bhts", q_r[:, lo:hi], k_r[:, :hi],
                          precision=HI)) * softmax_scale(hf)
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhts,bshv->bthv", p, v[:, :hi], precision=HI))
    heads = jnp.concatenate(out, axis=1).reshape(b, t, h * dv)
    return mm(heads, w["self_attn.o_proj"])


def gated_mlp(n, gate, up, down):
    return mm(jax.nn.silu(mm(n, gate)) * mm(n, up), down)


def route(hf, w, n):
    """``(ids [B, T, k], weights [B, T, k])`` over all the experts."""
    p = jax.nn.softmax(mm(n, published_init(hf, w)["mlp.gate.weight"]),
                       axis=-1)
    wts, ids = jax.lax.top_k(p, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob", False):
        wts = wts / jnp.sum(wts, -1, keepdims=True)
    return ids, wts * hf.get("routed_scaling_factor", 1.0)


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


def shared_experts(hf, w, n):
    """The shared experts' outputs SUMMED: expert j is columns (rows) ``j f
    .. (j + 1) f`` of the one published module."""
    f, up32 = _F(hf), lambda a: a.astype(jnp.float32)
    out = jnp.zeros_like(n)
    for j in range(_S(hf)):
        cols = slice(j * f, (j + 1) * f)
        out = out + gated_mlp(
            n, up32(w["mlp.shared_experts.gate_proj"][:, cols]),
            up32(w["mlp.shared_experts.up_proj"][:, cols]),
            up32(w["mlp.shared_experts.down_proj"][cols]))
    return out


def mixture(hf, w, n):
    ids, wts = route(hf, w, n)
    return routed_experts(hf, w, n, ids, wts) + shared_experts(hf, w, n)


def dense_mlp(hf, w, n):
    return gated_mlp(n, *(w[f"mlp.{m}_proj"].astype(jnp.float32)
                          for m in ("gate", "up", "down")))


def embed(hf, g, ids):
    return Stream(g["embed_tokens"][ids].astype(jnp.float32), jnp.int32(0))


def layer(hf, w, x):
    up32 = lambda name: w[name].astype(jnp.float32)
    h = x.h + attention(hf, w, rms_norm(
        x.h, up32("input_layernorm.weight"), _EPS(hf)))
    n = rms_norm(h, up32("post_attention_layernorm.weight"), _EPS(hf))
    dense = jnp.asarray([is_dense(hf, i) for i in range(num_layers(hf))])
    ffn = jax.lax.cond(dense[x.layer], lambda: dense_mlp(hf, w, n),
                       lambda: mixture(hf, w, n))
    return Stream(h + ffn, x.layer + 1)


def head(hf, g, x):
    g = f32(g)
    return mm(rms_norm(x, g["norm.weight"], _EPS(hf)), g["lm_head"])
