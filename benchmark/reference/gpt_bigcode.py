"""Plain reference for ``model_type: gpt_bigcode`` (StarCoder, Li et al.
2023, HF ``GPTBigCodeForCausalLM``): learned absolute positions, pre-LN,
multi-query attention (one key/value head, ``multi_query: true``), biased
projections, tanh-GELU MLP, LM head tied to ``wte``.  Tensors in kernel form
(``[in, out]``); ``c_attn`` is kept as its three row blocks q | k | v.
"""

import jax
import jax.numpy as jnp

from .common import causal_attention, f32, layer_norm, mm

_E = lambda hf: hf["n_embd"]
_F = lambda hf: hf["n_inner"] or 4 * hf["n_embd"]
_D = lambda hf: hf["n_embd"] // hf["n_head"]
_KV = lambda hf: 1 if hf.get("multi_query", True) else hf["n_head"]


def num_layers(hf):
    return hf["n_layer"]


def attention_shape(hf):
    """``(query heads, key/value heads, head size)``."""
    return hf["n_head"], _KV(hf), _D(hf)


GLOBAL = [
    ("wte", lambda hf: (hf["vocab_size"], _E(hf)), "matrix"),
    ("wpe", lambda hf: (hf["n_positions"], _E(hf)), "matrix"),
    ("ln_f.weight", lambda hf: (_E(hf),), "gain"),
    ("ln_f.bias", lambda hf: (_E(hf),), "bias"),
]
LAYER = [
    ("ln_1.weight", lambda hf: (_E(hf),), "gain"),
    ("ln_1.bias", lambda hf: (_E(hf),), "bias"),
    ("c_attn.q", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("c_attn.k", lambda hf: (_E(hf), _KV(hf) * _D(hf)), "matrix"),
    ("c_attn.v", lambda hf: (_E(hf), _KV(hf) * _D(hf)), "matrix"),
    ("c_attn.q.bias", lambda hf: (_E(hf),), "bias"),
    ("c_attn.k.bias", lambda hf: (_KV(hf), _D(hf)), "key_bias"),
    ("c_attn.v.bias", lambda hf: (_KV(hf) * _D(hf),), "bias"),
    ("attn.c_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("attn.c_proj.bias", lambda hf: (_E(hf),), "bias"),
    ("ln_2.weight", lambda hf: (_E(hf),), "gain"),
    ("ln_2.bias", lambda hf: (_E(hf),), "bias"),
    ("mlp.c_fc", lambda hf: (_E(hf), _F(hf)), "matrix"),
    ("mlp.c_fc.bias", lambda hf: (_F(hf),), "bias"),
    ("mlp.c_proj", lambda hf: (_F(hf), _E(hf)), "matrix"),
    ("mlp.c_proj.bias", lambda hf: (_E(hf),), "bias"),
]


def program_tree(hf, g, layers):
    """The serve graph's parameter tree: q/k/v fused kv-head-major
    ``[E, KV, H/KV + 2, D]`` (query heads in kv-major order, as published)."""
    e, h, d, kv = _E(hf), hf["n_head"], _D(hf), _KV(hf)
    tree = {
        "transformer.wte": {"weight": g["wte"]},
        "transformer.wpe": {"weight": g["wpe"]},
        "transformer.ln_f": {"gamma": g["ln_f.weight"],
                             "beta": g["ln_f.bias"]},
        "lm_head": {"kernel": g["wte"].T},
    }
    for i, w in enumerate(layers):
        lp = f"transformer.h.{i}"
        tree[f"{lp}.ln_1"] = {"gamma": w["ln_1.weight"],
                              "beta": w["ln_1.bias"]}
        tree[f"{lp}.ln_2"] = {"gamma": w["ln_2.weight"],
                              "beta": w["ln_2.bias"]}
        tree[f"{lp}.attn"] = {
            "qkv": jnp.concatenate(
                [w["c_attn.q"].reshape(e, kv, h // kv, d),
                 w["c_attn.k"].reshape(e, kv, 1, d),
                 w["c_attn.v"].reshape(e, kv, 1, d)], axis=2),
            "qkv_bias": jnp.concatenate(
                [w["c_attn.q.bias"].reshape(kv, h // kv, d),
                 w["c_attn.k.bias"].reshape(kv, 1, d),
                 w["c_attn.v.bias"].reshape(kv, 1, d)], axis=1),
            "o_proj": w["attn.c_proj"], "o_bias": w["attn.c_proj.bias"]}
        tree[f"{lp}.mlp.c_fc"] = {"kernel": w["mlp.c_fc"],
                                  "bias": w["mlp.c_fc.bias"]}
        tree[f"{lp}.mlp.c_proj"] = {"kernel": w["mlp.c_proj"],
                                    "bias": w["mlp.c_proj.bias"]}
    return tree


def embed(hf, g, ids):
    g = f32(g)
    return g["wte"][ids] + g["wpe"][jnp.arange(ids.shape[1])][None]


def layer(hf, w, x):
    w = f32(w)
    b, t, e = x.shape
    h, d, kv = hf["n_head"], _D(hf), _KV(hf)
    eps = hf.get("layer_norm_epsilon", 1e-5)
    a = layer_norm(x, w["ln_1.weight"], w["ln_1.bias"], eps)
    q = (mm(a, w["c_attn.q"]) + w["c_attn.q.bias"]).reshape(b, t, h, d)
    k = (mm(a, w["c_attn.k"]) + w["c_attn.k.bias"].reshape(-1)).reshape(
        b, t, kv, d)
    v = (mm(a, w["c_attn.v"]) + w["c_attn.v.bias"]).reshape(b, t, kv, d)
    x = x + mm(causal_attention(q, k, v), w["attn.c_proj"]) \
        + w["attn.c_proj.bias"]
    a = layer_norm(x, w["ln_2.weight"], w["ln_2.bias"], eps)
    a = jax.nn.gelu(mm(a, w["mlp.c_fc"]) + w["mlp.c_fc.bias"],
                    approximate=True)
    return x + mm(a, w["mlp.c_proj"]) + w["mlp.c_proj.bias"]


def head(hf, g, x):
    g = f32(g)
    x = layer_norm(x, g["ln_f.weight"], g["ln_f.bias"],
                   hf.get("layer_norm_epsilon", 1e-5))
    return mm(x, g["wte"].T)
