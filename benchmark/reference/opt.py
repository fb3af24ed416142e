"""Plain reference for ``model_type: opt`` (Zhang et al. 2022, HF
``OPTForCausalLM``), pre-LN sizes (``do_layer_norm_before``) with
``word_embed_proj_dim == hidden_size``: learned positions with offset 2,
biased projections, ReLU MLP, final layer norm, LM head tied to the token
embedding.  Tensors in kernel form (``[in, out]``), see seeded_weights.py.
"""

import jax.numpy as jnp

from .common import causal_attention, f32, layer_norm, mm

_E = lambda hf: hf["hidden_size"]
_F = lambda hf: hf["ffn_dim"]


def num_layers(hf):
    return hf["num_hidden_layers"]


def attention_shape(hf):
    """``(query heads, key/value heads, head size)``."""
    h = hf["num_attention_heads"]
    return h, h, _E(hf) // h


GLOBAL = [
    ("embed_tokens", lambda hf: (hf["vocab_size"], _E(hf)), "matrix"),
    ("embed_positions",
     lambda hf: (hf["max_position_embeddings"] + 2, _E(hf)), "matrix"),
    ("final_layer_norm.weight", lambda hf: (_E(hf),), "gain"),
    ("final_layer_norm.bias", lambda hf: (_E(hf),), "bias"),
]
LAYER = [
    ("self_attn_layer_norm.weight", lambda hf: (_E(hf),), "gain"),
    ("self_attn_layer_norm.bias", lambda hf: (_E(hf),), "bias"),
    ("q_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("k_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("v_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("q_proj.bias", lambda hf: (_E(hf),), "bias"),
    ("k_proj.bias", lambda hf: (hf["num_attention_heads"],
                                _E(hf) // hf["num_attention_heads"]),
     "key_bias"),
    ("v_proj.bias", lambda hf: (_E(hf),), "bias"),
    ("out_proj", lambda hf: (_E(hf), _E(hf)), "matrix"),
    ("out_proj.bias", lambda hf: (_E(hf),), "bias"),
    ("final_layer_norm.weight", lambda hf: (_E(hf),), "gain"),
    ("final_layer_norm.bias", lambda hf: (_E(hf),), "bias"),
    ("fc1", lambda hf: (_E(hf), _F(hf)), "matrix"),
    ("fc1.bias", lambda hf: (_F(hf),), "bias"),
    ("fc2", lambda hf: (_F(hf), _E(hf)), "matrix"),
    ("fc2.bias", lambda hf: (_E(hf),), "bias"),
]


def program_tree(hf, g, layers):
    """The serve graph's parameter tree (node names are the HF prefixes;
    q/k/v fused kv-head-major ``[E, KV, 1 + 2, D]``: plain MHA has one query
    head per kv head)."""
    e, h = _E(hf), hf["num_attention_heads"]
    d = e // h
    p = "model.decoder"
    tree = {
        f"{p}.embed_tokens": {"weight": g["embed_tokens"]},
        f"{p}.embed_positions": {"weight": g["embed_positions"]},
        f"{p}.final_layer_norm": {"gamma": g["final_layer_norm.weight"],
                                  "beta": g["final_layer_norm.bias"]},
        "lm_head": {"kernel": g["embed_tokens"].T},
    }
    for i, w in enumerate(layers):
        lp = f"{p}.layers.{i}"
        tree[f"{lp}.self_attn_layer_norm"] = {
            "gamma": w["self_attn_layer_norm.weight"],
            "beta": w["self_attn_layer_norm.bias"]}
        tree[f"{lp}.self_attn"] = {
            "qkv": jnp.stack([w[f"{n}_proj"].reshape(e, h, d)
                              for n in "qkv"], axis=2),
            "qkv_bias": jnp.stack([w[f"{n}_proj.bias"].reshape(h, d)
                                   for n in "qkv"], axis=1),
            "o_proj": w["out_proj"], "o_bias": w["out_proj.bias"]}
        tree[f"{lp}.final_layer_norm"] = {
            "gamma": w["final_layer_norm.weight"],
            "beta": w["final_layer_norm.bias"]}
        tree[f"{lp}.fc1"] = {"kernel": w["fc1"], "bias": w["fc1.bias"]}
        tree[f"{lp}.fc2"] = {"kernel": w["fc2"], "bias": w["fc2.bias"]}
    return tree


def embed(hf, g, ids):
    g = f32(g)
    pos = jnp.arange(ids.shape[1]) + 2
    return g["embed_tokens"][ids] + g["embed_positions"][pos][None]


def layer(hf, w, x):
    w = f32(w)
    b, t, e = x.shape
    h = hf["num_attention_heads"]
    d = e // h
    a = layer_norm(x, w["self_attn_layer_norm.weight"],
                   w["self_attn_layer_norm.bias"], 1e-5)
    q, k, v = ((mm(a, w[f"{n}_proj"]) + w[f"{n}_proj.bias"].reshape(-1)
                ).reshape(b, t, h, d) for n in "qkv")
    x = x + mm(causal_attention(q, k, v), w["out_proj"]) + w["out_proj.bias"]
    a = layer_norm(x, w["final_layer_norm.weight"],
                   w["final_layer_norm.bias"], 1e-5)
    a = jnp.maximum(mm(a, w["fc1"]) + w["fc1.bias"], 0.0)
    return x + mm(a, w["fc2"]) + w["fc2.bias"]


def head(hf, g, x):
    g = f32(g)
    x = layer_norm(x, g["final_layer_norm.weight"],
                   g["final_layer_norm.bias"], 1e-5)
    return mm(x, g["embed_tokens"].T)
