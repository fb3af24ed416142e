"""HuggingFace weight import: torch state dict → flexflow_tpu param tree.

Reference: ``inference/file_loader.cc`` (``FileDataLoader::load_weights``) and
``python/flexflow/serve/serve.py``'s download-and-convert path.  The reference
exports HF checkpoints to raw binary per-tensor files and loads them into
Legion regions with manual TP slicing; here the conversion is a pure name/
layout map into the param pytree and sharding is applied by ``device_put``
with the plan's NamedShardings — GSPMD handles the slicing.

Layout notes (torch ``nn.Linear.weight`` is ``[out, in]``; our Linear kernel
is ``[in, out]``, so every projection transposes):

* ``q/k/v_proj`` fuse into the kv-head-major ``qkv [E, KV, q_per_kv+2, D]``
  used by :class:`~flexflow_tpu.serve.ops.IncMultiHeadSelfAttention` (one MXU
  GEMM, TP = shard dim 1).
* ``o_proj.weight [E, QH*D]`` → ``[QH*D, E]``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .models.base import ServeModelConfig


def _t(x) -> np.ndarray:
    """torch tensor (any dtype/device) -> float32 numpy."""
    import torch

    if isinstance(x, torch.Tensor):
        return x.detach().to(torch.float32).cpu().numpy()
    return np.asarray(x, np.float32)


def fuse_qkv(qw, kw, vw, cfg: ServeModelConfig) -> np.ndarray:
    """[QH*D,E],[KV*D,E],[KV*D,E] (torch layout) -> [E, KV, q_per_kv+2, D]."""
    e = cfg.hidden_size
    kv, d = cfg.kv_heads, cfg.hdim
    gq = cfg.num_attention_heads // kv
    q = _t(qw).T.reshape(e, kv, gq, d)
    k = _t(kw).T.reshape(e, kv, 1, d)
    v = _t(vw).T.reshape(e, kv, 1, d)
    return np.concatenate([q, k, v], axis=2)


def convert_llama_state_dict(
    sd: Dict, cfg: ServeModelConfig, dtype=jnp.float32
) -> Dict[str, Dict[str, jax.Array]]:
    """HF LLaMA ``state_dict()`` → ``{node_name: {param_name: array}}``.

    Node names in the serve graph intentionally equal HF module prefixes
    (see ``models/llama.py``), so this is mostly a suffix map.
    """
    params: Dict[str, Dict[str, jax.Array]] = {}

    def put(node, pname, arr):
        params.setdefault(node, {})[pname] = jnp.asarray(arr, dtype)

    put("model.embed_tokens", "weight", _t(sd["model.embed_tokens.weight"]))
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        put(f"{p}.input_layernorm", "gamma", _t(sd[f"{p}.input_layernorm.weight"]))
        put(
            f"{p}.post_attention_layernorm", "gamma",
            _t(sd[f"{p}.post_attention_layernorm.weight"]),
        )
        put(
            f"{p}.self_attn", "qkv",
            fuse_qkv(
                sd[f"{p}.self_attn.q_proj.weight"],
                sd[f"{p}.self_attn.k_proj.weight"],
                sd[f"{p}.self_attn.v_proj.weight"],
                cfg,
            ),
        )
        put(f"{p}.self_attn", "o_proj", _t(sd[f"{p}.self_attn.o_proj.weight"]).T)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            put(f"{p}.mlp.{proj}", "kernel", _t(sd[f"{p}.mlp.{proj}.weight"]).T)
    put("model.norm", "gamma", _t(sd["model.norm.weight"]))
    if "lm_head.weight" in sd:
        put("lm_head", "kernel", _t(sd["lm_head.weight"]).T)
    else:  # tied embeddings
        put("lm_head", "kernel", _t(sd["model.embed_tokens.weight"]).T)
    return params


def fuse_qkv_rows(w, cfg: ServeModelConfig) -> np.ndarray:
    """Pre-fused row-stacked ``[QH*D + 2*KV*D, E]`` (q|k|v, q heads in
    kv-major order — HF falcon/mpt/gpt_bigcode layouts) → our
    ``[E, KV, q_per_kv+2, D]``."""
    e = cfg.hidden_size
    kv, d = cfg.kv_heads, cfg.hdim
    qh = cfg.num_attention_heads
    w = _t(w)
    q, k, v = np.split(w, [qh * d, qh * d + kv * d], axis=0)
    return np.concatenate(
        [
            q.T.reshape(e, kv, qh // kv, d),
            k.T.reshape(e, kv, 1, d),
            v.T.reshape(e, kv, 1, d),
        ],
        axis=2,
    )


def fuse_qkv_bias(qb, kb, vb, cfg: ServeModelConfig) -> np.ndarray:
    kv, d = cfg.kv_heads, cfg.hdim
    gq = cfg.num_attention_heads // kv
    return np.concatenate(
        [
            _t(qb).reshape(kv, gq, d),
            _t(kb).reshape(kv, 1, d),
            _t(vb).reshape(kv, 1, d),
        ],
        axis=1,
    )


def fuse_qkv_rows_bias(b, cfg: ServeModelConfig) -> np.ndarray:
    kv, d = cfg.kv_heads, cfg.hdim
    qh = cfg.num_attention_heads
    qb, kb, vb = np.split(_t(b), [qh * d, qh * d + kv * d])
    return fuse_qkv_bias(qb, kb, vb, cfg)


def convert_opt_state_dict(sd, cfg: ServeModelConfig, dtype=jnp.float32):
    params: Dict[str, Dict[str, jax.Array]] = {}

    def put(node, pname, arr):
        params.setdefault(node, {})[pname] = jnp.asarray(arr, dtype)

    def ln(node, key):
        put(node, "gamma", _t(sd[f"{key}.weight"]))
        put(node, "beta", _t(sd[f"{key}.bias"]))

    put("model.decoder.embed_tokens", "weight",
        _t(sd["model.decoder.embed_tokens.weight"]))
    put("model.decoder.embed_positions", "weight",
        _t(sd["model.decoder.embed_positions.weight"]))
    for i in range(cfg.num_hidden_layers):
        p = f"model.decoder.layers.{i}"
        ln(f"{p}.self_attn_layer_norm", f"{p}.self_attn_layer_norm")
        put(
            f"{p}.self_attn", "qkv",
            fuse_qkv(
                sd[f"{p}.self_attn.q_proj.weight"],
                sd[f"{p}.self_attn.k_proj.weight"],
                sd[f"{p}.self_attn.v_proj.weight"],
                cfg,
            ),
        )
        put(
            f"{p}.self_attn", "qkv_bias",
            fuse_qkv_bias(
                sd[f"{p}.self_attn.q_proj.bias"],
                sd[f"{p}.self_attn.k_proj.bias"],
                sd[f"{p}.self_attn.v_proj.bias"],
                cfg,
            ),
        )
        put(f"{p}.self_attn", "o_proj", _t(sd[f"{p}.self_attn.out_proj.weight"]).T)
        put(f"{p}.self_attn", "o_bias", _t(sd[f"{p}.self_attn.out_proj.bias"]))
        ln(f"{p}.final_layer_norm", f"{p}.final_layer_norm")
        for fc in ("fc1", "fc2"):
            put(f"{p}.{fc}", "kernel", _t(sd[f"{p}.{fc}.weight"]).T)
            put(f"{p}.{fc}", "bias", _t(sd[f"{p}.{fc}.bias"]))
    if "model.decoder.final_layer_norm.weight" in sd:  # pre-LN variants only
        ln("model.decoder.final_layer_norm", "model.decoder.final_layer_norm")
    for proj in ("project_in", "project_out"):  # opt-350m embed projection
        key = f"model.decoder.{proj}.weight"
        if key in sd:
            put(f"model.decoder.{proj}", "kernel", _t(sd[key]).T)
    lm = sd.get("lm_head.weight", sd["model.decoder.embed_tokens.weight"])
    put("lm_head", "kernel", _t(lm).T)
    return params


def convert_falcon_state_dict(sd, cfg: ServeModelConfig, dtype=jnp.float32):
    if cfg.new_decoder_architecture:
        raise NotImplementedError(
            "falcon new_decoder_architecture weight layout is not supported"
        )
    params: Dict[str, Dict[str, jax.Array]] = {}

    def put(node, pname, arr):
        params.setdefault(node, {})[pname] = jnp.asarray(arr, dtype)

    def ln(node, key):
        put(node, "gamma", _t(sd[f"{key}.weight"]))
        put(node, "beta", _t(sd[f"{key}.bias"]))

    put("transformer.word_embeddings", "weight",
        _t(sd["transformer.word_embeddings.weight"]))
    for i in range(cfg.num_hidden_layers):
        p = f"transformer.h.{i}"
        ln(f"{p}.input_layernorm", f"{p}.input_layernorm")
        if not cfg.parallel_attn:  # falcon-rw sequential layout
            ln(f"{p}.post_attention_layernorm", f"{p}.post_attention_layernorm")
        # falcon's fused weight is already kv-head-major interleaved
        # (HF _split_heads: view(heads, 3, D) / view(heads+2, D) for MQA),
        # which IS our [E, KV, q_per_kv+2, D] layout — a straight reshape
        put(f"{p}.self_attention", "qkv",
            _t(sd[f"{p}.self_attention.query_key_value.weight"]).T.reshape(
                cfg.hidden_size, cfg.kv_heads,
                cfg.num_attention_heads // cfg.kv_heads + 2, cfg.hdim))
        put(f"{p}.self_attention", "o_proj",
            _t(sd[f"{p}.self_attention.dense.weight"]).T)
        put(f"{p}.mlp.dense_h_to_4h", "kernel",
            _t(sd[f"{p}.mlp.dense_h_to_4h.weight"]).T)
        put(f"{p}.mlp.dense_4h_to_h", "kernel",
            _t(sd[f"{p}.mlp.dense_4h_to_h.weight"]).T)
        if cfg.bias:
            put(f"{p}.self_attention", "qkv_bias",
                _t(sd[f"{p}.self_attention.query_key_value.bias"]).reshape(
                    cfg.kv_heads,
                    cfg.num_attention_heads // cfg.kv_heads + 2, cfg.hdim))
            put(f"{p}.self_attention", "o_bias",
                _t(sd[f"{p}.self_attention.dense.bias"]))
            put(f"{p}.mlp.dense_h_to_4h", "bias",
                _t(sd[f"{p}.mlp.dense_h_to_4h.bias"]))
            put(f"{p}.mlp.dense_4h_to_h", "bias",
                _t(sd[f"{p}.mlp.dense_4h_to_h.bias"]))
    ln("transformer.ln_f", "transformer.ln_f")
    lm = sd.get("lm_head.weight", sd["transformer.word_embeddings.weight"])
    put("lm_head", "kernel", _t(lm).T)
    return params


def convert_mpt_state_dict(sd, cfg: ServeModelConfig, dtype=jnp.float32):
    params: Dict[str, Dict[str, jax.Array]] = {}

    def put(node, pname, arr):
        params.setdefault(node, {})[pname] = jnp.asarray(arr, dtype)

    put("transformer.wte", "weight", _t(sd["transformer.wte.weight"]))
    for i in range(cfg.num_hidden_layers):
        p = f"transformer.blocks.{i}"
        put(f"{p}.norm_1", "gamma", _t(sd[f"{p}.norm_1.weight"]))
        put(f"{p}.norm_2", "gamma", _t(sd[f"{p}.norm_2.weight"]))
        put(f"{p}.attn", "qkv", fuse_qkv_rows(sd[f"{p}.attn.Wqkv.weight"], cfg))
        put(f"{p}.attn", "o_proj", _t(sd[f"{p}.attn.out_proj.weight"]).T)
        put(f"{p}.ffn.up_proj", "kernel", _t(sd[f"{p}.ffn.up_proj.weight"]).T)
        put(f"{p}.ffn.down_proj", "kernel",
            _t(sd[f"{p}.ffn.down_proj.weight"]).T)
    put("transformer.norm_f", "gamma", _t(sd["transformer.norm_f.weight"]))
    lm = sd.get("lm_head.weight", sd["transformer.wte.weight"])
    put("lm_head", "kernel", _t(lm).T)
    return params


def convert_starcoder_state_dict(sd, cfg: ServeModelConfig, dtype=jnp.float32):
    params: Dict[str, Dict[str, jax.Array]] = {}

    def put(node, pname, arr):
        params.setdefault(node, {})[pname] = jnp.asarray(arr, dtype)

    def ln(node, key):
        put(node, "gamma", _t(sd[f"{key}.weight"]))
        put(node, "beta", _t(sd[f"{key}.bias"]))

    put("transformer.wte", "weight", _t(sd["transformer.wte.weight"]))
    put("transformer.wpe", "weight", _t(sd["transformer.wpe.weight"]))
    for i in range(cfg.num_hidden_layers):
        p = f"transformer.h.{i}"
        ln(f"{p}.ln_1", f"{p}.ln_1")
        ln(f"{p}.ln_2", f"{p}.ln_2")
        put(f"{p}.attn", "qkv", fuse_qkv_rows(sd[f"{p}.attn.c_attn.weight"], cfg))
        put(f"{p}.attn", "qkv_bias",
            fuse_qkv_rows_bias(sd[f"{p}.attn.c_attn.bias"], cfg))
        put(f"{p}.attn", "o_proj", _t(sd[f"{p}.attn.c_proj.weight"]).T)
        put(f"{p}.attn", "o_bias", _t(sd[f"{p}.attn.c_proj.bias"]))
        for fc in ("c_fc", "c_proj"):
            put(f"{p}.mlp.{fc}", "kernel", _t(sd[f"{p}.mlp.{fc}.weight"]).T)
            put(f"{p}.mlp.{fc}", "bias", _t(sd[f"{p}.mlp.{fc}.bias"]))
    ln("transformer.ln_f", "transformer.ln_f")
    lm = sd.get("lm_head.weight", sd["transformer.wte.weight"])
    put("lm_head", "kernel", _t(lm).T)
    return params


CONVERTERS = {
    "llama": convert_llama_state_dict,
    "opt": convert_opt_state_dict,
    "falcon": convert_falcon_state_dict,
    "mpt": convert_mpt_state_dict,
    "gpt_bigcode": convert_starcoder_state_dict,
}


def convert_state_dict(sd, cfg: ServeModelConfig, dtype=jnp.float32):
    if cfg.model_type not in CONVERTERS:
        raise ValueError(
            f"no weight converter for {cfg.model_type!r}; "
            f"known: {sorted(CONVERTERS)}"
        )
    return CONVERTERS[cfg.model_type](sd, cfg, dtype)


# ``cohere2_moe`` (Command A+): NO importer yet — no checkpoint of this
# family is in the repository, so the names below are the family's
# convention (HF ``Cohere2``'s module names with the mixture's added) and
# stay ASSUMED until one is.  ``{torch tensor: (graph node, parameter,
# layout)}`` per layer ``model.layers.<l>.``, beside the reference's tables
# (``benchmark/reference/cohere2_moe.py`` ``LAYER`` / ``program_tree`` hold
# the same map in kernel form, ``[in, out]``): a projection transposes;
# ``q/k/v`` fuse as :func:`fuse_qkv` does; expert ``e`` of the held ones is
# row ``e - expert_share_index x num_experts`` of ``mlp.experts``' three
# ``[E, in, out]`` tensors; shared expert ``j`` is columns (gate, up) / rows
# (down) ``j f .. (j + 1) f`` of the three ``SharedExpertLinear`` kernels;
# the head is the embedding transposed (``tie_word_embeddings``).  A chip
# that holds a share takes its K/V groups' columns of q/k/v and rows of
# ``o_proj``, its experts by id and its rows of the embedding.
COHERE2_MOE_TENSORS = {
    "model.embed_tokens.weight": ("model.embed_tokens", "weight", "[V, d]"),
    "model.norm.weight": ("model.norm", "gamma", "[d]"),
    "(tied) model.embed_tokens.weight": ("lm_head", "kernel", "[d, V] = .T"),
    "input_layernorm.weight": ("input_layernorm", "gamma", "[d]"),
    "self_attn.q_proj.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.k_proj.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.v_proj.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.o_proj.weight": ("self_attn", "o_proj", "[H hd, d] = .T"),
    "mlp.gate.weight": ("mlp.gate", "weight", "[d, experts] = .T, float32"),
    "mlp.experts.<e>.gate_proj.weight": ("mlp.experts", "gate", "[e] = .T"),
    "mlp.experts.<e>.up_proj.weight": ("mlp.experts", "up", "[e] = .T"),
    "mlp.experts.<e>.down_proj.weight": ("mlp.experts", "down", "[e] = .T"),
    "mlp.shared_experts.<j>.gate_proj.weight":
        ("mlp.shared_experts.gate_proj", "kernel", "[:, j f:(j + 1) f] = .T"),
    "mlp.shared_experts.<j>.up_proj.weight":
        ("mlp.shared_experts.up_proj", "kernel", "[:, j f:(j + 1) f] = .T"),
    "mlp.shared_experts.<j>.down_proj.weight":
        ("mlp.shared_experts.down_proj", "kernel", "[j f:(j + 1) f] = .T"),
}


# The published tensor names of ``mellum`` (Mellum 2), for an importer to be
# written when a checkpoint is in the repository (none is: they are the
# family's convention and stay ASSUMED until one is).  ``{torch tensor: (graph
# node, parameter, layout)}`` per layer ``model.layers.<l>.``, beside the
# reference's tables (``benchmark/reference/mellum.py`` ``LAYER`` /
# ``program_tree``: the same map in kernel form, ``[in, out]``): a projection
# transposes; q, k and v fuse per K/V head as ``IncMultiHeadSelfAttention``'s
# do (the ring layers' ``SlidingWindowAttention`` has the same layout); the
# experts ``mlp.experts.<e>`` stack into ``[E, in, out]``; a ``dense`` layer
# of ``mlp_layer_types`` (the published list has none) carries
# ``mlp.{gate,up,down}_proj`` instead of the router and the experts.
MELLUM_TENSORS = {
    "model.embed_tokens.weight": ("model.embed_tokens", "weight", "[V, d]"),
    "model.norm.weight": ("model.norm", "gamma", "[d]"),
    "lm_head.weight": ("lm_head", "kernel", "[d, V] = .T"),
    "input_layernorm.weight": ("input_layernorm", "gamma", "[d]"),
    "post_attention_layernorm.weight":
        ("post_attention_layernorm", "gamma", "[d]"),
    "self_attn.q_proj.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.k_proj.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.v_proj.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.o_proj.weight": ("self_attn", "o_proj", "[H hd, d] = .T"),
    "mlp.gate.weight": ("mlp.gate", "weight", "[d, experts] = .T, float32"),
    "mlp.experts.<e>.gate_proj.weight": ("mlp.experts", "gate", "[e] = .T"),
    "mlp.experts.<e>.up_proj.weight": ("mlp.experts", "up", "[e] = .T"),
    "mlp.experts.<e>.down_proj.weight": ("mlp.experts", "down", "[e] = .T"),
    "mlp.gate_proj.weight": ("mlp.gate_proj", "kernel", "[d, I] = .T"),
    "mlp.up_proj.weight": ("mlp.up_proj", "kernel", "[d, I] = .T"),
    "mlp.down_proj.weight": ("mlp.down_proj", "kernel", "[I, d] = .T"),
}


# The published tensor names of ``deepseek_v2`` (DeepSeek-V2-Lite: no
# ``q_a_proj`` / ``q_b_proj``, ``q_lora_rank`` null), for an importer to be
# written when a checkpoint is in the repository (none is: they are the
# family's convention and stay ASSUMED until one is).  ``{torch tensor:
# (graph node, parameter, layout)}`` per layer ``model.layers.<l>.``, beside
# the reference's tables (``benchmark/reference/deepseek_v2.py`` ``LAYER`` /
# ``program_tree``: the same map in kernel form, ``[in, out]``): a projection
# transposes.  ``kv_b_proj`` ``[H (nope + v), r]`` transposed is ``[r, H,
# nope + v]``: head ``i``'s ``U_k = kv_b[:, i, :nope]`` (absorbed into the
# query) and ``U_v = kv_b[:, i, nope:]`` (applied to the latent-wide
# output).  ``kv_a_proj_with_mqa``'s last ``rope`` columns are the ONE
# rotated key part all heads share.  Layer ``l < first_k_dense_replace`` has
# the dense ``mlp.{gate,up,down}_proj``; the others the router ``mlp.gate``,
# ``mlp.experts.<e>`` stacked into ``[E, in, out]`` and ONE module
# ``mlp.shared_experts`` of width ``n_shared_experts x moe_intermediate_size``.
DEEPSEEK_V2_TENSORS = {
    "model.embed_tokens.weight": ("model.embed_tokens", "weight", "[V, d]"),
    "model.norm.weight": ("model.norm", "gamma", "[d]"),
    "lm_head.weight": ("lm_head", "kernel", "[d, V] = .T"),
    "input_layernorm.weight": ("input_layernorm", "gamma", "[d]"),
    "post_attention_layernorm.weight":
        ("post_attention_layernorm", "gamma", "[d]"),
    "self_attn.q_proj.weight":
        ("self_attn", "q_proj", "[d, H, nope + rope] = .T reshaped"),
    "self_attn.kv_a_proj_with_mqa.weight":
        ("self_attn", "kv_a", "[d, r + rope] = .T"),
    "self_attn.kv_a_layernorm.weight": ("self_attn", "kv_norm", "[r]"),
    "self_attn.kv_b_proj.weight":
        ("self_attn", "kv_b", "[r, H, nope + v] = .T reshaped: U_k | U_v"),
    "self_attn.o_proj.weight": ("self_attn", "o_proj", "[H v, d] = .T"),
    "mlp.gate_proj.weight": ("mlp.gate_proj", "kernel", "[d, I] = .T"),
    "mlp.up_proj.weight": ("mlp.up_proj", "kernel", "[d, I] = .T"),
    "mlp.down_proj.weight": ("mlp.down_proj", "kernel", "[I, d] = .T"),
    "mlp.gate.weight": ("mlp.gate", "weight", "[d, experts] = .T, float32"),
    "mlp.experts.N.gate_proj.weight": ("mlp.experts", "gate", "[N] = .T"),
    "mlp.experts.N.up_proj.weight": ("mlp.experts", "up", "[N] = .T"),
    "mlp.experts.N.down_proj.weight": ("mlp.experts", "down", "[N] = .T"),
    "mlp.shared_experts.gate_proj.weight":
        ("mlp.shared_experts.gate_proj", "kernel", "[d, n f] = .T"),
    "mlp.shared_experts.up_proj.weight":
        ("mlp.shared_experts.up_proj", "kernel", "[d, n f] = .T"),
    "mlp.shared_experts.down_proj.weight":
        ("mlp.shared_experts.down_proj", "kernel", "[n f, d] = .T"),
}


# ``kimi_linear`` (Kimi-Linear-48B-A3B: ``serve/models/kimi_linear.py``).
# ASSUMED like the two tables above — the family's published convention, from
# memory; no checkpoint is on this machine and NO import path is written.
# Published ``model.layers.<l>.<name>`` -> (node under ``model.layers.<l>.``,
# parameter, what to do).  A layer in ``linear_attn_config.kda_layers``
# (1-based) carries the KDA tensors: its three projections go side by side
# into ONE ``qkv_proj`` kernel (q | k | v) and its three depthwise convs
# (torch ``[channels, 1, taps]``) into ONE conv ``[taps, 3 H D]``; a layer in
# ``full_attn_layers`` carries the latent tensors under the SAME
# ``self_attn.q_proj`` / ``self_attn.o_proj`` names with other shapes (keys
# marked ``@latent`` here, as in benchmark/reference/kimi_linear.py).  Layer
# ``l <= first_k_dense_replace`` has the dense ``mlp.{gate,up,down}_proj``;
# the others ``block_sparse_moe``: the router ``gate`` with its
# ``e_score_correction_bias``, the experts ``experts.<e>.{w1, w3, w2}`` (gate,
# up, down) stacked into ``[E, in, out]`` and ONE ``shared_experts`` module.
KIMI_LINEAR_TENSORS = {
    "model.embed_tokens.weight": ("model.embed_tokens", "weight", "[V, d]"),
    "model.norm.weight": ("model.norm", "gamma", "[d]"),
    "lm_head.weight": ("lm_head", "kernel", "[d, V] = .T"),
    "input_layernorm.weight": ("input_layernorm", "gamma", "[d]"),
    "post_attention_layernorm.weight":
        ("post_attention_layernorm", "gamma", "[d]"),
    "self_attn.q_proj.weight":
        ("self_attn.qkv_proj", "kernel", "[d, 3 H D] columns 0 .. H D = .T"),
    "self_attn.k_proj.weight":
        ("self_attn.qkv_proj", "kernel", "columns H D .. 2 H D = .T"),
    "self_attn.v_proj.weight":
        ("self_attn.qkv_proj", "kernel", "columns 2 H D .. 3 H D = .T"),
    "self_attn.q_conv1d.weight":
        ("self_attn.qkv_conv1d", "weight", "[taps, 3 H D] columns 0 .. H D "
         "= [:, 0, :].T"),
    "self_attn.k_conv1d.weight":
        ("self_attn.qkv_conv1d", "weight", "columns H D .. 2 H D"),
    "self_attn.v_conv1d.weight":
        ("self_attn.qkv_conv1d", "weight", "columns 2 H D .. 3 H D"),
    "self_attn.A_log": ("self_attn", "A_log", "[H] float32"),
    "self_attn.dt_bias": ("self_attn", "dt_bias", "[H D] float32"),
    "self_attn.f_a_proj.weight": ("self_attn", "f_a", "[d, D] = .T"),
    "self_attn.f_b_proj.weight": ("self_attn", "f_b", "[D, H D] = .T"),
    "self_attn.b_proj.weight": ("self_attn", "b_proj", "[d, H] = .T"),
    "self_attn.g_a_proj.weight": ("self_attn", "g_a", "[d, D] = .T"),
    "self_attn.g_b_proj.weight": ("self_attn", "g_b", "[D, H D] = .T"),
    "self_attn.o_norm.weight": ("self_attn", "o_norm", "[D]"),
    "self_attn.o_proj.weight": ("self_attn", "o_proj", "[H D, d] = .T"),
    "self_attn.q_proj@latent.weight":
        ("self_attn", "q_proj", "[d, H, nope + rope] = .T reshaped"),
    "self_attn.kv_a_proj_with_mqa.weight":
        ("self_attn", "kv_a", "[d, r + rope] = .T"),
    "self_attn.kv_a_layernorm.weight": ("self_attn", "kv_norm", "[r]"),
    "self_attn.kv_b_proj.weight":
        ("self_attn", "kv_b", "[r, H, nope + v] = .T reshaped: U_k | U_v"),
    "self_attn.o_proj@latent.weight":
        ("self_attn", "o_proj", "[H v, d] = .T"),
    "mlp.gate_proj.weight": ("mlp.gate_proj", "kernel", "[d, I] = .T"),
    "mlp.up_proj.weight": ("mlp.up_proj", "kernel", "[d, I] = .T"),
    "mlp.down_proj.weight": ("mlp.down_proj", "kernel", "[I, d] = .T"),
    "block_sparse_moe.gate.weight":
        ("block_sparse_moe.gate", "weight", "[d, experts] = .T, float32"),
    "block_sparse_moe.gate.e_score_correction_bias":
        ("block_sparse_moe.gate", "e_score_correction_bias",
         "[experts] float32"),
    "block_sparse_moe.experts.N.w1.weight":
        ("block_sparse_moe.experts", "gate", "[N] = .T"),
    "block_sparse_moe.experts.N.w3.weight":
        ("block_sparse_moe.experts", "up", "[N] = .T"),
    "block_sparse_moe.experts.N.w2.weight":
        ("block_sparse_moe.experts", "down", "[N] = .T"),
    "block_sparse_moe.shared_experts.gate_proj.weight":
        ("block_sparse_moe.shared_experts.gate_proj", "kernel",
         "[d, n f] = .T"),
    "block_sparse_moe.shared_experts.up_proj.weight":
        ("block_sparse_moe.shared_experts.up_proj", "kernel",
         "[d, n f] = .T"),
    "block_sparse_moe.shared_experts.down_proj.weight":
        ("block_sparse_moe.shared_experts.down_proj", "kernel",
         "[n f, d] = .T"),
}


# ``solar_open2`` (Solar-Open2-250B: ``serve/models/solar_open2.py``).
# ASSUMED like the tables above — the family's convention (``kimi_linear``'s
# names for the delta rule, deepseek_v3's for the mixture, whose key names the
# config carries); no checkpoint is on this machine and NO import path is
# written.  Published ``model.layers.<l>.<name>`` -> (node under
# ``model.layers.<l>.``, parameter, what to do).  A layer NOT in the 0-BASED
# ``gqa_layers`` carries the KDA tensors exactly as ``KIMI_LINEAR_TENSORS``
# maps them; a layer in it carries the attention's under the SAME
# ``self_attn.{q,k,v,o}_proj`` names with other shapes (keys marked ``@gqa``
# here, as in benchmark/reference/solar_open2.py) and the output gate's
# ``self_attn.g_proj``: q, k and v fuse per K/V head as
# ``IncMultiHeadSelfAttention``'s do.  Every layer from
# ``first_k_dense_replace`` (0) on has the router ``mlp.gate`` with its
# ``e_score_correction_bias``, ``mlp.experts.<e>`` stacked into ``[E, in,
# out]`` and ONE ``mlp.shared_experts`` module.
SOLAR_OPEN2_TENSORS = {
    **{k: v for k, v in KIMI_LINEAR_TENSORS.items()
       if not (k.startswith("block_sparse_moe.") or "@latent" in k
               or k.startswith("self_attn.kv_"))},
    "self_attn.q_proj@gqa.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.k_proj@gqa.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.v_proj@gqa.weight": ("self_attn", "qkv", "fuse_qkv"),
    "self_attn.g_proj@gqa.weight": ("self_attn", "g_proj", "[d, H hd] = .T"),
    "self_attn.o_proj@gqa.weight": ("self_attn", "o_proj", "[H hd, d] = .T"),
    "mlp.gate.weight": ("mlp.gate", "weight", "[d, experts] = .T, float32"),
    "mlp.gate.e_score_correction_bias":
        ("mlp.gate", "e_score_correction_bias", "[experts] float32"),
    "mlp.experts.<e>.gate_proj.weight": ("mlp.experts", "gate", "[e] = .T"),
    "mlp.experts.<e>.up_proj.weight": ("mlp.experts", "up", "[e] = .T"),
    "mlp.experts.<e>.down_proj.weight": ("mlp.experts", "down", "[e] = .T"),
    "mlp.shared_experts.gate_proj.weight":
        ("mlp.shared_experts.gate_proj", "kernel", "[d, n f] = .T"),
    "mlp.shared_experts.up_proj.weight":
        ("mlp.shared_experts.up_proj", "kernel", "[d, n f] = .T"),
    "mlp.shared_experts.down_proj.weight":
        ("mlp.shared_experts.down_proj", "kernel", "[n f, d] = .T"),
}


def load_hf_model(name_or_path: str):
    """Load a local HF checkpoint (config + weights + tokenizer if present).

    Returns (state_dict, ServeModelConfig, tokenizer_or_None).  Network
    download is NOT attempted (``local_files_only=True``) — ship checkpoints
    to disk first, as the reference's weight-export flow does.
    """
    import transformers

    hf_cfg = transformers.AutoConfig.from_pretrained(
        name_or_path, local_files_only=True
    )
    model = transformers.AutoModelForCausalLM.from_pretrained(
        name_or_path, local_files_only=True, torch_dtype="float32"
    )
    tok = None
    try:
        tok = transformers.AutoTokenizer.from_pretrained(
            name_or_path, local_files_only=True
        )
    except Exception:
        pass
    return model.state_dict(), ServeModelConfig.from_hf_config(hf_cfg), tok


def place_params(params, plan):
    """device_put converted params according to the plan's shardings."""
    mesh = plan.mesh
    if mesh.size == 1:
        return params
    out = {}
    for node, sub in params.items():
        shs = plan.param_shardings.get(node, {})
        out[node] = {
            k: jax.device_put(v, shs[k].named_sharding(mesh))
            if k in shs
            else v
            for k, v in sub.items()
        }
    return out
