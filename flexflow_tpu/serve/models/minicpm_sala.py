"""MiniCPM-SALA (``model_type: minicpm_sala``) serve graph builder.

The LLaMA skeleton — RMS norms, SwiGLU MLP, no bias, untied head — with two
kinds of mixer, ``mixer_types[i]`` says which layer has which:

* ``minicpm4``: InfLLM-v2 sparse attention
  (:class:`~flexflow_tpu.serve.hybrid_ops.SparseBlockAttention`): grouped
  queries on ``num_key_value_heads`` K/V heads, no RoPE, an output gate; each
  row chooses the blocks of its cache it reads by an index of compressed keys.
* ``lightning-attn``: Lightning linear attention
  (:class:`~flexflow_tpu.serve.hybrid_ops.LightningAttention`): ``lightning_nh``
  heads, QK-norm, RoPE, a matrix state per head, an output norm and gate.

muP (MiniCPM): the embedding is scaled by ``scale_emb``, each block's output
by ``scale_depth / sqrt(mup_denominator)`` — the PUBLISHED depth (32),
whatever the depth a deployment holds: a pipeline stage's layers are the
model's — and the final hidden state by ``dim_model_base / hidden_size``.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from .base import ServeModelConfig, register_model

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def layer_kind(cfg: ServeModelConfig, i: int) -> str:
    """What layer ``i`` mixes with: ``mixer_types[i]``, nothing else."""
    kind = cfg.mixer_types[i]
    if kind not in (SPARSE, LINEAR):
        raise ValueError(f"mixer_types[{i}] = {kind!r}: not a mixer this "
                         f"builder knows ({SPARSE}, {LINEAR})")
    return kind


def residual_scale(cfg: ServeModelConfig) -> float:
    depth = cfg.mup_denominator or cfg.num_hidden_layers
    return cfg.scale_depth / math.sqrt(depth)


@register_model("minicpm_sala")
def build_minicpm_sala(ff, cfg: ServeModelConfig, max_tokens: int):
    if not cfg.mixer_types or len(cfg.mixer_types) != cfg.num_hidden_layers:
        raise ValueError("mixer_types names every layer's mixer: "
                         f"{cfg.num_hidden_layers} layers, "
                         f"{len(cfg.mixer_types or ())} entries")
    if cfg.attn_use_rope:
        raise ValueError("attn_use_rope: the sparse layers' selection "
                         "compresses unrotated keys")
    d, eps, scale = cfg.hidden_size, cfg.rms_norm_eps, residual_scale(cfg)
    tokens = ff.create_tensor((max_tokens,), dtype=jnp.int32)
    x = ff.embedding(tokens, cfg.vocab_size, d, name="model.embed_tokens",
                     dtype=jnp.dtype(cfg.dtype))
    x = ff.scalar_multiply(x, float(cfg.scale_emb),
                           name="model.embed_tokens.scale")
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        a = ff.rms_norm(x, eps=eps, name=f"{p}.input_layernorm")
        if layer_kind(cfg, i) == SPARSE:
            h = ff.sparse_block_attention(
                a, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                kernel_size=cfg.sparse_kernel_size,
                kernel_stride=cfg.sparse_kernel_stride,
                block_size=cfg.sparse_block_size, topk=cfg.sparse_topk,
                window=cfg.sparse_window_size,
                init_blocks=cfg.sparse_init_blocks,
                dense_len=cfg.sparse_dense_len,
                output_gate=cfg.attn_use_output_gate,
                name=f"{p}.self_attn")
        else:
            h = ff.lightning_attention(
                a, d, cfg.lightning_nh or cfg.num_attention_heads,
                cfg.lightning_head_dim or cfg.hdim,
                rope_theta=cfg.rope_theta, use_rope=cfg.lightning_use_rope,
                qk_norm=cfg.qk_norm, output_norm=cfg.use_output_norm,
                output_gate=cfg.use_output_gate, eps=eps,
                name=f"{p}.self_attn")
        h = ff.scalar_multiply(h, scale, name=f"{p}.mixer_scale")
        x = ff.add(x, h, name=f"{p}.mixer_residual")
        a = ff.rms_norm(x, eps=eps, name=f"{p}.post_attention_layernorm")
        gate = ff.dense(a, cfg.intermediate_size, use_bias=False,
                        name=f"{p}.mlp.gate_proj")
        up = ff.dense(a, cfg.intermediate_size, use_bias=False,
                      name=f"{p}.mlp.up_proj")
        h = ff.sigmoid_silu_multi(gate, up, name=f"{p}.mlp.act")
        h = ff.dense(h, d, use_bias=False, name=f"{p}.mlp.down_proj")
        h = ff.scalar_multiply(h, scale, name=f"{p}.mlp_scale")
        x = ff.add(x, h, name=f"{p}.mlp_residual")
    x = ff.rms_norm(x, eps=eps, name="model.norm")
    x = ff.scalar_multiply(x, (cfg.dim_model_base or d) / d,
                           name="model.norm.scale")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
