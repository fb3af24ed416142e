"""EvaByte (``model_type: evabyte``) serve graph builder.

A byte-level decoder (vocabulary 320) on the LLaMA skeleton — RoPE, SwiGLU
MLP, fused residual RMS norms, no bias — whose attention is EVA (Zheng et
al., "Efficient Attention via Control Variates"): exact inside the query's
own ``window_size`` positions, one learned summary per ``chunk_size``
positions of every earlier window, one softmax over both
(:class:`~flexflow_tpu.serve.hybrid_ops.EvaAttention`, whose per-slot cache
compacts itself at each window's end).  What else departs from LLaMA, each by
a published key: the norms scale by ``1 + gamma`` (``norm_add_unit_offset``),
the residual stream is float32 whatever the compute type (``fp32_skip_add``:
every norm reads float32 and hands the projections the compute type), and
the logits are float32 (``fp32_logits``).

``num_pred_heads`` heads share the trunk: head 0 predicts the next byte, heads
1 .. 7 draft the bytes after it for the model's self-speculative decoding.
This graph holds head 0 — next-byte decoding is the model's own
non-speculative path, token for token; serving the drafting heads is
ROADMAP B-I 6's.
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model


@register_model("evabyte")
def build_evabyte(ff, cfg: ServeModelConfig, max_tokens: int):
    dt = jnp.dtype(cfg.dtype)
    norm = dict(eps=cfg.rms_norm_eps, unit_offset=cfg.norm_add_unit_offset,
                out_dtype=dt)
    tokens = ff.create_tensor((max_tokens,), dtype=jnp.int32)
    x = ff.embedding(tokens, cfg.vocab_size, cfg.hidden_size,
                     name="model.embed_tokens", dtype=dt)
    residual = ff.cast(x, jnp.float32, name="model.embed_tokens.float")
    mlp_out = None
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        if i == 0:
            attn_in = ff.rms_norm(residual, name=f"{p}.input_layernorm",
                                  **norm)
        else:
            residual, attn_in = ff.residual_rms_norm(
                mlp_out, residual, name=f"{p}.input_layernorm", **norm)
        attn = ff.eva_attention(
            attn_in, cfg.hidden_size, cfg.num_attention_heads, cfg.hdim,
            cfg.window_size, cfg.chunk_size, rope_theta=cfg.rope_theta,
            name=f"{p}.self_attn")
        residual, mlp_in = ff.residual_rms_norm(
            attn, residual, name=f"{p}.post_attention_layernorm", **norm)
        gate = ff.dense(mlp_in, cfg.intermediate_size, use_bias=False,
                        name=f"{p}.mlp.gate_proj")
        up = ff.dense(mlp_in, cfg.intermediate_size, use_bias=False,
                      name=f"{p}.mlp.up_proj")
        act = ff.sigmoid_silu_multi(gate, up, name=f"{p}.mlp.act")
        mlp_out = ff.dense(act, cfg.hidden_size, use_bias=False,
                           name=f"{p}.mlp.down_proj")
    _, normed = ff.residual_rms_norm(mlp_out, residual, name="model.norm",
                                     **norm)
    return ff.dense(normed, cfg.vocab_size, use_bias=False, name="lm_head",
                    dtype=jnp.float32)
