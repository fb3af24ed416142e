"""Cohere Command A+ (``model_type: cohere2_moe``) serve graph builder.

Every layer is ONE parallel block (``use_parallel_block``): a bias-free,
mean-subtracting LayerNorm feeds the attention AND the mixture, and both add
to the stream — ``x' = x + Attn_i(n) + MoE_i(n)``, ``n = LN_i(x)``.

* ``layer_types[i] == "sliding_attention"``: plain grouped-query attention
  over the last ``sliding_window`` positions, rotary at ``rope_theta`` on
  interleaved pairs (``position_embedding_type: rope_gptj``), its cache a
  ring (``SlidingWindowAttention``, ``serve/hybrid_ops.py``);
  ``"full_attention"``: the same projections with NO positional term over a
  full-length cache (``IncMultiHeadSelfAttention``).  No bias, no q/k norm.
* the mixture: a float32 sigmoid router over ``router_num_experts`` experts,
  the ``num_experts_per_tok`` largest chosen and their scores normalised to
  sum 1 (no scaling factor, no correction bias); gated experts
  ``down(silu(gate n) * up n)`` of width ``intermediate_size`` as a dropless
  grouped-GEMM layer (``MoERouter`` .. ``MoECombine``,
  ``serve/ssd_moe_ops.py``); ``num_shared_experts`` shared experts of the
  same form and width whose outputs are AVERAGED
  (``shared_expert_combination_strategy``).  The shared experts run side by
  side as ONE gated MLP of width ``num_shared_experts x intermediate_size``
  — three ``SharedExpertLinear`` nodes whose kernels are the published
  tensors concatenated along the width — and a scalar ``1 / n`` on the down
  projection's output: the same algebra as the mean of their outputs.
* a final LayerNorm and a head TIED to the embedding, its logits times
  ``logit_scale``.  The graph keeps the head's matrix as a node of its own
  (``lm_head``: the embedding transposed; a loader writes both from the one
  published tensor), and ``logit_scale`` — 1 as published, then no node —
  multiplies the normed rows in front of it, so that the head stays the
  ``Linear`` that produces the logits (``mark_gated_lm_head``).

A chip may hold a SHARE of each layer, as expert, tensor and vocabulary
parallelism would leave it: ``num_experts`` is what this graph holds
(published ids from ``expert_share_index x num_experts``) of the
``router_num_experts`` the router scores; ``num_attention_heads`` /
``num_key_value_heads`` the heads it holds (whole K/V groups);
``vocab_size`` its rows of the embedding.  The router, the norms and the
shared experts are whole.  What the absent experts and heads would add is
not in this graph, nor is the exchange that would bring it.
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model

SLIDING, FULL = "sliding_attention", "full_attention"


def layer_kind(cfg: ServeModelConfig, i: int) -> str:
    """What attention layer ``i`` has: ``layer_types[i]``, nothing else."""
    kind = cfg.layer_types[i]
    if kind not in (SLIDING, FULL):
        raise ValueError(f"layer_types[{i}] = {kind!r}: not an attention "
                         f"this builder knows ({SLIDING}, {FULL})")
    return kind


@register_model("cohere2_moe")
def build_cohere2_moe(ff, cfg: ServeModelConfig, max_tokens: int):
    kinds = cfg.layer_types or ()
    if len(kinds) != cfg.num_hidden_layers:
        raise ValueError("layer_types names every layer's attention: "
                         f"{cfg.num_hidden_layers} layers, {len(kinds)} "
                         "entries")
    if not cfg.use_parallel_block or cfg.first_k_dense_replace:
        raise ValueError("cohere2_moe here is the parallel block with a "
                         "mixture in every layer (use_parallel_block true, "
                         "first_k_dense_replace 0)")
    if cfg.shared_expert_combination_strategy not in ("average", "sum"):
        raise ValueError("shared_expert_combination_strategy "
                         f"{cfg.shared_expert_combination_strategy!r}: "
                         "'average' or 'sum'")
    if SLIDING in kinds and not cfg.sliding_window:
        raise ValueError("a sliding_attention layer needs sliding_window")
    d, eps, f = cfg.hidden_size, cfg.layer_norm_eps, cfg.intermediate_size
    held = cfg.num_experts
    scored = cfg.router_num_experts or held
    held_lo = cfg.expert_share_index * held
    if held_lo + held > scored:
        raise ValueError(f"experts {held_lo}..{held_lo + held - 1} are not "
                         f"among the router's {scored}")
    shared = cfg.num_shared_experts
    tokens = ff.create_tensor((max_tokens,), dtype=jnp.int32)
    x = ff.embedding(tokens, cfg.vocab_size, d, name="model.embed_tokens",
                     dtype=jnp.dtype(cfg.dtype))
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        n = ff.layer_norm(x, eps=eps, use_bias=False,
                          name=f"{p}.input_layernorm")
        if layer_kind(cfg, i) == SLIDING:
            a = ff.sliding_window_attention(
                n, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                cfg.sliding_window, rope_theta=cfg.rope_theta,
                rope_interleaved=cfg.position_embedding_type == "rope_gptj",
                name=f"{p}.self_attn")
        else:
            a = ff.inc_multihead_self_attention(
                n, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                rotary_embedding=False, use_bias=False,
                name=f"{p}.self_attn")
        ids, w = ff.moe_router(n, scored, cfg.num_experts_per_tok,
                               norm_topk=cfg.norm_topk_prob, bias=False,
                               name=f"{p}.mlp.gate")
        xs, sizes, order = ff.moe_dispatch(n, ids, held, held_lo,
                                           name=f"{p}.mlp.dispatch")
        ys = ff.moe_experts(xs, sizes, held, f, form="swiglu",
                            num_scored=scored, name=f"{p}.mlp.experts")
        m = ff.moe_combine(ys, order, ids, w, held, held_lo, dtype=n.dtype,
                           name=f"{p}.mlp.combine")
        if shared:
            s = f"{p}.mlp.shared_experts"
            h = ff.sigmoid_silu_multi(
                ff.shared_expert_dense(n, shared * f, name=f"{s}.gate_proj"),
                ff.shared_expert_dense(n, shared * f, name=f"{s}.up_proj"),
                name=f"{s}.act")
            h = ff.shared_expert_dense(h, d, name=f"{s}.down_proj")
            if cfg.shared_expert_combination_strategy == "average":
                h = ff.scalar_multiply(h, 1.0 / shared, name=f"{s}.mean")
            m = ff.add(m, h, name=f"{p}.mlp.sum")
        x = ff.add(ff.add(x, a, name=f"{p}.attn_residual"), m,
                   name=f"{p}.residual")
    x = ff.layer_norm(x, eps=eps, use_bias=False, name="model.norm")
    if cfg.logit_scale != 1.0:
        x = ff.scalar_multiply(x, cfg.logit_scale, name="model.logit_scale")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
