"""DeepSeek-V2 (``model_type: deepseek_v2``; the Lite model: no query
down-projection) serve graph builder.

Sequential pre-norm RMSNorm blocks: ``x <- x + Attn(RMS(x))``, then ``x <- x
+ FFN_l(RMS(x))``.

* attention: multi-head LATENT attention (``LatentAttention``,
  ``serve/hybrid_ops.py``) — per position one normed latent of
  ``kv_lora_rank`` values and one rotated key part of ``qk_rope_head_dim``,
  shared by all heads; queries of ``qk_nope_head_dim`` + ``qk_rope_head_dim``
  a head straight from the stream (``q_lora_rank`` null), values of
  ``v_head_dim``; rotary on interleaved pairs with YaRN's frequencies
  (``rope_scaling``) on the rotary part only; no bias.
* layers below ``first_k_dense_replace``: a dense gated MLP ``down(silu(gate
  n) * up n)`` of width ``intermediate_size``; the others (``moe_layer_freq``
  1): a float32 router that scores by softmax over ``n_routed_experts``
  (``scoring_func``), the ``num_experts_per_tok`` largest chosen
  (``topk_method`` greedy: no group limit) with their scores AS THEY ARE
  (``norm_topk_prob`` false) times ``routed_scaling_factor``; gated experts
  of width ``moe_intermediate_size`` as a dropless grouped-GEMM layer
  (``MoERouter`` .. ``MoECombine``, ``serve/ssd_moe_ops.py``); beside them
  ``n_shared_experts`` shared experts whose outputs are SUMMED — ONE gated
  MLP of width ``n_shared_experts x moe_intermediate_size`` (three
  ``SharedExpertLinear`` nodes: the published tensors are already side by
  side along the width).
* a final RMSNorm and an untied head.

``router_num_experts`` / ``expert_share_index`` let a chip hold a share of
the routed experts as in ``cohere2_moe``; unset, the graph holds them all.
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model


def is_dense(cfg: ServeModelConfig, i: int) -> bool:
    """Whether layer ``i``'s FFN is the dense MLP (the family's rule: a
    mixture from ``first_k_dense_replace`` on, every ``moe_layer_freq``-th
    layer)."""
    return not (cfg.n_routed_experts and i >= cfg.first_k_dense_replace
                and i % cfg.moe_layer_freq == 0)


def _gated_mlp(ff, n, width, d, prefix, dense):
    h = ff.sigmoid_silu_multi(dense(n, width, name=f"{prefix}.gate_proj"),
                              dense(n, width, name=f"{prefix}.up_proj"),
                              name=f"{prefix}.act")
    return dense(h, d, name=f"{prefix}.down_proj")


@register_model("deepseek_v2")
def build_deepseek_v2(ff, cfg: ServeModelConfig, max_tokens: int):
    if cfg.q_lora_rank:
        raise ValueError("deepseek_v2 here projects queries straight from "
                         "the stream (q_lora_rank null): a query "
                         "down-projection is not in the latent operator")
    if cfg.topk_method != "greedy" or cfg.n_group != 1 or cfg.topk_group != 1:
        raise ValueError("deepseek_v2 here routes greedily over all experts "
                         "(topk_method 'greedy', n_group 1, topk_group 1): "
                         "a group-limited choice is not in MoERouter")
    if cfg.scoring_func not in ("softmax", "sigmoid"):
        raise ValueError(f"scoring_func {cfg.scoring_func!r}: 'softmax' or "
                         "'sigmoid'")
    for key in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim"):
        if not getattr(cfg, key):
            raise ValueError(f"latent attention needs {key}")
    d, eps = cfg.hidden_size, cfg.rms_norm_eps
    held = cfg.n_routed_experts
    scored = cfg.router_num_experts or held
    held_lo = cfg.expert_share_index * held
    if held_lo + held > scored:
        raise ValueError(f"experts {held_lo}..{held_lo + held - 1} are not "
                         f"among the router's {scored}")
    plain = lambda x, width, name: ff.dense(x, width, use_bias=False,
                                            name=name)
    tokens = ff.create_tensor((max_tokens,), dtype=jnp.int32)
    x = ff.embedding(tokens, cfg.vocab_size, d, name="model.embed_tokens",
                     dtype=jnp.dtype(cfg.dtype))
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        n = ff.rms_norm(x, eps=eps, name=f"{p}.input_layernorm")
        a = ff.latent_attention(
            n, d, cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
            rope_theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling,
            eps=eps, name=f"{p}.self_attn")
        x = ff.add(x, a, name=f"{p}.attn_residual")
        n = ff.rms_norm(x, eps=eps, name=f"{p}.post_attention_layernorm")
        if is_dense(cfg, i):
            m = _gated_mlp(ff, n, cfg.intermediate_size, d, f"{p}.mlp", plain)
        else:
            f = cfg.moe_intermediate_size
            ids, w = ff.moe_router(
                n, scored, cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob, bias=False,
                scoring=cfg.scoring_func, name=f"{p}.mlp.gate")
            xs, sizes, order = ff.moe_dispatch(n, ids, held, held_lo,
                                               name=f"{p}.mlp.dispatch")
            ys = ff.moe_experts(xs, sizes, held, f, form="swiglu",
                                num_scored=scored,
                                name=f"{p}.mlp.experts")
            m = ff.moe_combine(ys, order, ids, w, held, held_lo,
                               dtype=n.dtype, name=f"{p}.mlp.combine")
            if cfg.n_shared_experts:
                shared = _gated_mlp(
                    ff, n, cfg.n_shared_experts * f, d,
                    f"{p}.mlp.shared_experts",
                    lambda x, width, name: ff.shared_expert_dense(
                        x, width, name=name))
                m = ff.add(m, shared, name=f"{p}.mlp.sum")
        x = ff.add(x, m, name=f"{p}.residual")
    x = ff.rms_norm(x, eps=eps, name="model.norm")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
