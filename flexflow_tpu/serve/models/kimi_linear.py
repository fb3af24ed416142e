"""Kimi Linear (``model_type: kimi_linear``; Kimi-Linear-48B-A3B) serve graph
builder.

Sequential pre-norm RMSNorm blocks: ``x <- x + Mix_l(RMS(x))``, then ``x <- x
+ FFN_l(RMS(x))``.

* ``linear_attn_config`` names every layer's mixer by two 1-BASED lists (27
  of 27 layers is in ``full_attn_layers``): ``kda_layers`` — Kimi Delta
  Attention (``KimiDeltaAttention``, ``serve/hybrid_ops.py``: ``num_heads``
  heads of ``head_dim``, a gated delta rule with a per-channel decay over a
  ``head_dim x head_dim`` float32 state a head) behind ONE bias-free
  depthwise conv of ``short_conv_kernel_size`` with SiLU over the fused ``q
  | k | v`` projection (the three published convs side by side: a depthwise
  conv treats each channel alone) — and ``full_attn_layers`` — multi-head
  LATENT attention (``LatentAttention``) with NO rotation of either part
  (``mla_use_nope``; ``rope_scaling`` null): the delta-rule layers carry
  position.  No bias anywhere; no query down-projection (``q_lora_rank``
  null).
* layers below ``first_k_dense_replace``: a dense gated MLP ``down(silu(gate
  n) * up n)`` of width ``intermediate_size``; the others (``moe_layer_freq``
  1): a float32 router that scores by sigmoid over all the experts
  (``moe_router_activation_func``), the ``num_experts_per_token`` largest of
  score + ``e_score_correction_bias`` chosen (``num_expert_group`` 1,
  ``topk_group`` 1: no group limit), their scores normalised to sum 1
  (``moe_renormalize``) times ``routed_scaling_factor``; gated experts of
  width ``moe_intermediate_size`` as a dropless grouped-GEMM layer
  (``MoERouter`` .. ``MoECombine``, ``serve/ssd_moe_ops.py``); beside them
  ``num_shared_experts`` shared experts — ONE gated MLP of width
  ``num_shared_experts x moe_intermediate_size`` (three
  ``SharedExpertLinear`` nodes), unweighted.
* a final RMSNorm and an untied head.

``num_experts`` is what THIS graph holds: share ``expert_share_index`` of the
``router_num_experts`` the router scores (unset: all), as in ``cohere2_moe``.

``solar_open2`` (``models/solar_open2.py``) builds the same delta-rule
operator from OTHER keys: there ``linear_attn_config`` holds no layer list
and a 0-BASED top-level ``gqa_layers`` names the full layers (here two
1-BASED lists inside ``linear_attn_config`` name every layer); there the
mixture reads ``n_routed_experts`` / ``n_shared_experts`` /
``num_experts_per_tok`` / ``norm_topk_prob`` (here ``num_experts`` /
``num_shared_experts`` / ``num_experts_per_token`` / ``moe_renormalize``),
and ``kda_allow_neg_eigval`` doubles ``beta`` (here it lies in (0, 1)).
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model
from .deepseek_v2 import _gated_mlp

KDA, LATENT = "kda", "latent"


def layer_kind(cfg: ServeModelConfig, i: int) -> str:
    """What layer ``i`` (0-based, as the graph counts) mixes with: the list
    of ``linear_attn_config`` that holds ``i + 1`` — the config counts from
    1."""
    lists = cfg.linear_attn_config or {}
    kda = (i + 1) in lists.get("kda_layers", ())
    full = (i + 1) in lists.get("full_attn_layers", ())
    if kda == full:
        raise ValueError(f"layer {i + 1} is in "
                         f"{'both' if kda else 'neither'} of "
                         "linear_attn_config's kda_layers and "
                         "full_attn_layers: each layer is in exactly one")
    return KDA if kda else LATENT


def is_dense(cfg: ServeModelConfig, i: int) -> bool:
    """Whether layer ``i``'s FFN is the dense MLP (the family's rule)."""
    return not (cfg.num_experts and i >= cfg.first_k_dense_replace
                and i % cfg.moe_layer_freq == 0)


@register_model("kimi_linear")
def build_kimi_linear(ff, cfg: ServeModelConfig, max_tokens: int):
    lists = cfg.linear_attn_config or {}
    for key in ("kda_layers", "full_attn_layers", "num_heads", "head_dim",
                "short_conv_kernel_size"):
        if key not in lists:
            raise ValueError(f"kimi_linear needs linear_attn_config.{key}")
    if cfg.q_lora_rank:
        raise ValueError("kimi_linear here projects queries straight from "
                         "the stream (q_lora_rank null): a query "
                         "down-projection is not in the latent operator")
    if not cfg.mla_use_nope or cfg.rope_scaling:
        raise ValueError("kimi_linear here runs its latent layers without "
                         "a positional term (mla_use_nope true, "
                         "rope_scaling null)")
    if cfg.num_expert_group != 1 or cfg.topk_group != 1:
        raise ValueError("kimi_linear here routes over all experts "
                         "(num_expert_group 1, topk_group 1): a "
                         "group-limited choice is not in MoERouter")
    if cfg.moe_router_activation_func != "sigmoid":
        raise ValueError("moe_router_activation_func "
                         f"{cfg.moe_router_activation_func!r}: 'sigmoid'")
    for key in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim"):
        if not getattr(cfg, key):
            raise ValueError(f"latent attention needs {key}")
    d, eps = cfg.hidden_size, cfg.rms_norm_eps
    heads, hd = lists["num_heads"], lists["head_dim"]
    held = cfg.num_experts
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
        if layer_kind(cfg, i) == KDA:
            qkv = plain(n, 3 * heads * hd, f"{p}.self_attn.qkv_proj")
            qkv = ff.causal_conv1d(qkv, lists["short_conv_kernel_size"],
                                   bias=False,
                                   name=f"{p}.self_attn.qkv_conv1d")
            a = ff.kimi_delta_attention(qkv, n, d, heads, hd, eps=eps,
                                        name=f"{p}.self_attn")
        else:
            a = ff.latent_attention(
                n, d, cfg.num_attention_heads, cfg.qk_nope_head_dim,
                cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank,
                rope_theta=cfg.rope_theta, eps=eps, use_rope=False,
                name=f"{p}.self_attn")
        x = ff.add(x, a, name=f"{p}.attn_residual")
        n = ff.rms_norm(x, eps=eps, name=f"{p}.post_attention_layernorm")
        if is_dense(cfg, i):
            m = _gated_mlp(ff, n, cfg.intermediate_size, d, f"{p}.mlp", plain)
        else:
            f, moe = cfg.moe_intermediate_size, f"{p}.block_sparse_moe"
            ids, w = ff.moe_router(
                n, scored, cfg.num_experts_per_token,
                scaling=cfg.routed_scaling_factor,
                norm_topk=cfg.moe_renormalize, name=f"{moe}.gate")
            xs, sizes, order = ff.moe_dispatch(n, ids, held, held_lo,
                                               name=f"{moe}.dispatch")
            ys = ff.moe_experts(xs, sizes, held, f, form="swiglu",
                                num_scored=scored,
                                name=f"{moe}.experts")
            m = ff.moe_combine(ys, order, ids, w, held, held_lo,
                               dtype=n.dtype, name=f"{moe}.combine")
            if cfg.num_shared_experts:
                shared = _gated_mlp(
                    ff, n, cfg.num_shared_experts * f, d,
                    f"{moe}.shared_experts",
                    lambda x, width, name: ff.shared_expert_dense(
                        x, width, name=name))
                m = ff.add(m, shared, name=f"{moe}.sum")
        x = ff.add(x, m, name=f"{p}.residual")
    x = ff.rms_norm(x, eps=eps, name="model.norm")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
