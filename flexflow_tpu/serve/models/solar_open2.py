"""Upstage Solar Open 2 (``model_type: solar_open2``; Solar-Open2-250B) serve
graph builder.

Sequential pre-norm RMSNorm blocks: ``x <- x + Mix_i(RMS(x))``, then ``x <- x
+ FFN_i(RMS(x))``.  No bias and NO positional term anywhere (``use_rope``
false; ``rope_theta`` and ``partial_rotary_factor`` are inert): the
delta-rule layers carry position.

* ``gqa_layers`` — a 0-BASED list (0 is in it; ``kimi_linear``'s two lists
  count from 1) — names the layers that are softmax grouped-query attention
  (``IncMultiHeadSelfAttention``: ``num_attention_heads`` query heads of
  ``head_dim`` on ``num_key_value_heads`` K/V heads, a plain full-length K/V
  cache, ``rotary_embedding=False``) behind an OUTPUT GATE (``use_gqa_gate``:
  ``o * sigmoid(n W_g)`` before ``W_o``, one gate a channel).  Every OTHER
  layer is Kimi Delta Attention (``KimiDeltaAttention``, ``serve/
  hybrid_ops.py``) at ``linear_attn_config``'s ``num_heads`` heads of
  ``head_dim`` — which holds NO layer list here — behind ONE bias-free
  depthwise conv of ``short_conv_kernel_size`` with SiLU over the fused ``q |
  k | v`` projection, with ``beta`` in (0, 2) where ``kda_allow_neg_eigval``
  says so.  ``num_kv_heads`` null: k and v have ``num_heads`` heads;
  ``kda_use_full_proj`` false: the decay's and the output gate's projections
  are the low-rank pairs the operator holds.
* every layer from ``first_k_dense_replace`` on (0: all) is a mixture: a
  float32 router that scores by sigmoid over all the experts, the
  ``num_experts_per_tok`` largest of score + ``e_score_correction_bias``
  chosen (no group limit), normalised to sum 1 (``norm_topk_prob``) times
  ``routed_scaling_factor``; gated experts of width ``moe_intermediate_size``
  as a dropless grouped-GEMM layer (``MoERouter`` .. ``MoECombine``);
  ``n_shared_experts`` shared experts as ONE gated MLP, unweighted.  Below
  it: a dense gated MLP of ``intermediate_size``.
* a final RMSNorm and an untied head.

``n_routed_experts`` is what THIS graph holds: share ``expert_share_index``
of the ``router_num_experts`` the router scores (unset: all), as in the other
mixture builders (``models/kimi_linear.py``'s header lists the keys the two
delta-rule builders read under different names).
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model
from .deepseek_v2 import _gated_mlp

KDA, GQA = "kda", "gqa"
LINEAR_KEYS = ("num_heads", "head_dim", "short_conv_kernel_size")


def layer_kinds(cfg: ServeModelConfig):
    """``"gqa"`` or ``"kda"`` per layer: ``gqa_layers`` counts from 0; a
    listed layer the model does not have is refused, and so is a list that
    disagrees with ``gqa_interval`` (a 1-based reading of it would)."""
    n = cfg.num_hidden_layers
    full = tuple(cfg.gqa_layers or ())
    outside = [i for i in full if not 0 <= i < n]
    if outside or len(set(full)) != len(full):
        raise ValueError(f"gqa_layers {list(full)} names layers 0-based; "
                         f"{outside or 'a repeated entry'} is not among the "
                         f"{n} layers")
    if cfg.gqa_interval is not None:
        step = cfg.gqa_interval + 1
        if sorted(full) != list(range(0, n, step)):
            raise ValueError(
                f"gqa_layers {list(full)} is not every {step}th layer from 0 "
                f"(gqa_interval {cfg.gqa_interval}: {cfg.gqa_interval} "
                "delta-rule layers after each attention layer)")
    return [GQA if i in full else KDA for i in range(n)]


@register_model("solar_open2")
def build_solar_open2(ff, cfg: ServeModelConfig, max_tokens: int):
    lists = cfg.linear_attn_config or {}
    for key in LINEAR_KEYS:
        if key not in lists:
            raise ValueError(f"solar_open2 needs linear_attn_config.{key}")
    if lists.get("num_kv_heads") not in (None, lists["num_heads"]):
        raise ValueError("linear_attn_config.num_kv_heads "
                         f"{lists['num_kv_heads']}: the delta rule here has "
                         "one key and one value a head (null)")
    if cfg.use_rope:
        raise ValueError("solar_open2 here runs without a positional term "
                         "(use_rope false)")
    if cfg.kda_use_full_proj:
        raise ValueError("kda_use_full_proj: KimiDeltaAttention's decay and "
                         "gate projections are low-rank pairs")
    if cfg.n_group != 1 or cfg.topk_group != 1:
        raise ValueError("solar_open2 here routes over all experts: a "
                         "group-limited choice is not in MoERouter")
    kinds = layer_kinds(cfg)
    d, eps = cfg.hidden_size, cfg.rms_norm_eps
    heads, hd = lists["num_heads"], lists["head_dim"]
    held = cfg.n_routed_experts
    scored = cfg.router_num_experts or held
    held_lo = cfg.expert_share_index * held
    if cfg.first_k_dense_replace < cfg.num_hidden_layers and not (
            held and cfg.moe_intermediate_size):
        raise ValueError("a mixture layer needs n_routed_experts and "
                         "moe_intermediate_size")
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
        if kinds[i] == KDA:
            qkv = plain(n, 3 * heads * hd, f"{p}.self_attn.qkv_proj")
            qkv = ff.causal_conv1d(qkv, lists["short_conv_kernel_size"],
                                   bias=False,
                                   name=f"{p}.self_attn.qkv_conv1d")
            a = ff.kimi_delta_attention(
                qkv, n, d, heads, hd, eps=eps,
                allow_neg_eigval=cfg.kda_allow_neg_eigval,
                name=f"{p}.self_attn")
        else:
            a = ff.inc_multihead_self_attention(
                n, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                rotary_embedding=False, use_bias=False,
                gate="elementwise" if cfg.use_gqa_gate else None,
                name=f"{p}.self_attn")
        x = ff.add(x, a, name=f"{p}.attn_residual")
        n = ff.rms_norm(x, eps=eps, name=f"{p}.post_attention_layernorm")
        if i < cfg.first_k_dense_replace:
            m = _gated_mlp(ff, n, cfg.intermediate_size, d, f"{p}.mlp", plain)
        else:
            f = cfg.moe_intermediate_size
            ids, w = ff.moe_router(n, scored, cfg.num_experts_per_tok,
                                   scaling=cfg.routed_scaling_factor,
                                   norm_topk=cfg.norm_topk_prob,
                                   name=f"{p}.mlp.gate")
            xs, sizes, order = ff.moe_dispatch(n, ids, held, held_lo,
                                               name=f"{p}.mlp.dispatch")
            ys = ff.moe_experts(xs, sizes, held, f, form="swiglu",
                                num_scored=scored, name=f"{p}.mlp.experts")
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
