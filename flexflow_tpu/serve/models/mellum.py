"""JetBrains Mellum 2 (``model_type: mellum``) serve graph builder.

Sequential pre-norm RMSNorm blocks: ``x <- x + Attn_i(RMS(x))``, then ``x <-
x + FFN_i(RMS(x))``.

* attention: plain grouped-query attention, no bias, no q/k norm, rotary
  over the whole head on half-against-half pairs ``(j, j + D / 2)`` under
  the parameter set ``rope_parameters`` holds FOR THE LAYER'S KIND:
  ``layer_types[i] == "sliding_attention"``: the last ``sliding_window``
  positions, its cache a ring (``SlidingWindowAttention``,
  ``serve/hybrid_ops.py``), plain ``rope_theta``; ``"full_attention"``: a
  full-length cache (``IncMultiHeadSelfAttention``) under YaRN — its
  frequencies and the STATED ``attention_factor`` on cos and sin
  (``rope_type: yarn``), or plain rotary where its set says ``default``.
* ``mlp_layer_types[i] == "sparse"``: a float32 router that scores by
  softmax over ``num_experts``, the ``num_experts_per_tok`` largest chosen
  and renormalised to sum 1 (``norm_topk_prob``), no bias, no scaling
  factor, no shared expert; gated experts ``down(silu(gate n) * up n)`` of
  width ``moe_intermediate_size`` as a dropless grouped-GEMM layer
  (``MoERouter`` .. ``MoECombine``, ``serve/ssd_moe_ops.py``);
  ``"dense"``: one gated MLP of width ``intermediate_size`` (the published
  list has none).
* a final RMSNorm and an untied head.

``router_num_experts`` / ``expert_share_index`` let a chip hold a share of
the routed experts as in ``cohere2_moe``; unset, the graph holds them all.
The multi-token-prediction head the model card mentions has no key in the
configuration and is not built.
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model
from .cohere2_moe import SLIDING, layer_kind
from .deepseek_v2 import _gated_mlp

SPARSE, DENSE = "sparse", "dense"


def rope_of(cfg: ServeModelConfig, kind: str):
    """``(theta, rope_scaling or None)`` of the layers of ``kind``, from
    ``rope_parameters[kind]``; a kind without an entry is refused."""
    sets = cfg.rope_parameters or {}
    if kind not in sets:
        raise ValueError(f"rope_parameters holds no set for {kind!r} layers "
                         f"(it names {sorted(sets)}): mellum nests its "
                         "rotary parameters by layer type")
    p = dict(sets[kind])
    theta = float(p.get("rope_theta", cfg.rope_theta))
    rope_type = p.get("rope_type", p.get("type", "default"))
    if rope_type == "default":
        return theta, None
    if rope_type != "yarn":
        raise ValueError(f"rope_parameters[{kind!r}]: rope_type "
                         f"{rope_type!r} is neither 'default' nor 'yarn'")
    return theta, p


@register_model("mellum")
def build_mellum(ff, cfg: ServeModelConfig, max_tokens: int):
    kinds = cfg.layer_types or ()
    mlps = cfg.mlp_layer_types or (SPARSE,) * cfg.num_hidden_layers
    for name, entries in (("layer_types", kinds), ("mlp_layer_types", mlps)):
        if len(entries) != cfg.num_hidden_layers:
            raise ValueError(f"{name} names every layer: "
                             f"{cfg.num_hidden_layers} layers, "
                             f"{len(entries)} entries")
    unknown = sorted(set(mlps) - {SPARSE, DENSE})
    if unknown:
        raise ValueError(f"mlp_layer_types holds {unknown}: not an FFN this "
                         f"builder knows ({SPARSE}, {DENSE})")
    if cfg.attention_bias:
        raise ValueError("mellum here projects without bias (attention_bias "
                         "false): SlidingWindowAttention has no bias")
    if SLIDING in kinds and not cfg.sliding_window:
        raise ValueError("a sliding_attention layer needs sliding_window")
    rope = {kind: rope_of(cfg, kind) for kind in sorted(set(kinds))}
    if rope.get(SLIDING, (0, None))[1]:
        raise ValueError("rope_parameters['sliding_attention'] asks for "
                         "YaRN: SlidingWindowAttention rotates by plain "
                         "rope_theta only")
    d, eps = cfg.hidden_size, cfg.rms_norm_eps
    held = cfg.num_experts
    scored = cfg.router_num_experts or held
    held_lo = cfg.expert_share_index * held
    if SPARSE in mlps and not (held and cfg.moe_intermediate_size):
        raise ValueError("a sparse layer needs num_experts and "
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
        kind = layer_kind(cfg, i)
        theta, scaling = rope[kind]
        if kind == SLIDING:
            a = ff.sliding_window_attention(
                n, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                cfg.sliding_window, rope_theta=theta, name=f"{p}.self_attn")
        else:
            a = ff.inc_multihead_self_attention(
                n, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                rope_theta=theta, rope_scaling=scaling, use_bias=False,
                name=f"{p}.self_attn")
        x = ff.add(x, a, name=f"{p}.attn_residual")
        n = ff.rms_norm(x, eps=eps, name=f"{p}.post_attention_layernorm")
        if mlps[i] == DENSE:
            m = _gated_mlp(ff, n, cfg.intermediate_size, d, f"{p}.mlp", plain)
        else:
            ids, w = ff.moe_router(n, scored, cfg.num_experts_per_tok,
                                   norm_topk=cfg.norm_topk_prob, bias=False,
                                   scoring="softmax", name=f"{p}.mlp.gate")
            xs, sizes, order = ff.moe_dispatch(n, ids, held, held_lo,
                                               name=f"{p}.mlp.dispatch")
            ys = ff.moe_experts(xs, sizes, held, cfg.moe_intermediate_size,
                                form="swiglu", num_scored=scored,
                                name=f"{p}.mlp.experts")
            m = ff.moe_combine(ys, order, ids, w, held, held_lo,
                               dtype=n.dtype, name=f"{p}.mlp.combine")
        x = ff.add(x, m, name=f"{p}.residual")
    x = ff.rms_norm(x, eps=eps, name="model.norm")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
