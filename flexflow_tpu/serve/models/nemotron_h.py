"""Nemotron-H / Nemotron-3 (``model_type: nemotron_h``) serve graph builder.

A stack of blocks of ONE mixer each — ``x += mixer_i(RMSNorm_i(x))`` — where
``hybrid_override_pattern[i]`` says what mixer layer i is: ``M`` Mamba-2
(``in_proj`` to ``z | xBC | dt``, a causal conv over ``xBC``, the SSD scan,
the gated group norm, ``out_proj``), ``*`` grouped-query attention with no
positional term (the Mamba layers carry position), ``E`` a mixture of
experts (a sigmoid router over ``router_num_experts`` experts with top-k,
``relu^2`` experts without a gate, one shared expert), ``-`` a dense
``relu^2`` MLP.  A final RMSNorm and an untied head.

A chip may hold a SHARE of the routed experts: ``n_routed_experts`` is what
this graph holds (published ids from ``expert_share_index x
n_routed_experts``), ``router_num_experts`` what the router scores
(``None``: the same).  A pair routed to an expert held elsewhere adds
nothing here; the exchange that would bring it is not in this graph.

Every mechanism is a node of its own class (``serve/ssd_moe_ops.py``:
``Mamba2Scan``, ``GatedGroupNorm``, ``MoERouter``, ``MoEDispatch``,
``MoEExperts``, ``MoECombine``); the shared expert is two ``Linear`` nodes.
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import ServeModelConfig, register_model

MAMBA, ATTENTION, EXPERTS, MLP = "M", "*", "E", "-"


def layer_kind(cfg: ServeModelConfig, i: int) -> str:
    """What layer ``i`` is: ``hybrid_override_pattern[i]``, nothing else."""
    kind = cfg.hybrid_override_pattern[i]
    if kind not in (MAMBA, ATTENTION, EXPERTS, MLP):
        raise ValueError(f"hybrid_override_pattern[{i}] = {kind!r}: not a "
                         "mixer this builder knows (M, *, E, -)")
    return kind


def _relu2_mlp(ff, a, width, d, prefix):
    h = ff.dense(a, width, use_bias=False, name=f"{prefix}.up_proj")
    h = ff.pow(ff.relu(h, name=f"{prefix}.relu"), 2.0, name=f"{prefix}.act")
    return ff.dense(h, d, use_bias=False, name=f"{prefix}.down_proj")


@register_model("nemotron_h")
def build_nemotron_h(ff, cfg: ServeModelConfig, max_tokens: int):
    pattern = cfg.hybrid_override_pattern or ""
    if len(pattern) != cfg.num_hidden_layers:
        raise ValueError("hybrid_override_pattern names every layer's "
                         f"mixer: {cfg.num_hidden_layers} layers, "
                         f"{len(pattern)} entries")
    d, eps = cfg.hidden_size, cfg.layer_norm_eps
    heads, hd = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.n_groups, cfg.ssm_state_size
    inner, bc_cols = heads * hd, 2 * groups * n
    held = cfg.n_routed_experts
    scored = cfg.router_num_experts or held
    held_lo = cfg.expert_share_index * held
    if held_lo + held > scored:
        raise ValueError(f"experts {held_lo}..{held_lo + held - 1} are not "
                         f"among the router's {scored}")
    tokens = ff.create_tensor((max_tokens,), dtype=jnp.int32)
    x = ff.embedding(tokens, cfg.vocab_size, d, name="backbone.embeddings",
                     dtype=jnp.dtype(cfg.dtype))
    for i in range(cfg.num_hidden_layers):
        p = f"backbone.layers.{i}"
        kind = layer_kind(cfg, i)
        a = ff.rms_norm(x, eps=eps, name=f"{p}.norm")
        if kind == MAMBA:
            zxd = ff.dense(a, 2 * inner + bc_cols + heads, use_bias=False,
                           name=f"{p}.mixer.in_proj")
            z, xbc, dt = ff.split(zxd, [inner, inner + bc_cols, heads],
                                  axis=1, name=f"{p}.mixer.in_split")
            xbc = ff.causal_conv1d(xbc, cfg.conv_kernel,
                                   name=f"{p}.mixer.conv1d")
            y = ff.mamba2_scan(xbc, dt, heads, hd, groups, n,
                               dt_min=cfg.time_step_min,
                               dt_max=cfg.time_step_max,
                               dt_floor=cfg.time_step_floor,
                               name=f"{p}.mixer.scan")
            y = ff.gated_group_norm(y, z, groups, eps=eps,
                                    name=f"{p}.mixer.norm")
            h = ff.dense(y, d, use_bias=False, name=f"{p}.mixer.out_proj")
        elif kind == ATTENTION:
            h = ff.inc_multihead_self_attention(
                a, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                rotary_embedding=False, use_bias=False, name=f"{p}.mixer")
        elif kind == EXPERTS:
            ids, w = ff.moe_router(
                a, scored, cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor,
                norm_topk=cfg.norm_topk_prob, name=f"{p}.mixer.gate")
            xs, sizes, order = ff.moe_dispatch(
                a, ids, held, held_lo, name=f"{p}.mixer.dispatch")
            ys = ff.moe_experts(xs, sizes, held, cfg.moe_intermediate_size,
                                num_scored=scored,
                                name=f"{p}.mixer.experts")
            h = ff.moe_combine(ys, order, ids, w, held, held_lo,
                               dtype=a.dtype, name=f"{p}.mixer.combine")
            if cfg.n_shared_experts:
                shared = _relu2_mlp(
                    ff, a, cfg.n_shared_experts
                    * cfg.moe_shared_expert_intermediate_size, d,
                    f"{p}.mixer.shared_experts")
                h = ff.add(h, shared, name=f"{p}.mixer.sum")
        else:
            h = _relu2_mlp(ff, a, cfg.intermediate_size, d, f"{p}.mixer")
        x = ff.add(x, h, name=f"{p}.residual")
    x = ff.rms_norm(x, eps=eps, name="backbone.norm_f")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
