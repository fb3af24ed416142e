"""Phi-4-mini-flash (``model_type: phi4flash``) serve graph builder.

SambaY (Ren et al. 2025, "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation"): a self-decoder of Mamba-1 and
sliding-window attention layers, ONE full-attention layer whose K/V cache is
the model's only full-length cache, and a cross-decoder whose layers
alternate gated memory units (which gate the last Mamba layer's scan output,
carried there by a graph edge) and cross-attention over that one cache.
:func:`layer_kind` says what layer i is; nothing else selects a path.  Every layer is ``x += mixer(LN(x)); x += MLP(LN'(x))`` with a SwiGLU
MLP; all attention is differential; there is no positional encoding; the LM
head is tied to the token embedding (kept as an untied copy here, as the
other builders keep theirs).

The fused published projections are cut where the graph needs two tensors —
Mamba's ``in_proj`` into its ``x`` and ``z`` halves, the MLP's
``gate_up_proj`` into ``gate_proj`` and ``up_proj`` — so every projection is
a plain ``Linear`` node and the mechanisms are nodes of their own classes
(``CausalConv1d``, ``SelectiveScan``, ``DiffAttention``).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from .base import ServeModelConfig, register_model


def layer_kind(cfg: ServeModelConfig, i: int) -> Tuple[str, Optional[str]]:
    """What layer ``i`` is: ``(mixer, state)``.  ``mixer`` is one of
    ``mamba``, ``window_attention``, ``full_attention``, ``gmu``,
    ``cross_attention``; ``state`` the kind of per-slot state the layer
    WRITES — ``recurrent``, ``kv_window``, ``kv_full`` or None (the gated
    memory units and the cross-attention layers read what layer n/2 exported
    and layer n/2 + 1 cached, and keep nothing).

    With n layers: 0 .. n/2 alternate Mamba (every ``mb_per_layer``-th, the
    last of them exports its scan output) and window attention; n/2 + 1 is
    the full-attention layer; the rest alternate gated memory units and
    cross-attention the same way."""
    half = cfg.num_hidden_layers // 2
    mamba_slot = i % cfg.mb_per_layer == 0
    if i <= half:
        return (("mamba", "recurrent") if mamba_slot
                else ("window_attention", "kv_window"))
    if i == half + 1:
        return "full_attention", "kv_full"
    return ("gmu" if mamba_slot else "cross_attention"), None


def mamba_inner(cfg: ServeModelConfig) -> int:
    return cfg.mamba_expand * cfg.hidden_size


def dt_rank(cfg: ServeModelConfig) -> int:
    return cfg.mamba_dt_rank or -(-cfg.hidden_size // 16)


@register_model("phi4flash")
def build_phi4flash(ff, cfg: ServeModelConfig, max_tokens: int):
    d, d_i, n = cfg.hidden_size, mamba_inner(cfg), cfg.mamba_d_state
    r = dt_rank(cfg)
    tokens = ff.create_tensor((max_tokens,), dtype=jnp.int32)
    x = ff.embedding(tokens, cfg.vocab_size, d, name="model.embed_tokens",
                     dtype=jnp.dtype(cfg.dtype))
    memory = None       # the last self-decoder Mamba layer's scan output
    cache_owner = None  # the full-attention node whose cache the rest read
    for i in range(cfg.num_hidden_layers):
        p = f"model.layers.{i}"
        mixer, _ = layer_kind(cfg, i)
        a = ff.layer_norm(x, eps=cfg.layer_norm_eps,
                          name=f"{p}.input_layernorm")
        if mixer == "mamba":
            xs = ff.dense(a, d_i, use_bias=False, name=f"{p}.mixer.in_proj_x")
            z = ff.dense(a, d_i, use_bias=False, name=f"{p}.mixer.in_proj_z")
            xs = ff.causal_conv1d(xs, cfg.mamba_d_conv,
                                  name=f"{p}.mixer.conv1d")
            dbc = ff.dense(xs, r + 2 * n, use_bias=False,
                           name=f"{p}.mixer.x_proj")
            dt, b, c = ff.split(dbc, [r, n, n], axis=1,
                                name=f"{p}.mixer.x_split")
            dt = ff.dense(dt, d_i, use_bias=False, name=f"{p}.mixer.dt_proj")
            memory = ff.selective_scan(xs, dt, b, c, n,
                                       name=f"{p}.mixer.scan")
            h = ff.sigmoid_silu_multi(z, memory, name=f"{p}.mixer.gate")
            h = ff.dense(h, d, use_bias=False, name=f"{p}.mixer.out_proj")
        elif mixer == "gmu":
            g = ff.dense(a, d_i, use_bias=False, name=f"{p}.mixer.in_proj")
            h = ff.sigmoid_silu_multi(g, memory, name=f"{p}.mixer.gate")
            h = ff.dense(h, d, use_bias=False, name=f"{p}.mixer.out_proj")
        else:
            mode = mixer[: -len("_attention")]
            h = ff.diff_attention(
                a, d, cfg.num_attention_heads, cfg.kv_heads, cfg.hdim,
                layer=i, mode=mode,
                window=cfg.sliding_window if mode == "window" else 0,
                state_owner=cache_owner if mode == "cross" else None,
                name=f"{p}.attn")
            if mode == "full":
                cache_owner = f"{p}.attn"
        x = ff.add(x, h, name=f"{p}.mixer_residual")
        a = ff.layer_norm(x, eps=cfg.layer_norm_eps,
                          name=f"{p}.post_attention_layernorm")
        gate = ff.dense(a, cfg.intermediate_size, use_bias=False,
                        name=f"{p}.mlp.gate_proj")
        up = ff.dense(a, cfg.intermediate_size, use_bias=False,
                      name=f"{p}.mlp.up_proj")
        h = ff.sigmoid_silu_multi(gate, up, name=f"{p}.mlp.act")
        h = ff.dense(h, d, use_bias=False, name=f"{p}.mlp.down_proj")
        x = ff.add(x, h, name=f"{p}.mlp_residual")
    x = ff.layer_norm(x, eps=cfg.layer_norm_eps, name="model.final_layernorm")
    return ff.dense(x, cfg.vocab_size, use_bias=False, name="lm_head")
