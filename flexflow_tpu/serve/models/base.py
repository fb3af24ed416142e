"""Serve model zoo scaffolding: config + graph-builder registry.

Reference: ``inference/models/*.cc/.h`` — each architecture is a function that
builds the serve PCG on an ``FFModel`` from an HF-style config.  Here a
:class:`ServeModelConfig` mirrors the HF ``config.json`` fields we need, and
each family registers a builder keyed by HF ``model_type``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

MODEL_REGISTRY: Dict[str, Callable] = {}


def register_model(model_type: str):
    def deco(fn):
        MODEL_REGISTRY[model_type] = fn
        return fn

    return deco


@dataclasses.dataclass
class ServeModelConfig:
    """Architecture hyperparameters (HF config.json field names)."""

    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    head_dim: Optional[int] = None
    rms_norm_eps: float = 1e-6
    layer_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    bos_token_id: int = 1
    eos_token_id: int = 2
    tie_word_embeddings: bool = False
    # opt/mpt/starcoder-family extras
    do_layer_norm_before: bool = True
    word_embed_proj_dim: Optional[int] = None  # opt-350m embed != hidden
    parallel_attn: bool = False       # falcon: attn & mlp in parallel
    bias: bool = False                # falcon-rw: linear biases
    use_alibi: bool = False           # mpt
    new_decoder_architecture: bool = False  # falcon >= 40b
    # phi4flash (SambaY; ``models/phi4flash.py`` says what layer i is).  The
    # Mamba sizes are the family's defaults; HF's config.json leaves them out.
    sliding_window: Optional[int] = None
    mb_per_layer: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None  # None = ceil(hidden_size / 16)
    # evabyte (EVA attention; ``models/evabyte.py``): exact attention inside
    # ``window_size`` positions, one summary per ``chunk_size`` positions of
    # everything before; ``num_pred_heads`` heads share the trunk (head 0
    # is the next byte); norms scale by ``1 + gamma``
    window_size: Optional[int] = None
    chunk_size: Optional[int] = None
    num_pred_heads: int = 1
    norm_add_unit_offset: bool = False
    # minicpm_sala (``models/minicpm_sala.py``): ``mixer_types[i]`` says what
    # layer i mixes with — ``minicpm4`` (InfLLM-v2 sparse attention over
    # ``num_key_value_heads`` K/V heads, the ``attn_*`` keys and the
    # ``sparse_*`` sizes) or ``lightning-attn`` (linear attention, the
    # ``lightning_*`` keys, ``qk_norm``, ``use_output_norm``,
    # ``use_output_gate``); muP: the embedding times ``scale_emb``, each
    # block's output times ``scale_depth / sqrt(mup_denominator)`` (the
    # PUBLISHED depth, whatever the depth run), the final hidden state over
    # ``hidden_size / dim_model_base``.  The sparse sizes are MiniCPM4's
    # InfLLM-v2 convention; HF's config.json nests them in ``sparse_config``.
    mixer_types: Optional[tuple] = None
    attn_use_rope: bool = False
    attn_use_output_gate: bool = True
    lightning_nh: Optional[int] = None
    lightning_nkv: Optional[int] = None
    lightning_head_dim: Optional[int] = None
    lightning_use_rope: bool = True
    qk_norm: bool = True
    use_output_norm: bool = True
    use_output_gate: bool = True
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    mup_denominator: Optional[int] = None
    dim_model_base: Optional[int] = None
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_window_size: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    # nemotron_h (``models/nemotron_h.py``): ``hybrid_override_pattern[i]``
    # says what the ONE mixer of block i is — ``M`` Mamba-2 (the ``mamba_*``
    # sizes, ``n_groups`` B/C groups, ``ssm_state_size``, ``conv_kernel``,
    # the ``time_step_*`` of its initialisation), ``*`` attention, ``E`` a
    # mixture of experts (the keys below), ``-`` a dense relu^2 MLP.
    # ``n_routed_experts`` is what THIS graph holds: share
    # ``expert_share_index`` of the ``router_num_experts`` the router scores
    # (None: it holds them all).  ``layer_norm_epsilon`` maps to
    # ``layer_norm_eps``.
    hybrid_override_pattern: Optional[str] = None
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    n_routed_experts: int = 0
    router_num_experts: Optional[int] = None
    expert_share_index: int = 0
    num_experts_per_tok: int = 1
    n_shared_experts: int = 0
    moe_intermediate_size: Optional[int] = None
    moe_shared_expert_intermediate_size: Optional[int] = None
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    # cohere2_moe (``models/cohere2_moe.py``): ``layer_types[i]`` says
    # whether layer i is ``sliding_attention`` (a ring of ``sliding_window``
    # positions, rotary at ``rope_theta`` on the pairs
    # ``position_embedding_type`` names: ``rope_gptj`` interleaved) or
    # ``full_attention`` (a full-length cache, no positional term); every
    # layer a parallel block (``use_parallel_block``) of attention and a
    # mixture of ``num_experts`` gated experts of width
    # ``intermediate_size`` — what THIS graph holds: share
    # ``expert_share_index`` of the ``router_num_experts`` the router
    # scores (None: all) — top
    # ``num_experts_per_tok`` by sigmoid, and ``num_shared_experts`` combined
    # by ``shared_expert_combination_strategy``; the head tied to the
    # embedding, times ``logit_scale``.
    layer_types: Optional[tuple] = None
    position_embedding_type: str = "rope_gptj"
    use_parallel_block: bool = True
    num_experts: int = 0
    num_shared_experts: int = 0
    shared_expert_combination_strategy: str = "average"
    first_k_dense_replace: int = 0
    logit_scale: float = 1.0
    # deepseek_v2 (``models/deepseek_v2.py``): latent attention — queries of
    # ``qk_nope_head_dim`` + ``qk_rope_head_dim`` a head (``q_lora_rank``
    # None: no query down-projection), a cached latent of ``kv_lora_rank``
    # and one rotated key part of ``qk_rope_head_dim`` a position, values of
    # ``v_head_dim`` a head; ``rope_scaling`` (type ``yarn``) on the rotary
    # part; layers below ``first_k_dense_replace`` a dense gated MLP of
    # ``intermediate_size``, the others (every ``moe_layer_freq``-th) a
    # mixture of ``n_routed_experts`` gated experts of
    # ``moe_intermediate_size``, top ``num_experts_per_tok`` by
    # ``scoring_func`` (``topk_method`` ``greedy``: no group limit), beside
    # ``n_shared_experts`` shared ones summed.
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_scaling: Optional[dict] = None
    moe_layer_freq: int = 1
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    # kimi_linear (``models/kimi_linear.py``): ``linear_attn_config`` holds the
    # two 1-BASED lists that name every layer's mixer (``kda_layers``: Kimi
    # Delta Attention; ``full_attn_layers``: latent attention, the
    # ``deepseek_v2`` keys above) and the delta rule's ``num_heads``,
    # ``head_dim`` and ``short_conv_kernel_size``; ``mla_use_nope``: the
    # latent layers rotate nothing; the mixture is ``num_experts`` held of
    # ``router_num_experts``, top ``num_experts_per_token`` by
    # ``moe_router_activation_func`` with ``moe_renormalize``,
    # ``num_expert_group`` / ``topk_group`` 1 (no group limit), beside
    # ``num_shared_experts`` of the same width.
    linear_attn_config: Optional[dict] = None
    mla_use_nope: bool = False
    num_experts_per_token: int = 1
    moe_router_activation_func: str = "sigmoid"
    moe_renormalize: bool = True
    num_expert_group: int = 1
    # mellum (``models/mellum.py``): ``rope_parameters`` NESTED by layer type
    # — ``{"full_attention": {rope_type, rope_theta, ...}, "sliding_attention":
    # {...}}``, one rotary parameter set per entry of ``layer_types`` —;
    # ``mlp_layer_types[i]`` ``sparse`` (the mixture: ``num_experts`` of
    # ``moe_intermediate_size``, top ``num_experts_per_tok`` by softmax,
    # ``norm_topk_prob``) or ``dense`` (a gated MLP of ``intermediate_size``);
    # ``attention_bias`` must be false
    rope_parameters: Optional[dict] = None
    mlp_layer_types: Optional[tuple] = None
    attention_bias: bool = False
    # solar_open2 (``models/solar_open2.py``): ``gqa_layers`` — a 0-BASED list
    # — names the layers that are softmax grouped-query attention (every
    # ``gqa_interval + 1``-th), every other layer is Kimi Delta Attention at
    # ``linear_attn_config``'s ``num_heads`` / ``head_dim`` /
    # ``short_conv_kernel_size`` (NO layer lists in it here); ``use_rope``
    # false: no positional term; ``use_gqa_gate``: an output gate on the
    # attention layers; ``kda_allow_neg_eigval``: ``beta`` in (0, 2);
    # ``kda_use_full_proj`` false: the decay's and the gate's projections
    # are low-rank pairs; the mixture by deepseek's keys (``n_routed_experts``
    # held of ``router_num_experts``, ``n_shared_experts``,
    # ``num_experts_per_tok``, ``norm_topk_prob``)
    gqa_layers: Optional[tuple] = None
    gqa_interval: Optional[int] = None
    use_gqa_gate: bool = False
    use_rope: bool = True
    kda_allow_neg_eigval: bool = False
    kda_use_full_proj: bool = False
    # compute/cache dtype for the whole graph: the token embedding is built
    # in this dtype and every downstream op inherits it (x.dtype plumbing),
    # including the attention ops' KV caches.  "bfloat16" is the TPU-native
    # serving dtype (HF config.json's torch_dtype maps here).
    dtype: str = "float32"

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def hdim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @staticmethod
    def from_hf_config(hf) -> "ServeModelConfig":
        """Build from a transformers PretrainedConfig (or plain dict)."""
        get = (lambda k, d=None: getattr(hf, k, d)) if not isinstance(hf, dict) \
            else (lambda k, d=None: hf.get(k, d))
        fields = {f.name for f in dataclasses.fields(ServeModelConfig)}
        kw = {}
        for name in fields:
            v = get(name, None)
            if v is not None:
                kw[name] = v
        # family-specific renames
        if get("layer_norm_epsilon") is not None:  # falcon/gpt_bigcode
            kw["layer_norm_eps"] = get("layer_norm_epsilon")
        if get("n_embd") is not None:      # starcoder/gpt_bigcode, mpt (d_model)
            kw["hidden_size"] = get("n_embd")
        if get("d_model") is not None:
            kw["hidden_size"] = get("d_model")
        if get("n_head") is not None:
            kw["num_attention_heads"] = get("n_head")
        if get("n_heads") is not None:
            kw["num_attention_heads"] = get("n_heads")
        if get("n_layer") is not None:
            kw["num_hidden_layers"] = get("n_layer")
        if get("n_layers") is not None:
            kw["num_hidden_layers"] = get("n_layers")
        if get("ffn_dim") is not None:     # opt
            kw["intermediate_size"] = get("ffn_dim")
        if get("n_inner") is not None and get("n_inner"):
            kw["intermediate_size"] = get("n_inner")
        if get("expansion_ratio") is not None:  # mpt
            kw["intermediate_size"] = get("expansion_ratio") * kw["hidden_size"]
        if get("n_positions") is not None:  # gpt_bigcode
            kw["max_position_embeddings"] = get("n_positions")
        if get("num_kv_heads") is not None and get(
            "new_decoder_architecture", False
        ):  # falcon new-decoder GQA only; old arch ignores num_kv_heads
            kw["num_key_value_heads"] = get("num_kv_heads")
        if get("multi_query", False):      # falcon-7b / starcoder MQA
            kw["num_key_value_heads"] = 1
        if get("alibi", None) is not None:
            kw["use_alibi"] = get("alibi")
        attn_cfg = get("attn_config", None)  # mpt nests attention settings
        if attn_cfg is not None:
            aget = (lambda k, d=None: attn_cfg.get(k, d)) \
                if isinstance(attn_cfg, dict) \
                else (lambda k, d=None: getattr(attn_cfg, k, d))
            if aget("kv_n_heads") is not None:
                kw["num_key_value_heads"] = aget("kv_n_heads")
            if aget("alibi") is not None:
                kw["use_alibi"] = aget("alibi")
        if get("model_type") == "gpt_bigcode" and "intermediate_size" not in kw:
            kw["intermediate_size"] = 4 * kw["hidden_size"]
        td = get("torch_dtype", None)
        if td is not None:
            td = str(td).replace("torch.", "")
            # fp16 has no TPU hardware path; bf16 is the TPU half-precision
            kw["dtype"] = "bfloat16" if td in ("float16", "bfloat16") else td
        return ServeModelConfig(**kw)


def build_model(ff, config: ServeModelConfig, max_tokens: int):
    """Dispatch to the registered family builder; returns the logits Tensor."""
    if config.model_type not in MODEL_REGISTRY:
        raise ValueError(
            f"unknown model_type {config.model_type!r}; "
            f"known: {sorted(MODEL_REGISTRY)}"
        )
    return MODEL_REGISTRY[config.model_type](ff, config, max_tokens)
