"""Operations and bytes of what ``deepseek_v2`` (DeepSeek-V2-Lite) adds to a
step, from its shapes and from the routing's own counts (the companion of
costs.py, same rule: the least the algorithm must do, whatever implements
it, so a roofline share computed from these cannot be flattered by wasted
work).
"""


def latent_decode_cost(context_lens, hf, kv_bytes=2, io_bytes=2):
    """The latent attention proper of every layer for the decode rows
    ``context_lens`` (positions in the cache per row, the decoded token
    included), in the ABSORBED form — the least of either form for one
    query a row.

    bytes: the latent and the rotated key part of every position read ONCE
    (``kv_lora_rank + qk_rope_head_dim`` elements: 1 152 B in bf16, whatever
    a layout pads), this step's written, ``q_lat`` and ``q_r`` in and
    ``o_lat`` out per head, per layer.
    ops: the score over ``rank + rope`` and the weighted sum over ``rank``,
    ``2 x heads x (2 rank + rope)`` a position."""
    layers, heads = hf["num_hidden_layers"], hf["num_attention_heads"]
    rank, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    rows, positions = len(context_lens), sum(context_lens)
    per_pos = (rank + rope) * kv_bytes
    nbytes = layers * (per_pos * (positions + rows)
                       + rows * heads * (2 * rank + rope) * io_bytes)
    return layers * positions * 2 * heads * (2 * rank + rope), nbytes


def routed_decode_cost(experts_visited, pairs, hf, weight_bytes=2,
                       io_bytes=2):
    """The gated experts' three GEMMs for ``pairs`` (row, choice) pairs that
    visit ``experts_visited`` (expert, layer, step) triples: each visited
    expert's gate, up and down matrix (``3 x hidden x moe width`` elements)
    streamed once, each pair's row in and out (``hidden`` elements each way;
    the width between can stay on chip); ``6 x hidden x width`` operations a
    pair."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    nbytes = (experts_visited * 3 * d * f * weight_bytes
              + pairs * 2 * d * io_bytes)
    return pairs * 6 * d * f, nbytes
