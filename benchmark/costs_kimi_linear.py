"""Operations and bytes of what ``kimi_linear`` (Kimi-Linear-48B-A3B) adds to
a decode step, from its shapes, the two layer lists and the routing's own
counts (the companion of costs.py, same rule: the least the algorithm must
do, whatever implements it, so a roofline share computed from these cannot be
flattered by wasted work).
"""

# the gated experts' three grouped GEMMs cost what deepseek_v2's do, at this
# configuration's widths (3 x 2304 x 1024 x 2 B a visited expert, each pair's
# row in and out, 6 x 2304 x 1024 operations a pair)
from benchmark.costs_deepseek_v2 import routed_decode_cost  # noqa: F401
from benchmark.reference import kimi_linear as arch


def kda_decode_cost(context_lens, hf, state_bytes=4, io_bytes=2):
    """The Kimi Delta Attention layers' state update and read-out for
    ``len(context_lens)`` decode rows (the contexts do not matter: the state
    is of fixed size): per row and KDA layer the ``heads x head size x head
    size`` float32 state read ONCE and written ONCE — the delta correction
    needs ``S'^T k`` before the write, but a tile held on chip serves both —,
    the three convs' one tail (``kernel - 1`` rows of ``3 x heads x head
    size``) read and written, ``q``, ``k``, ``v``, the decay (a vector a
    head) and ``beta`` in and ``o`` out; 8 operations a state element (the
    decay's product; the product and sum of ``S'^T k``; the correction's
    product and sum; the read-out's product and sum; one for the ``v - r``
    and ``beta k`` row work, rounded up)."""
    lists = hf["linear_attn_config"]
    layers = arch.layer_kinds(hf).count("kda")
    h, d = lists["num_heads"], lists["head_dim"]
    width = h * d
    tail = (lists["short_conv_kernel_size"] - 1) * 3 * width
    rows = len(context_lens)
    state = h * d * d
    per_row = (2 * state * state_bytes
               + (2 * tail + 3 * width + width + h + width) * io_bytes)
    return layers * rows * 8 * state, layers * rows * per_row


def latent_decode_cost(context_lens, hf, kv_bytes=2, io_bytes=2):
    """The latent attention proper of the LATENT layers alone
    (``full_attn_layers``: one of the five run) for the decode rows
    ``context_lens`` — ``costs_deepseek_v2.latent_decode_cost``'s formula,
    the absorbed form: the latent and the second key part of every position
    read ONCE (``kv_lora_rank + qk_rope_head_dim`` elements), this step's
    written, ``q_lat`` and ``q_r`` in and ``o_lat`` out per head; ``2 x heads
    x (2 rank + rope)`` operations a position."""
    layers = arch.layer_kinds(hf).count("latent")
    heads = hf["num_attention_heads"]
    rank, rope = hf["kv_lora_rank"], hf["qk_rope_head_dim"]
    rows, positions = len(context_lens), sum(context_lens)
    per_pos = (rank + rope) * kv_bytes
    nbytes = layers * (per_pos * (positions + rows)
                       + rows * heads * (2 * rank + rope) * io_bytes)
    return layers * positions * 2 * heads * (2 * rank + rope), nbytes
