"""Operations and bytes of what ``cohere2_moe`` (Command A+) adds to a step,
from its shapes and from the routing's own counts (the companion of
costs.py, same rule: the least the algorithm must do, whatever implements
it, so a roofline share computed from these cannot be flattered by wasted
work).
"""

from benchmark.reference import cohere2_moe as arch


def mixed_attention_decode_cost(context_lens, hf, kv_bytes=2, io_bytes=2):
    """The attention proper of every layer for the decode rows
    ``context_lens`` (positions in the cache per row, the decoded token
    included): a sliding layer reads the ``min(context, sliding_window)``
    newest positions of its ring, a full layer the row's whole prefix.

    bytes: K and V of every position read (``2 x kv heads x head size``
    elements), this step's K and V written, q in and o out, per layer.
    ops: QK' and PV, ``4 x query heads x head size`` a position."""
    kinds = arch.layer_kinds(hf)
    rings, fulls = kinds.count(arch.SLIDING), kinds.count(arch.FULL)
    q_heads, kv_heads, hd = arch.attention_shape(hf)
    window = hf["sliding_window"]
    rows = len(context_lens)
    positions = (rings * sum(min(c, window) for c in context_lens)
                 + fulls * sum(context_lens))
    per_pos = 2 * kv_heads * hd * kv_bytes
    nbytes = (per_pos * (positions + (rings + fulls) * rows)
              + (rings + fulls) * rows * 2 * q_heads * hd * io_bytes)
    return 4 * q_heads * hd * positions, nbytes


def routed_decode_cost(experts_visited, pairs, hf, weight_bytes=2,
                       io_bytes=2):
    """The held GATED experts' three GEMMs for ``pairs`` (row, choice) pairs
    that visit ``experts_visited`` (expert, layer, step) triples: each
    visited expert's gate, up and down matrix (``3 x hidden x width``
    elements) streamed once, each pair's row in and out (``hidden`` elements
    each way; the ``width`` between can stay on chip); ``6 x hidden x
    width`` operations a pair."""
    d, f = hf["hidden_size"], hf["intermediate_size"]
    nbytes = (experts_visited * 3 * d * f * weight_bytes
              + pairs * 2 * d * io_bytes)
    return pairs * 6 * d * f, nbytes


def window_prefill_cost(chunk_len, start, hf, kv_bytes=2, io_bytes=2):
    """One sliding layer's attention for a prompt chunk of ``chunk_len``
    queries from position ``start`` (``costs.prefill_attention_cost`` with
    the window's lower bound): query ``start + i`` attends ``min(start + i
    + 1, window)`` positions; K and V of the positions any query sees, once;
    q in, o out.  No cell's traced span holds a prompt of this configuration
    yet (PERF.md section 7): the function is here for the cell that will."""
    q_heads, kv_heads, hd = arch.attention_shape(hf)
    window = hf["sliding_window"]
    pairs = sum(min(start + i + 1, window) for i in range(chunk_len))
    seen = min(start + chunk_len, window + chunk_len - 1)
    nbytes = (2 * kv_heads * hd * kv_bytes * seen
              + 2 * chunk_len * q_heads * hd * io_bytes)
    return 4 * q_heads * hd * pairs, nbytes
