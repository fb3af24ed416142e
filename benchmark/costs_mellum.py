"""Operations and bytes of what ``mellum`` (Mellum 2) adds to a PROMPT chunk,
from its shapes and from the program's own counts (the companion of
costs.py, same rule: the least the algorithm must do, whatever implements
it, so a roofline share computed from these cannot be flattered by wasted
work — padded rows, re-read row tiles, masked or skipped key blocks and an
unvisited expert's matrices are not in them).
"""

from benchmark.reference import mellum as arch


def routed_prefill_cost(experts_visited, pairs, hf, weight_bytes=2,
                        io_bytes=2):
    """The GATED experts' three GEMMs for ``pairs`` (row, choice) pairs that
    visit ``experts_visited`` (expert, layer, chunk) triples — the prompt
    launches' counts (``prefill_experts_visited``, ``prefill_expert_pairs``
    on the ``commit`` spans): each visited expert's gate, up and down matrix
    (``3 x hidden x width`` elements) streamed once a chunk and layer, each
    pair's row in and out (``hidden`` elements each way; the ``width``
    between can stay on chip); ``6 x hidden x width`` operations a pair."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    nbytes = (experts_visited * 3 * d * f * weight_bytes
              + pairs * 2 * d * io_bytes)
    return pairs * 6 * d * f, nbytes


def window_prefill_cost(ring_ctx_sum, prompt_tokens, hf, kv_bytes=2,
                        io_bytes=2):
    """The attention proper of ALL the sliding layers for the prompt rows of
    some launches, from their dispatch spans' sums: ``ring_ctx_sum`` =
    ``prompt_ring_ctx_sum``, sum over the rows of ``min(position + 1,
    sliding_window)`` — the keys a row sees in ONE ring layer —, and
    ``prompt_tokens`` the rows themselves.

    ops: QK' and PV, ``4 x query heads x head size`` a visible key.
    bytes: every row's K and V written to the ring once and read back once
    (``2 x kv heads x head size`` elements each way: a key is read by the
    rows of its own chunk and of the next ones, at least once), q in and o
    out; per sliding layer."""
    rings = arch.layer_kinds(hf).count(arch.SLIDING)
    q_heads, kv_heads, hd = arch.attention_shape(hf)
    ops = 4 * q_heads * hd * ring_ctx_sum
    nbytes = prompt_tokens * (2 * 2 * kv_heads * hd * kv_bytes
                              + 2 * q_heads * hd * io_bytes)
    return rings * ops, rings * nbytes
