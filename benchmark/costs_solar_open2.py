"""Operations and bytes of what ``solar_open2`` (Solar-Open2-250B) adds to a
PROMPT chunk, from its shapes and from the program's own counts (the
companion of costs.py, same rule: the least the algorithm must do, whatever
implements it, so a roofline share computed from these cannot be flattered
by wasted work — the chunked form's ``[32, 32, heads, 128]`` decay tensors,
its triangular solve, padded rows, masked key blocks and an unvisited
expert's matrices are not in them).
"""

# the gated experts' three grouped GEMMs of a prompt chunk cost what mellum's
# do, at this configuration's widths (3 x 4096 x 1280 x 2 B a visited expert,
# each pair's row in and out, 6 x 4096 x 1280 operations a pair)
from benchmark.costs_mellum import routed_prefill_cost  # noqa: F401
from benchmark.reference import solar_open2 as arch


def kda_prefill_cost(prompt_tokens, segments, hf, state_bytes=4, io_bytes=2,
                     decay_bytes=4):
    """The delta rule proper of ALL the Kimi Delta Attention layers for the
    prompt rows of some launches, from their dispatch spans' sums:
    ``prompt_tokens`` rows in ``segments`` runs (one request's rows of one
    chunk; a flat step's span carries none and adds none).

    ops: 8 a state element a row (``costs_kimi_linear.kda_decode_cost``'s
    count: the decay's product; the product and sum of ``S'^T k``; the
    correction's product and sum; the read-out's product and sum; one for
    the row work) — ``8 x heads x head size^2``.
    bytes: a row's ``q``, ``k``, ``v`` in (bf16), its float32 decay (a
    vector a head) and ``beta`` in, ``o`` out; the ``heads x head size^2``
    float32 state read ONCE and written ONCE a SEGMENT, not a row — a state
    held on chip serves every row of its run; per KDA layer."""
    lists = hf["linear_attn_config"]
    layers = arch.layer_kinds(hf).count("kda")
    h, d = lists["num_heads"], lists["head_dim"]
    width, state = h * d, h * d * d
    per_row = (3 * width + width) * io_bytes + (width + h) * decay_bytes
    nbytes = prompt_tokens * per_row + segments * 2 * state * state_bytes
    return layers * prompt_tokens * 8 * state, layers * nbytes


def full_prefill_cost(prompt_ctx_sum, prompt_tokens, hf, kv_bytes=2,
                      io_bytes=2):
    """The attention proper of the gated softmax layers for the prompt rows
    of some launches, from their dispatch spans' sums: ``prompt_ctx_sum``,
    sum over the rows of ``position + 1`` — the keys a row sees —, and
    ``prompt_tokens`` the rows themselves.

    ops: QK' and PV, ``4 x query heads x head size`` a visible key.
    bytes: every row's K and V written to the cache once and read back once
    (``2 x kv heads x head size`` elements each way: a key is read by the
    rows of its own chunk and of the later ones, at least once), ``q`` in
    and ``o`` out; per attention layer.  The gate (its projection, sigmoid
    and product) lies under ``o_proj`` and is in neither side."""
    full = arch.layer_kinds(hf).count("gqa")
    q_heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf["head_dim"]
    ops = 4 * q_heads * hd * prompt_ctx_sum
    nbytes = prompt_tokens * (2 * 2 * kv_heads * hd * kv_bytes
                              + 2 * q_heads * hd * io_bytes)
    return full * ops, full * nbytes
