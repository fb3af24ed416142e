"""Operations and bytes a kernel call needs, from its shapes alone.

The least the algorithm must do — not what an implementation happens to
do: padding rows, masked blocks and re-reads are not counted, so a roofline
share computed from these can never be flattered by wasted work.
"""


def decode_attention_cost(context_lens, q_heads, kv_heads, head_dim,
                          kv_bytes=2, io_bytes=2):
    """One decode-attention call of one layer: each row attends its whole
    live prefix (``context_lens``: positions in the cache per row,
    including the token being decoded).

    ops: QK^T and PV, 2 * 2 * q_heads * head_dim per position.
    bytes: every live K and V position once, plus q in and o out.
    """
    positions = sum(context_lens)
    rows = len(context_lens)
    ops = 4 * q_heads * head_dim * positions
    nbytes = (2 * kv_heads * head_dim * kv_bytes * positions
              + 2 * rows * q_heads * head_dim * io_bytes)
    return ops, nbytes


def prefill_attention_cost(chunk_len, start, q_heads, kv_heads, head_dim,
                           kv_bytes=2, io_bytes=2):
    """One causal prefill-attention call of one layer: ``chunk_len`` query
    positions starting at ``start`` in a sequence.  Query i attends
    ``start + i + 1`` positions.

    ops: 4 * q_heads * head_dim per (query, key) pair under the mask.
    bytes: K and V of positions ``[0, start + chunk_len)`` once, q in, o out.
    """
    pairs = chunk_len * start + chunk_len * (chunk_len + 1) // 2
    ops = 4 * q_heads * head_dim * pairs
    nbytes = (2 * kv_heads * head_dim * kv_bytes * (start + chunk_len)
              + 2 * chunk_len * q_heads * head_dim * io_bytes)
    return ops, nbytes


def dense_step_cost(matrix_params, rows, weight_bytes=2):
    """The dense part (projections, FFN, LM head) of one step over ``rows``
    token rows: every weight matrix is multiplied once per row and streamed
    from memory once per step, however many rows share it.

    ops: 2 * parameters * rows.  bytes: parameters * ``weight_bytes``
    (activations are thousands of times smaller and left out).
    """
    return 2 * matrix_params * rows, matrix_params * weight_bytes


def roofline_seconds(ops, nbytes, peak):
    """Least seconds the chip could take and which roof bounds it."""
    t_ops = ops / peak["flops_bf16"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
