"""Operations and bytes of what a hybrid decoder adds to a decode step, from
its shapes alone (the companion of costs.py, same rule: the least the
algorithm must do, so a roofline share computed from these cannot be
flattered by wasted work).

Both take the decode rows' contexts and the configuration's published fields
(``hf``), so a reader needs nothing of the program but its trace.
"""

from benchmark.reference import phi4flash as arch


def ssm_decode_cost(context_lens, hf, act_bytes=2, state_bytes=4):
    """The Mamba layers' conv + selective scan for ``len(context_lens)``
    decode rows (one position each; the contexts do not matter: the state
    is of fixed size), all Mamba layers together.

    bytes per row and layer: the scan state ``[d_i, N]`` read and written
    once (float32), the conv's tail of K - 1 inputs read and written, and the
    activations in and out (x, xs, the step, y: ``d_i`` each; B and C: ``N``).
    ops: ~7 per state element (the step's product and exp, the update's two
    products and sum, the read-out's product and sum) + the conv's 2K per
    channel.  Left out: ``A_log``, ``D``, the biases and the conv's weights,
    read once per STEP whatever the rows (0.34 MB a layer against 0.66 MB
    per row): the least is that much too small.
    """
    rows = len(context_lens)
    layers = arch.layer_kinds(hf).count(arch.MAMBA)
    d_i, n, k = arch._DI(hf), arch._N(hf), arch._K(hf)
    per_row = (2 * d_i * n * state_bytes + 2 * (k - 1) * d_i * act_bytes
               + (5 * d_i + 2 * n) * act_bytes)
    ops = 7 * d_i * n + 2 * k * d_i
    return layers * rows * ops, layers * rows * per_row


def shared_kv_decode_cost(context_lens, hf, kv_bytes=2, io_bytes=2):
    """The attention proper of every attention layer for the decode rows
    ``context_lens`` (positions in the cache per row, the decoded token
    included): the full-attention layer and each cross-attention layer read
    the row's live prefix of the ONE shared cache; each window layer reads
    the ``min(context, window)`` newest positions of its ring.

    bytes: K and V of every position read, ``2 * kv_heads * head_dim``
    elements each (a pair's ``[k1|k2]``, ``[v1|v2]`` is the same bytes);
    this step's K and V written by the layers that own a cache; q in and
    the pairs' 2 x 128-wide outputs out.
    ops per position and query head: 2 hd for the score, 2 * 2 hd for the
    128-wide value.
    """
    kinds = arch.layer_kinds(hf)
    readers = kinds.count(arch.FULL) + kinds.count(arch.CROSS)
    windows = kinds.count(arch.WINDOW)
    writers = kinds.count(arch.FULL) + windows
    q_heads, kv_heads, hd = arch.attention_shape(hf)
    rows = len(context_lens)
    full = sum(context_lens)
    near = sum(min(c, hf["sliding_window"]) for c in context_lens)
    positions = readers * full + windows * near
    per_pos = 2 * kv_heads * hd * kv_bytes
    nbytes = (per_pos * (positions + writers * rows)
              + (readers + windows) * rows * 3 * q_heads * hd * io_bytes)
    return 6 * q_heads * hd * positions, nbytes
