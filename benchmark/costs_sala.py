"""Operations and bytes of what MiniCPM-SALA's two mixers add to a decode
step, from its shapes alone (the companion of costs.py, same rule: the least
the algorithm must do, so a roofline share computed from these cannot be
flattered by wasted work, whatever implements it).

Both take the decode rows' contexts (positions in the cache per row, the
decoded token included) and the configuration's published fields (``hf``);
the layers of each kind are counted from ``mixer_types``, not from
``num_hidden_layers``.
"""

from benchmark.reference import minicpm_sala as arch


def attended_positions(context, sizes):
    """Exact keys a decode row with ``context`` positions in its cache
    attends, per K/V-head group: every position below ``dense_len``; past it
    the init blocks, the window's newest blocks up to the row's own position
    and ``topk`` whole blocks of the rest — LIVE positions only, the newest
    block counted as far as it is filled."""
    _, _, size, topk, window, init, dense_len = sizes
    t = context - 1
    if t < dense_len:
        return context
    last = t // size
    near = window // size                     # blocks last - near + 1 .. last
    first_near = max(last - near + 1, 0)
    forced = min(init, first_near) * size + (t + 1 - first_near * size)
    others = max(first_near - min(init, first_near), 0)
    return forced + min(topk, others) * size


def sparse_decode_cost(context_lens, hf, kv_bytes=2, io_bytes=2):
    """The sparse layers' selection and attention for the decode rows
    ``context_lens``: per row and layer the attended positions' K and V
    entries (``2 x kv heads x head size`` elements each) and the visible
    compressed keys (``kv heads x head size`` each) read, this step's K and V
    entry written, q in and o out; 4 x query heads x head size operations
    an attended position (QK' and PV; the scores over the compressed keys
    are a sixteenth of that and left out)."""
    layers = arch.layer_kinds(hf).count(arch.SPARSE)
    q_heads, kv_heads, hd = arch.attention_shape(hf)
    sizes = arch.sparse_sizes(hf)
    kernel, stride = sizes[:2]
    positions = sum(attended_positions(c, sizes) for c in context_lens)
    index = sum(max((c - kernel) // stride + 1, 0) for c in context_lens)
    rows = len(context_lens)
    entry = kv_heads * hd * kv_bytes
    nbytes = (2 * entry * (positions + rows) + entry * index
              + 2 * rows * q_heads * hd * io_bytes)
    return layers * 4 * q_heads * hd * positions, layers * nbytes


def lightning_decode_cost(context_lens, hf, state_bytes=4, io_bytes=2):
    """The lightning layers' state update and read-out for
    ``len(context_lens)`` decode rows (the contexts do not matter: the state
    is of fixed size): per row and layer the ``heads x head size x head
    size`` float32 state read once and written once, q, k, v in and o out;
    5 operations a state element (the decay's product, the rank-one update's
    product and sum, the read-out's product and sum)."""
    layers = arch.layer_kinds(hf).count(arch.LINEAR)
    heads, hd = arch._LH(hf), arch._LD(hf)
    rows = len(context_lens)
    state = heads * hd * hd
    per_row = 2 * state * state_bytes + 4 * heads * hd * io_bytes
    return layers * rows * 5 * state, layers * rows * per_row
