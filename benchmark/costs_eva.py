"""Operations and bytes of EVA attention's decode step, from its shapes alone
(the companion of costs.py, same rule: the least the algorithm must do, so a
roofline share computed from these cannot be flattered by wasted work).

A decode row at position ``t`` (context ``t + 1``) attends, per layer, the
``W / C`` summaries of each of its ``t // W`` closed windows and the
``t % W + 1`` exact entries of its open one: ``L(t)`` entries, whatever holds
them — LIVE entries only, no block of a kernel rounded up, no entry of a
padded cache — and writes one.  The summaries are counted as read, not as
made: what closing a window costs is the compaction's, a metric of its own.
"""

from benchmark import costs


def live_entries(context, window, chunk):
    """``L(t)`` for a row whose cache holds ``context`` = ``t + 1``
    positions."""
    t = context - 1
    return (window // chunk) * (t // window) + t % window + 1


def eva_decode_cost(context_lens, hf, kv_bytes=2, io_bytes=2):
    """The attention proper of every layer for the decode rows
    ``context_lens`` (positions in the cache per row, the decoded token
    included): ``costs.decode_attention_cost`` — 4 x heads x head size
    operations and one K and one V entry of bytes per entry attended, q in
    and o out per row — over ``L(t)`` entries a row, plus this step's K and V
    entry written."""
    heads = hf["num_attention_heads"]
    hd = hf["hidden_size"] // heads
    live = [live_entries(c, hf["window_size"], hf["chunk_size"])
            for c in context_lens]
    ops, nbytes = costs.decode_attention_cost(live, heads, heads, hd,
                                              kv_bytes, io_bytes)
    nbytes += len(live) * 2 * heads * hd * kv_bytes
    layers = hf["num_hidden_layers"]
    return layers * ops, layers * nbytes
