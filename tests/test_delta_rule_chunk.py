"""``delta_rule_chunk`` (ops/pallas/delta_rule.py): the chunked delta form's
pieces in one Pallas kernel, in interpret mode on toy heads — against the XLA
loop it replaces (``KimiDeltaAttention._chunked``, kept as the oracle) AND
against the per-row recurrence in float64, at the tolerance
tests/test_solar_open2.py holds the loop to (5e-5 of the outputs' size).

What interpret mode cannot see — whether the MXU multiplies float32 where
the kernel asks for it — is ``scripts/delta_chunk_bench.py``'s, on the chip;
that the kernel compiles for a v5e at the cells' shapes is
tests/test_tpu_aot_compile.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from delta_rule_forms import draw, layout, recurrence64, segments

from flexflow_tpu.core.op import OpContext
from flexflow_tpu.ops.pallas.delta_rule import (CONTINUE, STORED, ZEROS,
                                                delta_rule_chunk, head_group)
from flexflow_tpu.serve.batch_config import BatchConfig
from flexflow_tpu.serve.hybrid_ops import KimiDeltaAttention

PIECE, D, TOL = 32, 16, 5e-5

# a flat batch as ``[(slot or -1: pads, first position, rows)]``
BATCHES = {
    # one request in full pieces, from position 0
    "full_pieces": [(0, 0, 64)],
    # ... and a ragged last piece, the batch's end inside its window
    "ragged_last": [(0, 0, 70)],
    # a tiled chunk three prompts share: segments on 16-row tiles, pad rows
    # between; one continues a stored state, two are fresh
    "tile_padded": [(0, 100, 37), (-1, 0, 11), (1, 0, 20), (-1, 0, 12),
                    (2, 0, 33), (-1, 0, 15)],
    # a flat step: every live row a request of its own, pads among them
    "one_row_pieces": [(0, 3, 1), (1, 0, 1), (2, 5, 1), (-1, 0, 2),
                       (3, 7, 1), (4, 9, 1), (5, 11, 1), (6, 1, 1),
                       (7, 2, 1), (8, 3, 1), (9, 4, 1), (-1, 0, 4)],
    # segments that start on ANY row (a join's prefill beside decode rows):
    # windows shifted inside their sublane tile, a tile several pieces share
    "any_row": [(-1, 0, 3), (0, 5, 37), (1, 0, 3), (-1, 0, 2), (2, 7, 40),
                (3, 9, 1), (4, 0, 1), (5, 3, 1), (-1, 0, 5)],
    # no live row at all: no piece, the state as it was
    "all_pads": [(-1, 0, 16)],
}


def _forms(op, operands, kda, req, pos, slots):
    """``(o, state)`` of the kernel and of the loop on one batch."""
    seg = segments(req, pos, slots)

    def kernel(kda, *a):
        o, s = delta_rule_chunk(kda, *a, op._pieces(seg), chunk=op.chunk,
                                interpret=True)
        return jnp.where(seg.live[:, None, None], o, 0.0), s

    loop = lambda kda, *a: op._chunked(*a, kda, seg)
    return jax.jit(kernel)(kda, *operands), jax.jit(loop)(kda, *operands)


def _close(got, want, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= TOL * max(np.abs(want).max(), 1e-30), \
        (what, np.abs(got - want).max(), np.abs(want).max())


@pytest.mark.parametrize("heads", [3, 8], ids=["h3", "h8"])
@pytest.mark.parametrize("batch", list(BATCHES))
def test_the_kernel_is_the_loop_and_the_recurrence(batch, heads):
    """Every batch shape the prompt path meets, on 3 heads (ONE pack of
    three side by side in the solve's lanes) and on 8 (two packs of four):
    outputs and every slot's state against the loop and against the float64
    recurrence; the slots no segment ended in keep their state to the
    bit."""
    req, pos = layout(BATCHES[batch])
    slots = max(max(req) + 1, 1) + 1          # one slot no row names
    rng = np.random.default_rng(5)
    operands = draw(rng, len(req), heads, D)
    kda = jnp.asarray(rng.standard_normal((slots + 1, heads, D, D)),
                      jnp.float32)
    (o, s), (loop_o, loop_s) = _forms(
        KimiDeltaAttention(64, heads, D, chunk=PIECE, allow_neg_eigval=True),
        operands, kda, req, pos, slots)
    want_o, want_s = recurrence64(*operands, req, pos, kda)
    if max(req) >= 0:
        _close(o, loop_o, "o against the loop")
        _close(o, want_o, "o against the recurrence")
    assert not np.asarray(o)[np.asarray(req) < 0].any()
    _close(s[:slots], loop_s[:slots], "state against the loop")
    _close(s[:slots], want_s[:slots], "state against the recurrence")
    # written only where a segment ended: the slot no row names and the
    # scratch row (the loop parks every unfinished piece there) untouched
    untouched = sorted(set(range(slots + 1)) - set(req))
    assert np.array_equal(np.asarray(s)[untouched], np.asarray(kda)[untouched])


@pytest.mark.parametrize("heads", [32, 64])
def test_head_groups_at_the_cells_head_counts(heads):
    """32 heads (``kimi-linear-d5-e32``) and 64 (``solar-open2-d4-e40``):
    two and four grid groups of 16 (the step kernel's ``head_group``; a
    count it does not divide is one group), each with its own state tile and
    its own lanes of ``beta``; one ragged piece on a stored state."""
    assert head_group(heads) == 16 and head_group(12) == 12
    req, pos = layout([(1, 9, 21), (-1, 0, 3)])
    rng = np.random.default_rng(heads)
    operands = draw(rng, len(req), heads, D)
    kda = jnp.asarray(rng.standard_normal((3, heads, D, D)), jnp.float32)
    (o, s), _ = _forms(
        KimiDeltaAttention(64, heads, D, chunk=PIECE), operands, kda, req,
        pos, 2)
    want_o, want_s = recurrence64(*operands, req, pos, kda)
    _close(o, want_o, "o")
    _close(s[:2], want_s[:2], "state")


@pytest.mark.parametrize("decay", [1.0, 0.999], ids=["no_decay", "slow"])
@pytest.mark.parametrize("keys", ["repeated", "alternating"])
def test_the_kernel_holds_beta_near_2_on_keys_that_repeat(keys, decay):
    """tests/test_solar_open2.py's worst case for the solve — ``beta`` 1.99,
    ONE unit key (or its alternating sign) through two full pieces and a
    ragged one, decay 1 or 0.999 a row — through the kernel: its block
    forward substitution is ``unit_lower_inverse``'s, levels and all.  (On
    this CPU the LOOP reads up to 4.9e-5 on such keys — tests/
    test_solar_open2.py's own operands, state, no decay — and the kernel
    between 1.1e-5 and 3.9e-5 on these: the same 32-term float32 sums in
    another order.)"""
    rows, heads = 70, 3
    req, pos = layout([(0, 0, rows)])
    operands = draw(np.random.default_rng(7), rows, heads, D, keys=keys,
                    decay=decay, beta=1.99)
    kda = jnp.zeros((2, heads, D, D), jnp.float32)
    (o, s), (loop_o, _) = _forms(
        KimiDeltaAttention(64, heads, D, chunk=PIECE, allow_neg_eigval=True),
        operands, kda, req, pos, 1)
    want_o, want_s = recurrence64(*operands, req, pos, kda)
    _close(o, want_o, "o")
    _close(s[:1], want_s[:1], "state")
    _close(o, loop_o, "o against the loop")


def test_a_segment_is_carried_from_chunk_to_chunk():
    """A prompt of 100 rows in chunks of 48 rows (48, 48, 4): each chunk
    ends INSIDE the segment, so its last piece writes the slot's row and the
    next chunk's first piece reads it (``STORED``); the pieces between take
    the state the piece before left in the kernel's scratch (``CONTINUE``)
    and touch no row of the state array."""
    heads, slots, rows = 3, 2, 100
    rng = np.random.default_rng(11)
    operands = draw(rng, rows, heads, D)
    op = KimiDeltaAttention(64, heads, D, chunk=PIECE, allow_neg_eigval=True)
    kda = jnp.asarray(rng.standard_normal((slots + 1, heads, D, D)),
                      jnp.float32)
    want_o, want_s = recurrence64(*operands, [1] * rows, range(rows), kda)
    s, outs = kda, []
    for lo in range(0, rows, 48):
        n = min(48, rows - lo)
        req, pos = layout([(1, lo, n), (-1, 0, 48 - n)])
        seg = segments(req, pos, slots)
        count, first, own, row, init, last = (
            np.asarray(a) for a in op._pieces(seg))
        p = int(count)
        assert p == -(-n // PIECE) and first[:p].tolist() == [0, 32][:p]
        assert init[:p].tolist() == [ZEROS if lo == 0 else STORED,
                                     CONTINUE][:p]
        assert last[:p].tolist() == [0, 1][2 - p:] and (row[:p] == 1).all()
        part = tuple(jnp.pad(a[lo:lo + n], ((0, 48 - n),) + ((0, 0),) *
                             (a.ndim - 1)) for a in operands)
        o, s = delta_rule_chunk(s, *part, op._pieces(seg), chunk=PIECE,
                                interpret=True)
        outs.append(o[:n])
        assert np.array_equal(np.asarray(s)[[0, 2]], np.asarray(kda)[[0, 2]])
    _close(jnp.concatenate(outs), want_o, "o")
    _close(s[1], want_s[1], "state")


@pytest.mark.parametrize("use_pallas", [False, True], ids=["xla", "pallas"])
def test_the_layer_takes_the_kernel_by_the_flag_and_says_so(use_pallas):
    """``KimiDeltaAttention.lower`` on a flat batch: the FORM's note reads
    ``chunked`` either way (the benchmark's tests pin it), the pieces'
    implementation has a key of its own, and both give the same layer
    output."""
    heads, e, rows, slots = 4, 32, 40, 2
    op = KimiDeltaAttention(e, heads, D, chunk=PIECE)
    rng = np.random.default_rng(3)
    normal = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
    params = {p.name: normal(*p.spec.shape) for p in op.params()}
    qkv, x = normal(rows, 3 * heads * D), normal(rows, e)
    kda = normal(slots + 1, heads, D, D)
    req, pos = layout([(1, 6, 37), (-1, 0, 3)])
    bc = BatchConfig(tokens=jnp.asarray(pos, jnp.int32),
                     request_index=jnp.asarray(req, jnp.int32),
                     token_position=jnp.asarray(pos, jnp.int32),
                     num_tokens=jnp.int32(37),
                     seq_lens=jnp.zeros((slots,), jnp.int32))

    def run(pallas):
        paths = {}
        ctx = OpContext(extras={
            "node_name": "n", "batch_config": bc, "state": {"kda": kda},
            "attention_paths": paths, "pallas_decode": pallas,
            "pallas_interpret": pallas})
        y = op.lower(ctx, [qkv, x], params)[0]
        return y, ctx.extras["state_out"]["kda"], paths

    y, s, paths = run(use_pallas)
    assert paths[("kimi_delta_attention", "BatchConfig")] == "chunked"
    assert paths[("delta_pieces", "kimi_delta_attention")] == (
        "delta_rule_chunk" if use_pallas else "xla_loop")
    want_y, want_s, _ = run(False)
    _close(y, want_y, "y")
    _close(s[:slots], want_s[:slots], "state")
