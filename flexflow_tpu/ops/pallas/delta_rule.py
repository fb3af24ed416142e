"""Pallas TPU kernels of a gated DELTA rule with a per-channel decay (Kimi
Delta Attention, ``serve/hybrid_ops.py`` ``KimiDeltaAttention``), the state
updated IN PLACE: ``delta_rule_step`` — one decode step of every live row —
and ``delta_rule_chunk`` — the chunked form of a prompt's pieces.

Per live row and head, with the state ``S [key channel, value channel]``
float32:

    S' = Diag(alpha) S;   S <- S' + (beta k) (v - S'^T k)^T;   o = S^T q

**The step kernel.**  The correction needs ``S'^T k`` — a reduction over the
state — BEFORE it can write the state, so XLA's fusion reads a row's 2 MB (32
heads x 128 x 128 float32) twice: once to reduce, once to update and read
out.  Here a (row, head group) tile of the state comes into VMEM ONCE, is
decayed, reduced, corrected and read out there, and goes back ONCE, to where
it came from (``input_output_aliases``; the rows ride scalar prefetch as
``kv_row_write``'s do).

* **layout**: the value channel in lanes, the key channel in sublanes, so a
  head's matrix is 16 float32 vregs and both reductions (over the KEY
  channel) are sums of vregs and one sublane reduce — no cross-lane reduce.
  ``alpha``, ``k``, ``beta k`` and ``q`` vary along the key channel, i.e.
  along SUBLANES, while they arrive lane-major like every row vector: the
  group's vectors (``heads x 8`` rows of 128) are transposed once a step, on
  the XLU, and a vector is then one column of the result, broadcast along
  lanes.  ``v`` and ``o`` vary along lanes and are used as they come.
* **grid** = (head groups, rows), rows the MINOR axis.  A pad row (a slot of
  the batch no request holds) is given the block index of the last live row
  before it and does nothing: the pipeline neither fetches nor writes a
  block whose index did not change, so a pad costs no state traffic and
  touches no state.  Pads BEFORE the first live row sit on the scratch row
  (the last) and copy it through.

**The chunk kernel.**  A PIECE is a run of at most ``chunk`` (32) rows of one
request; entered with ``S0``, ``G`` the running sum of the rows' log decays
``g`` (<= 0), it leaves (``KimiDeltaAttention``'s docstring has the algebra)

    A_ij = sum_d k_i k_j exp(G_i - G_j)  (j < i),   B_ij likewise with q_i (j <= i)
    U = (I + Diag(beta) A)^-1 Diag(beta) (V - (K exp G) S0)
    o = (q exp G) S0 + B U;     S = Diag(exp G_C) S0 + (K exp(G_C - G))^T U

XLA ran a loop trip a piece (~20 operations, the ``[32, 32, heads, 128]``
float32 tensor of ``exp(G_i - G_j)`` through HBM: 33.5 MB at 64 heads).  Here
ONE call a layer runs every piece of a flat batch or scan chunk; nothing of a
piece goes to HBM but its rows, and a head's state stays in VMEM from a
segment's first piece to its last.

* **layout**: rows in sublanes, a head's 128 channels in lanes — ``q``,
  ``k``, ``v``, ``g`` as ``[T, heads x 128]``, a head a static lane slice, no
  transpose on the way in or out; ``beta`` as ``[T, groups x 128]``, a
  group's heads in its tile's first lanes.  The heads' ``[32, 32]`` systems
  (``A``, ``B``, the inverse) lie FOUR SIDE BY SIDE in a tile's 128 lanes:
  the products that make them are one ``[4 x 64, 128] x [128, 4 x 32]``
  contraction a level (a head's own lanes kept), the solve's are ``[32,
  128] x`` a block diagonal ``[128, 128]`` — a quarter of the MXU passes
  one head at a time would take.
* **no ``exp(-G)``, no ``[32, 32, 128]`` tensor**: the pairs ``(i, j)`` split
  by the LEVEL at which they part — ``s`` = 16, 8, 4, 2, 1: ``i`` in the
  second half of a ``2 s`` block, ``j`` in its first —, and at a level
  ``exp(G_i - G_j) = exp(G_i - G_ref) exp(G_ref - G_j)`` with ``G_ref`` the
  first half's last row: both exponents are ``-|G - G_ref|`` <= 0, ONE
  ``exp`` of the piece's rows a level, and ``A`` and ``B`` at that level one
  matrix product ``[k e; q e] (k e)^T`` masked to the level's pairs.
* **precision**: every product is float32 — ``precision=HIGHEST`` reaches
  Mosaic as ``contract_precision<fp32>`` (the bf16 passes of XLA's HIGHEST;
  the default in a kernel is ONE bf16 pass); ``G`` is a running sum by
  doubling on the VPU.  ``scripts/delta_chunk_bench.py`` reads it against the
  float64 recurrence ON THE CHIP.  The solve is ``unit_lower_inverse``'s
  block forward substitution, level for level.
* **grid** = (head groups, pieces), pieces the MINOR axis and a DYNAMIC
  bound (``pieces[0]``: a chunk of 1024 rows is 32 grid steps a group, not
  1024).  Scalar prefetch a piece: its first row, how many of the 32 rows
  from there are its own, its slot's state row, where its entering state is
  (``CONTINUE``: what the piece before left in the scratch; ``STORED``: one
  copy in from the slot's row; ``ZEROS``) and whether it holds its segment's
  last row (then ONE copy out to the slot's row — else the state array is
  not touched).  The rows come as a 40-row WINDOW from the sublane tile the
  piece starts in (``pl.Element``: a window starts on any tile and may run
  past the batch's end) and are rolled to its top where the piece starts
  inside a tile (a flat step's; a tiled chunk's pieces start on tiles and
  skip the roll).  Rows that are not the piece's own are zeroed on the way
  in — a pad or foreign row costs nothing but its share of the window — and
  a one-row piece (a flat step's decode row) costs a whole piece.
* **the output** goes out by window too, one copy at a time in the pieces'
  order (a ragged piece's window overlaps the next one's, and a later copy
  must land later); what earlier pieces left in the window's first tile is
  carried in VMEM (``tail``) and put there again.  Rows of no piece are
  never written: the caller masks them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _VMEM_SCOPED_LIMIT

# rows of a head's packed vectors ``x [T, H, VECTORS, D]``
ALPHA, K, KB, Q, V = range(5)
VECTORS = 8       # padded to a whole sublane tile
LIVE, SKIP, COPY = range(3)
HEAD_GROUP = 16   # 16 heads x 8 vectors: one 128 x 128 transpose a step


def _delta_step_kernel(rows_ref, how_ref, x_ref, s_ref, o_ref, s_out_ref, *,
                       heads):
    del rows_ref                       # the index maps' alone
    how = how_ref[pl.program_id(1)]

    @pl.when(how == LIVE)
    def _():
        x = x_ref[0]                                   # [heads, 8, D]
        d = x.shape[-1]
        # [D, heads x 8]: vector i of head h is column 8 h + i
        xt = x.reshape(heads * VECTORS, d).T
        for h in range(heads):
            col = lambda i: xt[:, h * VECTORS + i:h * VECTORS + i + 1]
            s = s_ref[0, h] * col(ALPHA)               # S' [key, value]
            u = x[h, V:V + 1] - jnp.sum(s * col(K), axis=0, keepdims=True)
            s = s + col(KB) * u
            s_out_ref[0, h] = s
            o_ref[0, h:h + 1] = jnp.sum(s * col(Q), axis=0, keepdims=True)

    @pl.when(how == COPY)
    def _():
        s_out_ref[...] = s_ref[...]

    @pl.when(how != LIVE)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def head_group(num_heads: int) -> int:
    """Heads a grid step holds: ``HEAD_GROUP`` where it divides them (one
    whole 128 x 128 transpose a step), else all of them."""
    return HEAD_GROUP if num_heads % HEAD_GROUP == 0 else num_heads


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_rule_step(
    state: jax.Array,    # f32[R+1, H, D, D] (key channel, value channel)
    alpha: jax.Array,    # f32[T, H, D] the step's decay (0 on a fresh row)
    k: jax.Array,        # f32[T, H, D]
    kb: jax.Array,       # f32[T, H, D] beta * k
    q: jax.Array,        # f32[T, H, D]
    v: jax.Array,        # f32[T, H, D]
    rows: jax.Array,     # i32[T] state row per batch row
    live: jax.Array,     # bool[T]
    interpret: bool = False,
):
    """One decode step of every live row: ``(o f32[T, H, D], state)``, the
    state updated in place, each live row's matrices read once and written
    once.  The caller's contract (the decode scan: ``one_row_per_request``):
    no two LIVE rows name one state row.  A row that is not live reads and
    writes no slot's state and gets ``o = 0``."""
    r1, h, d, _ = state.shape
    t = rows.shape[0]
    hg = head_group(h)
    zeros = jnp.zeros_like(q)
    x = jnp.stack([alpha, k, kb, q, v, zeros, zeros, zeros], axis=2)
    idx = jnp.arange(t, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1), axis=0)
    at = jnp.where(before >= 0, rows[jnp.maximum(before, 0)], r1 - 1)
    how = jnp.where(live, LIVE, jnp.where(before >= 0, SKIP, COPY))
    tile = pl.BlockSpec((1, hg, d, d), lambda j, i, at, how: (at[i], j, 0, 0),
                        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(h // hg, t),
        in_specs=[pl.BlockSpec((1, hg, VECTORS, d),
                               lambda j, i, *_: (i, j, 0, 0),
                               memory_space=pltpu.VMEM), tile],
        out_specs=[pl.BlockSpec((1, hg, d), lambda j, i, *_: (i, j, 0),
                                memory_space=pltpu.VMEM), tile],
    )
    block = hg * d * d * 4
    o, state = pl.pallas_call(
        functools.partial(_delta_step_kernel, heads=hg),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, h, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count from the first scalar-prefetch argument
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the tile in and out, two buffers each, and the body's copies
            vmem_limit_bytes=max(_VMEM_SCOPED_LIMIT, 8 * block + 4 * 2**20)),
        interpret=interpret,
    )(jnp.clip(at.astype(jnp.int32), 0, r1 - 1), how.astype(jnp.int32),
      x.astype(jnp.float32), state)
    return o, state


# ---- the chunked form's pieces ---------------------------------------------

CONTINUE, STORED, ZEROS = range(3)   # where a piece's entering state is
LANE, SUBLANE = 128, 8


def _piece_constants(c, pack):
    """What every piece shares, from iotas, for ``pack`` heads' ``[c, c]``
    matrices side by side in ``wide = pack c`` lanes: a level ``s = c/2,
    c/4, .., 1``'s mask ``[c, wide]`` (row in a ``2 s`` block's second half,
    column in its first), the diagonals', the lanes' head, and the block
    diagonal ``[wide, wide]`` (row and column of one head)."""
    wide = pack * c
    i = jax.lax.broadcasted_iota(jnp.int32, (c, wide), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, wide), 1)
    j = lane % c
    masks, s = [], c // 2
    while s:
        masks.append((i // (2 * s) == j // (2 * s))
                     & (i % (2 * s) >= s) & (j % (2 * s) < s))
        s //= 2
    same = jax.lax.broadcasted_iota(jnp.int32, (wide, wide), 0) // c \
        == jax.lax.broadcasted_iota(jnp.int32, (wide, wide), 1) // c
    return masks, i == j, lane // c, same


def _level_rows(run, s):
    """``run [c, lanes]`` with every row replaced by its ``2 s`` block's last
    first-half row (the level's reference): sublane broadcasts, a tile of 8
    rows at a time."""
    c, lanes = run.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, 1), 0)
    tiles = []
    for lo in range(0, c, SUBLANE):
        starts = sorted({(i // (2 * s)) * (2 * s)
                         for i in range(lo, lo + SUBLANE)})
        pick = lambda b: jnp.broadcast_to(run[b + s - 1:b + s],
                                          (SUBLANE, lanes))
        tile = pick(starts[0])
        for b in starts[1:]:
            tile = jnp.where(at >= b - lo, pick(b), tile)
        tiles.append(tile)
    return jnp.concatenate(tiles, axis=0)


def _delta_chunk_kernel(first_ref, own_ref, row_ref, init_ref, last_ref,
                        q_ref, k_ref, v_ref, g_ref, b_ref, state_ref,
                        o_ref, state_out_ref, s_ref, o_buf, tail, tail_at,
                        o_sem, s_sem, *, heads, d, c):
    j, p = pl.program_id(0), pl.program_id(1)
    slot = p % 2
    w = c + SUBLANE
    # the window: the piece's rows from ``shift`` on, in whole sublane tiles
    base = lambda step: (first_ref[step] // SUBLANE) * SUBLANE
    shift = first_ref[p] - base(p)
    own_state = lambda ref: ref.at[row_ref[p], pl.ds(j * heads, heads)]

    @pl.when(init_ref[p] == STORED)
    def _():
        copy = pltpu.make_async_copy(own_state(state_ref), s_ref, s_sem)
        copy.start()
        copy.wait()

    @pl.when(init_ref[p] == ZEROS)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(p == 0)
    def _():
        tail_at[0] = -1

    # float32 products: Mosaic's ``contract_precision<fp32>`` (the bf16
    # passes of XLA's HIGHEST); its default is ONE bf16 pass
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    # a head a batch entry: [heads, rows, columns] operands
    mm = lambda a, b: dot(a, b, (((2,), (1,)), ((0,), (0,))))  # a b
    nt = lambda a, b: dot(a, b, (((2,), (2,)), ((0,), (0,))))  # a b^T
    by_head = lambda x: jnp.stack(
        [x[:, h * d:(h + 1) * d] for h in range(heads)])
    pack = max(n for n in range(1, LANE // c + 1) if heads % n == 0)
    packs, wide = heads // pack, pack * c
    masks, diag, lane_head, same = _piece_constants(c, pack)
    by_pack = lambda x: x.reshape(packs, pack * x.shape[1], x.shape[2])
    # a column a head [heads, c, 1] into its head's lanes [packs, c, wide]
    member = lambda x, i: jnp.stack([x[at] for at in range(i, heads, pack)])
    spread = lambda col: functools.reduce(
        lambda acc, i: jnp.where(lane_head == i, member(col, i), acc),
        range(1, pack), jnp.broadcast_to(member(col, 0), (packs, c, wide)))
    # the heads' [c, c] side by side [packs, c, wide] into a block diagonal
    blocks = lambda x: jnp.where(same, jnp.concatenate([x] * pack, axis=1),
                                 0.0)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    mine = row < own_ref[p]
    # the piece's rows to the window's top, the rows of others to zero
    top = lambda ref: jnp.where(mine, jax.lax.cond(
        shift == 0, lambda x: x, lambda x: pltpu.roll(x, w - shift, 0),
        ref[...])[:c], 0.0)
    q, k, v, g, beta = (top(ref) for ref in
                        (q_ref, k_ref, v_ref, g_ref, b_ref))
    beta = jnp.stack([beta[:, h:h + 1] for h in range(heads)])  # [H, c, 1]
    # G: the running sum of g down the rows, by doubling
    run, step = g, 1
    while step < c:
        run = run + jnp.where(row >= step, pltpu.roll(run, step, 0), 0.0)
        step *= 2
    a = jnp.zeros((packs, c, wide), jnp.float32)
    b = jnp.where(diag, spread(jnp.sum(by_head(q * k), axis=2,
                                       keepdims=True)), 0.0)
    for lv, mask in enumerate(masks):
        # exp(G_i - G_ref) in a block's second half, exp(G_ref - G_j) in
        # its first: both -|G - G_ref|, nothing above 0
        e = jnp.exp(-jnp.abs(run - _level_rows(run, c >> (lv + 1))))
        ke = by_head(k * e)
        # a pack's heads against each other's keys: a head's own lanes kept
        both = nt(by_pack(jnp.concatenate([ke, by_head(q * e)], axis=1)),
                  by_pack(ke))                       # [packs, 2 wide, wide]
        own = lambda at: functools.reduce(
            lambda acc, i: jnp.where(
                lane_head == i,
                both[:, 2 * c * i + at:2 * c * i + at + c], acc),
            range(1, pack), both[:, at:at + c])
        a = jnp.where(mask, own(0), a)
        b = jnp.where(mask, own(c), b)
    # (I + Diag(beta) A)^-1 by block forward substitution
    n = spread(beta) * a
    inv = jnp.where(diag, 1.0, jnp.where(masks[-1], -n, 0.0))
    for mask in masks[-2::-1]:
        inv = inv - mm(mm(inv, blocks(jnp.where(mask, n, 0.0))), blocks(inv))
    decayed = jnp.exp(run)
    s0 = s_ref[...]
    into = mm(jnp.concatenate([by_head(k * decayed), by_head(q * decayed)],
                              axis=1), s0)                     # [H, 2 c, d]
    u = mm(blocks(inv),
           by_pack(beta * (by_head(v) - into[:, :c])))     # [packs, wide, d]
    o = (by_pack(into[:, c:]) + mm(blocks(b), u)).reshape(heads, c, d)
    for h in range(heads):
        o_buf[slot, :c, h * d:(h + 1) * d] = o[h]
    end = run[c - 1:c]
    kd, left = by_head(k * jnp.exp(end - run)), jnp.exp(end)
    s_ref[...] = jnp.stack([left[:, h * d:(h + 1) * d].T
                            for h in range(heads)]) * s0 \
        + mm(jnp.stack([kd[h].T for h in range(heads)]),
             u.reshape(heads, c, d))

    # the window goes out whole: its rows back under the piece's, what
    # earlier pieces left in its first tile put there again, zeros after
    o_buf[slot, c:, :] = jnp.zeros((SUBLANE, heads * d), jnp.float32)

    @pl.when(shift != 0)
    def _():
        o_buf[slot] = pltpu.roll(o_buf[slot], shift, 0)

    @pl.when(tail_at[0] == base(p))
    def _():
        o_buf[slot, :SUBLANE, :] = o_buf[slot, :SUBLANE, :] + tail[...]

    # two pieces' windows may overlap: one copy out at a time, in order
    out = lambda step, buf: pltpu.make_async_copy(
        o_buf.at[buf],
        o_ref.at[pl.ds(pl.multiple_of(base(step), SUBLANE), w),
                 pl.ds(j * heads * d, heads * d)],
        o_sem.at[buf])

    @pl.when(p > 0)
    def _():
        out(p - 1, 1 - slot).wait()

    out(p, slot).start()
    # the tile the next piece may start in, as this one leaves it
    after = ((first_ref[p] + own_ref[p]) // SUBLANE) * SUBLANE
    tail[...] = o_buf[slot, pl.ds(pl.multiple_of(after - base(p), SUBLANE),
                                  SUBLANE), :]
    tail_at[0] = after

    @pl.when(p == pl.num_programs(1) - 1)
    def _():
        out(p, slot).wait()

    @pl.when(last_ref[p] == 1)
    def _():
        copy = pltpu.make_async_copy(s_ref, own_state(state_out_ref), s_sem)
        copy.start()
        copy.wait()


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def delta_rule_chunk(
    state: jax.Array,    # f32[R+1, H, D, D] (key channel, value channel)
    q: jax.Array,        # f32[T, H, D]
    k: jax.Array,        # f32[T, H, D]
    v: jax.Array,        # f32[T, H, D]
    g: jax.Array,        # f32[T, H, D] the rows' log decays (<= 0)
    beta: jax.Array,     # f32[T, H]
    pieces,              # (count i32[], first, own, row, init, last i32[T])
    chunk: int = 32,
    interpret: bool = False,
):
    """The chunked form of every piece of a flat batch: ``(o f32[T, H, D],
    state)``, the state updated in place.  ``pieces``: how many there are
    and, a piece in row order, its first row, how many of the ``chunk`` rows
    from there are its own, its slot's state row, where its entering state
    is (``CONTINUE``: what the piece before left, ``STORED``, ``ZEROS``) and
    whether it holds its segment's last row (then the slot's row is written).
    Rows of no piece get whatever: the caller masks them."""
    r1, h, d, _ = state.shape
    t, c = q.shape[0], chunk
    hg = head_group(h)
    w = c + SUBLANE
    count, first, own, row, init, last = pieces
    # a window starts on any sublane tile (every dimension by element) and
    # may run past the batch's end: the window's own padding on the chip,
    # rows of zeros for the interpreter (which refuses a padded window
    # beside an aliased operand)
    past = w if interpret else 0
    flat = lambda a: jnp.pad(a.astype(jnp.float32).reshape(t, -1),
                             ((0, past), (0, 0)))
    lanes = jnp.pad(beta.astype(jnp.float32).reshape(t, h // hg, hg),
                    ((0, 0), (0, 0), (0, LANE - hg)))
    window = lambda lanes: pl.BlockSpec(
        (pl.Element(w, (0, w - past)), pl.Element(lanes)),
        lambda j, p, first, *_: ((first[p] // SUBLANE) * SUBLANE, j * lanes),
        memory_space=pltpu.VMEM)
    rows = window(hg * d)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(h // hg, count),
        in_specs=[rows, rows, rows, rows, window(LANE), anywhere],
        out_specs=[anywhere, anywhere],
        scratch_shapes=[pltpu.VMEM((hg, d, d), jnp.float32),
                        pltpu.VMEM((2, w, hg * d), jnp.float32),
                        pltpu.VMEM((SUBLANE, hg * d), jnp.float32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA(())],
    )
    o, state = pl.pallas_call(
        functools.partial(_delta_chunk_kernel, heads=hg, d=d, c=c),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t + w, h * d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count from the first scalar-prefetch argument
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_SCOPED_LIMIT),
        interpret=interpret,
    )(first, own, row, init, last, flat(q), flat(k), flat(v), flat(g),
      flat(lanes), state)
    return o[:t].reshape(t, h, d), state
