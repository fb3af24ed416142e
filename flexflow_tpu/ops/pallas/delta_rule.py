"""Pallas TPU kernel: one decode step of a gated DELTA rule with a
per-channel decay (Kimi Delta Attention, ``serve/hybrid_ops.py``
``KimiDeltaAttention``), the state updated IN PLACE.

Per live row and head, with the state ``S [key channel, value channel]``
float32:

    S' = Diag(alpha) S;   S <- S' + (beta k) (v - S'^T k)^T;   o = S^T q

The correction needs ``S'^T k`` — a reduction over the state — BEFORE it can
write the state, so XLA's fusion reads a row's 2 MB (32 heads x 128 x 128
float32) twice: once to reduce, once to update and read out.  Here a (row,
head group) tile of the state comes into VMEM ONCE, is decayed, reduced,
corrected and read out there, and goes back ONCE, to where it came from
(``input_output_aliases``; the rows ride scalar prefetch as ``kv_row_write``'s
do).

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
