"""Pallas TPU kernel: Mamba-1's selective scan over the rows of a flat batch.

``serve/hybrid_ops.SelectiveScan`` walks a flat batch's rows in order,
``h_t = exp(delta_t (x) A) * h_{t-1} + dx_t (x) B_t``, ``y_t = h_t . C_t``,
carrying ``h [C, N]`` across a segment (a run of one request's consecutive
positions) and exchanging it with the slot's stored state at the segment's
two ends.  As XLA runs it — one ``lax.scan`` trip per row — every row is a
handful of separate device operations on ``[C, N]`` tensors in HBM, strictly
serial, with the device idle between them (on a v5e 2.6 ms per layer for a
512-row chunk at 5120 channels: PERF.md, PR 43).  This kernel runs the same
recurrence, row by row and in float32, with ``h`` in vector registers:

* **layout**: channels in lanes.  A block of ``8 x 128`` channels is ONE
  float32 vreg per state entry ``n``, so ``h`` is ``N`` vregs, ``delta_t`` and
  ``dx_t`` one each, and ``B_t[n]`` / ``C_t[n]`` are SCALARS (read from SMEM,
  splat): the update is elementwise and ``y_t = sum_n h[n] * C_t[n]`` needs
  no cross-lane reduce.  The wrapper relayouts the state ``[R, C, N]`` (the
  layout the decode scan reads, kept in HBM) to ``[R, N, C / 128, 128]`` and
  back around the call: two passes over one layer's state, a sixth of the
  call's time on the chip.
* **grid** = (channel blocks, row blocks), rows the minor axis: ``h`` carries
  from one row block to the next in a VMEM scratch, and the channel block of
  ALL the slots' states stays resident in VMEM (the output block's index does
  not move along the row axis), so a segment's first row reads its slot's
  state there, its last row writes it there, and the rows between touch no
  HBM beyond their own ``delta`` / ``dx`` / ``y``.
* ``start`` / ``fresh`` / ``rows`` / ``store`` (``hybrid_ops.Segments``'
  fields) and the flattened ``B`` / ``C`` ride scalar prefetch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _VMEM_SCOPED_LIMIT

LANES = 128
SUBLANES = 8      # channel block = 8 x 128: one float32 vreg per state entry
ROW_BLOCK = 128   # rows per grid step


def _scan_kernel(start_ref, fresh_ref, rows_ref, store_ref, b_ref, c_ref,
                 delta_ref, dx_ref, a_ref, hs_ref, y_ref, hs_out_ref, h_ref,
                 *, n_state, row_block, scratch_row):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        hs_out_ref[...] = hs_ref[...]
        h_ref[...] = jnp.zeros_like(h_ref)

    a = [a_ref[n] for n in range(n_state)]

    def row(r, h):
        t = i * row_block + r
        at = rows_ref[t]

        def opened():
            # a segment's first row: the slot's stored state, or zero
            keep = fresh_ref[t] == 0
            return tuple(jnp.where(keep, hs_out_ref[at, n], 0.0)
                         for n in range(n_state))

        h = jax.lax.cond(start_ref[t] != 0, opened, lambda: h)
        delta, dx = delta_ref[r], dx_ref[r]
        new, y = [], None
        for n in range(n_state):
            hn = jnp.exp(delta * a[n]) * h[n] + dx * b_ref[t * n_state + n]
            term = hn * c_ref[t * n_state + n]
            y = term if y is None else y + term
            new.append(hn)
        y_ref[r] = y
        to = store_ref[t]

        # the scratch row is what every row but a segment's last stores to;
        # nothing reads it (a pad is fresh), so those stores are left out
        @pl.when(to != scratch_row)
        def _():
            for n in range(n_state):
                hs_out_ref[to, n] = new[n]

        return tuple(new)

    h = jax.lax.fori_loop(0, row_block, row,
                          tuple(h_ref[n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[n] = h[n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_rows(delta, dx, b, c, a, hs, start, fresh, rows, store, *,
                        interpret: bool = False):
    """``(y [T, C], hs')`` of the rows' recurrence.

    ``delta``, ``dx [T, C]``, ``b``, ``c [T, N]``, ``a [C, N]``, the slots'
    states ``hs [R + 1, C, N]`` (row ``R`` the scratch row), all float32;
    ``start`` / ``fresh`` ``[T]`` bool and ``rows`` / ``store`` ``[T]`` int32
    as ``hybrid_ops.Segments`` has them.  ``C`` is a multiple of 128.
    """
    t, ch = delta.shape
    r1, _, n = hs.shape
    if ch % LANES:
        raise ValueError(f"{ch} channels do not fill whole lanes of {LANES}")
    sub = ch // LANES
    sub_b = SUBLANES if sub % SUBLANES == 0 else sub
    row_b = ROW_BLOCK if t % ROW_BLOCK == 0 else t

    lanes = lambda x: x.reshape(x.shape[0], sub, LANES)
    hst = hs.transpose(0, 2, 1).reshape(r1, n, sub, LANES)
    rows_spec = pl.BlockSpec((row_b, sub_b, LANES),
                             lambda j, i, *_: (i, j, 0),
                             memory_space=pltpu.VMEM)
    state_spec = pl.BlockSpec((r1, n, sub_b, LANES),
                              lambda j, i, *_: (0, 0, j, 0),
                              memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(sub // sub_b, t // row_b),
        in_specs=[
            rows_spec, rows_spec,
            pl.BlockSpec((n, sub_b, LANES), lambda j, i, *_: (0, j, 0),
                         memory_space=pltpu.VMEM),
            state_spec,
        ],
        out_specs=[rows_spec, state_spec],
        scratch_shapes=[pltpu.VMEM((n, sub_b, LANES), jnp.float32)],
    )
    block = 4 * sub_b * LANES
    # double-buffered: delta, dx, y; a; the state in and out; the carry
    need = block * (2 * (3 * row_b + n + 2 * r1 * n) + n)
    y, hst = pl.pallas_call(
        functools.partial(_scan_kernel, n_state=n, row_block=row_b,
                          scratch_row=r1 - 1),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, sub, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(hst.shape, jnp.float32)],
        # operand 9 (after the six prefetched scalars): the state, in place
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(_VMEM_SCOPED_LIMIT, need + 4 * 2**20)),
        interpret=interpret,
    )(start.astype(jnp.int32), fresh.astype(jnp.int32),
      rows.astype(jnp.int32), store.astype(jnp.int32),
      b.reshape(-1), c.reshape(-1), lanes(delta), lanes(dx), lanes(a.T), hst)
    return y.reshape(t, ch), hst.reshape(r1, n, ch).transpose(0, 2, 1)
