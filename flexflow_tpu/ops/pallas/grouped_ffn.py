"""Pallas TPU kernels: the grouped GEMMs of a routed-expert layer where a
call brings a ROW TILE OR MORE per held expert (a prompt chunk of a graph
that holds every expert its router scores: ``serve/ssd_moe_ops.py``
``MoEExperts``).

``lhs [M, K]`` holds the (row, choice) pairs sorted by expert, ``sizes [E]``
the rows of each held expert, the weights are ``[E, K, N]``.  As in
megablox's ``gmm`` (whose metadata this reuses: active tiles only, a dynamic
grid) a grid step is one (group, row tile) pair, a row tile that two groups
share is visited once by each and the rows of the other are masked out of
the store, and an expert no pair chose costs nothing.  What differs is what
a group that is SEVERAL steps long wants:

* **the weights are fetched a GROUP ahead.**  They stay in HBM
  (``memory_space=pl.ANY``); a group's blocks are copied into one of two
  VMEM slots by the kernel's own DMA, started when the PREVIOUS visited
  group's first step starts and waited for at this group's first step.  The
  pipeline's own prefetch asks for a block one grid step before it is
  needed: a 4 MB matrix then has one 128-row step to hide behind, where a
  group of 128-900 rows gives it 1-7.
* **gate and up are one call, the product in the epilogue** (``swiglu``:
  ``silu(x gate) * (x up)``; ``relu2``: ``max(x up, 0)^2``): the row tile is
  read once, both products accumulate in float32, the activation is taken in
  float32 and cast ONCE to the output's type — what the three-call form
  does, without ``[M, N]`` float32 going out to HBM twice and coming back.
  ``linear`` (the down projection) is the same kernel with one matrix and no
  activation.

The output's columns are tiled (``tn``) only where two slots of a group's
``[K, N]`` blocks do not fit the budget; a ragged last tile copies its own
width.  The grid is ``(column tiles, steps)``, both sequential: the slots
alternate along the WALK over (column tile, visited group), so the first
group of the next column tile is fetched behind the last group of this one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

FORMS = ("swiglu", "relu2", "linear")
# VMEM slots a weight's blocks alternate between: one read, one filled (three
# and four read the same times on the chip: PERF.md, PR 62)
SLOTS = 2
# what the two weight slots, the pipeline's row and output tiles and the
# float32 results may take of the v5e's 128 MiB of VMEM
VMEM_BUDGET = 48 * 2**20


def working_set(tm: int, k: int, tn: int, itemsize: int, out_itemsize: int,
                num_weights: int) -> int:
    """Bytes of VMEM a step holds: ``SLOTS`` of each weight block, the
    pipeline's two buffers of the row tile and of the output tile, and the
    float32 products with the activation's copy."""
    weights = num_weights * SLOTS * k * tn * itemsize
    rows = 2 * tm * k * itemsize
    out = 2 * tm * tn * out_itemsize
    return weights + rows + out + (num_weights + 1) * tm * tn * 4


def out_tile(tm: int, k: int, n: int, itemsize: int, out_itemsize: int,
             num_weights: int) -> int:
    """``n`` whole where the working set fits ``VMEM_BUDGET``; else the
    fewest column tiles that do, evened out to whole lanes (the last may be
    ragged)."""
    fits = lambda tn: working_set(tm, k, tn, itemsize, out_itemsize,
                                  num_weights) <= VMEM_BUDGET
    if fits(n):
        return n
    tiles = 2
    while not fits(-(-n // (tiles * 128)) * 128):
        tiles += 1
    return -(-n // (tiles * 128)) * 128


def _kernel(offs_ref, gids_ref, mids_ref, rank_ref, visited_ref, nv_ref,
            lhs_ref, *refs, form, tm, tn, n):
    nw = 2 if form == "swiglu" else 1
    w_hbm, out_ref, bufs, sem = refs[:nw], refs[nw], refs[nw + 1:2 * nw + 1], \
        refs[2 * nw + 1]
    n_i, s = pl.program_id(0), pl.program_id(1)
    tiles_n = pl.num_programs(0)
    g, nv = gids_ref[s], nv_ref[0]
    # the walk over (column tile, visited group), flattened: this step's
    # place in it, and its length
    here, end = n_i * nv + rank_ref[s], tiles_n * nv
    slot = here % SLOTS
    last_tn = n - (-(-n // tn) - 1) * tn       # the last column tile's width

    def copies(at, act):
        """Start or wait for every weight's block of place ``at``."""
        group, col, slot = visited_ref[at % nv], at // nv, at % SLOTS

        def each(width):
            for i in range(nw):
                src = w_hbm[i].at[group] if width == n else \
                    w_hbm[i].at[group, :, pl.ds(col * tn, width)]
                dst = bufs[i].at[slot] if width == tn else \
                    bufs[i].at[slot, :, pl.ds(0, width)]
                act(pltpu.make_async_copy(src, dst, sem.at[i, slot]))

        if last_tn == tn:
            each(tn)
        else:
            pl.when(col < tiles_n - 1)(lambda: each(tn))
            pl.when(col == tiles_n - 1)(lambda: each(last_tn))

    start = lambda c: c.start()
    first = (s == 0) | (g != gids_ref[jnp.maximum(s - 1, 0)])

    @pl.when(first)
    def _():
        # the very first step starts its own copies; every group's first
        # step those of the NEXT place of the walk, into the slot the group
        # before this one has left
        pl.when(here == 0)(lambda: copies(here, start))
        pl.when(here + 1 < end)(lambda: copies(here + 1, start))
        copies(here, lambda c: c.wait())

    x = lhs_ref[...]
    dot = lambda w: jnp.dot(x, w[slot], preferred_element_type=jnp.float32)
    if form == "swiglu":
        h = jax.nn.silu(dot(bufs[0])) * dot(bufs[1])
    elif form == "relu2":
        h = jnp.square(jnp.maximum(dot(bufs[0]), 0.0))
    else:
        h = dot(bufs[0])
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0) + mids_ref[s] * tm
    mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
    out_ref[...] = jnp.where(mine, h.astype(out_ref.dtype), out_ref[...])


@functools.partial(jax.jit, static_argnames=("form", "out_dtype", "tm", "tn",
                                             "interpret"))
def grouped_ffn(lhs, weights, sizes, *, form, out_dtype, tm=128, tn=None,
                interpret=False):
    """``act(lhs[rows of e] @ weights[..][e])`` for every expert ``e`` with
    rows: ``[M, N]`` in ``out_dtype``; rows past the groups' sum are left as
    they were allocated.  ``weights``: ``(gate, up)`` for ``swiglu``, one
    matrix for ``relu2`` and ``linear``; ``M`` a multiple of ``tm``."""
    assert form in FORMS, form
    nw = 2 if form == "swiglu" else 1
    assert len(weights) == nw, (form, len(weights))
    m, k = lhs.shape
    e, _, n = weights[0].shape
    itemsize = jnp.dtype(weights[0].dtype).itemsize
    out_itemsize = jnp.dtype(out_dtype).itemsize
    if tn is None:
        tn = out_tile(tm, k, n, itemsize, out_itemsize, nw)
    tiles_n = -(-n // tn)
    (offs, gids, mids), steps = make_group_metadata(
        group_sizes=sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=e, visit_empty_groups=False)
    # the visited groups in order, and each step's group's rank among them
    visited = jnp.argsort(sizes == 0, stable=True).astype(jnp.int32)
    nv = jnp.maximum(jnp.sum(sizes > 0, dtype=jnp.int32), 1)
    rank = jnp.cumsum(sizes > 0, dtype=jnp.int32)[gids] - 1
    kernel = functools.partial(_kernel, form=form, tm=tm, tn=tn, n=n)
    need = working_set(tm, k, tn, itemsize, out_itemsize, nw)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(tiles_n, steps),
            in_specs=[pl.BlockSpec((tm, k), lambda j, s, o, g, mi, *_:
                                   (mi[s], 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * nw,
            out_specs=pl.BlockSpec((tm, tn), lambda j, s, o, g, mi, *_:
                                   (mi[s], j)),
            scratch_shapes=[pltpu.VMEM((SLOTS, k, tn), weights[0].dtype)] * nw
            + [pltpu.SemaphoreType.DMA((nw, SLOTS))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=need + need // 4 + 4 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=2 * nw * m * k * n, transcendentals=m * n * (nw - 1),
            bytes_accessed=m * k * itemsize * tiles_n
            + nw * e * k * n * itemsize + m * n * out_itemsize),
        interpret=interpret,
        name=f"grouped_ffn_{form}",
    )(offs, gids, mids, rank, visited, nv[None], lhs, *weights)
