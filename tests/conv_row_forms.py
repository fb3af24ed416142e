"""``CausalConv1d``'s row form as it was and as it might have been — the
oracles of ``scripts/conv_rows_bench.py`` and the control of the AOT reading
in ``tests/test_tpu_aot_compile.py``; the tree's form is
``CausalConv1d._rows``.  Same signature as it: ``f(op, x, tails, seg, w, b)
-> (y, tails)``."""

import jax
import jax.numpy as jnp

from flexflow_tpu.serve.hybrid_ops import _set_rows


def shifted(x, back):
    return jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]], axis=0)


def gather_scatter(op, x, tails, seg, w, b):
    """The row form before PR 67."""
    k = op.kernel
    tail = tails[seg.rows]                       # [T, K-1, C]
    taps = [x]
    for back in range(1, k):
        at = jnp.clip(k - 1 + seg.offset - back, 0, k - 2)
        stored = jnp.take_along_axis(tail, at[:, None, None], axis=1)[:, 0]
        val = jnp.where((seg.offset >= back)[:, None], shifted(x, back),
                        stored)
        taps.append(jnp.where(((seg.pos >= back) & seg.live)[:, None],
                              val, 0))
    y = op._taps_out(taps, w, b)
    left = jnp.stack(taps[k - 2::-1], axis=1)
    return y, _set_rows(tails, seg.store, left)


def onehot(op, x, tails, seg, w, b):
    """Form (i): a tap's stored entries come to their rows by ONE product
    of a one-hot ``[T, (slots + 1)(K - 1)]`` with the tails laid flat (bf16
    x 1.0 summed in float32 is exact).  The new tails: the tree's form."""
    k, n = op.kernel, tails.shape[0] * (op.kernel - 1)
    flat = tails.reshape(n, -1)
    exact = (jax.lax.Precision.HIGHEST if flat.dtype == jnp.float32
             else None)
    taps = [x]
    for back in range(1, k):
        at = seg.rows * (k - 1) + jnp.clip(k - 1 + seg.offset - back, 0,
                                           k - 2)
        need = (seg.offset < back) & (seg.pos >= back) & seg.live
        pick = (need[:, None] & (at[:, None] == jnp.arange(n))).astype(
            flat.dtype)
        stored = jnp.dot(pick, flat, precision=exact,
                         preferred_element_type=flat.dtype)
        taps.append(jnp.where((seg.offset >= back)[:, None],
                              shifted(x, back), stored))
    return op._taps_out(taps, w, b), op._rows(x, tails, seg, w, b)[1]
