"""Pipeline parallelism: explicit GPipe microbatch schedule over a mesh axis.

The reference has NO pipeline schedule engine (SURVEY.md §2.3: Legion's async
tasking gives only implicit cross-iteration pipelining), so this is a
capability the TPU rebuild adds outright.  Design: homogeneous stages laid
out along a ``pp`` mesh axis; stage parameters are stacked on a leading stage
dimension and sharded over the axis; activations hop stage→stage via
``ppermute``; a static-length loop runs the classic GPipe fill/steady/drain
schedule.  Reverse-mode autodiff through the loop (ppermute transposes to the
reverse rotation) yields the backward pipeline automatically — no hand-built
1F1B needed for correctness; the schedule is still bubble-bounded like GPipe.

Runs inside ``shard_map`` (explicit-collective layer, like ring attention).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x_micro: jax.Array,   # [n_micro, ...mb...] microbatched input
    axis_name: str,
    n_stages: int,
    broadcast: bool = True,
    feed_fn: Callable | None = None,
    act_shape: tuple | None = None,
    act_dtype=None,
) -> jax.Array:
    """Run ``n_stages`` pipelined applications of ``stage_fn``.

    ``stage_params``: pytree whose leaves carry this shard's stage slice with
    a leading stage dim of 1 (i.e. globally ``[n_stages, ...]`` sharded over
    ``axis_name``).  ``stage_fn(params, x) -> y`` must preserve the
    activation shape (homogeneous pipeline).  Returns ``[n_micro, ...]``
    outputs of the final stage, broadcast to every shard — or, with
    ``broadcast=False``, each shard's LOCAL buffer (only valid on the last
    stage; use this under autodiff and mask the loss instead, because the
    psum broadcast would multiply cotangents by ``n_stages`` when every
    shard evaluates the loss).

    ``feed_fn``: optional transform applied to each raw microbatch before it
    enters stage 0 (a non-uniform graph PREFIX — e.g. an embedding);
    ``act_shape``/``act_dtype`` then give the post-prefix activation
    shape/dtype (they default to the raw microbatch's).
    """
    idx = lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    params = jax.tree.map(lambda p: p[0], stage_params)
    mb_shape = tuple(act_shape) if act_shape is not None else x_micro.shape[1:]
    act_dtype = act_dtype if act_dtype is not None else x_micro.dtype

    def body(t, carry):
        state, outputs = carry
        feed = lax.dynamic_index_in_dim(
            x_micro, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
        )
        if feed_fn is not None:
            feed = feed_fn(feed)
        # non-0 shards compute feed too but never select it: its cotangent
        # is zero there, so prefix grads flow only from stage 0 (psum'd by
        # the caller)
        x_in = jnp.where(idx == 0, feed, state)
        y = stage_fn(params, x_in)
        oi = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        cur = lax.dynamic_index_in_dim(outputs, oi, 0, keepdims=False)
        # only the LAST stage materializes outputs: under per-shard autodiff
        # seeding, intermediate stages' buffers would otherwise feed their
        # (garbage) local losses and corrupt gradients
        keep = (t >= n_stages - 1) & (idx == n_stages - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(keep, y, cur), oi, 0
        )
        state = lax.ppermute(y, axis_name, perm)
        return state, outputs

    state0 = jnp.zeros(mb_shape, act_dtype)
    out0 = jnp.zeros((n_micro,) + mb_shape, act_dtype)
    _, outputs = lax.fori_loop(0, total, body, (state0, out0), unroll=False)
    if not broadcast:
        return outputs
    # only the last stage holds real outputs; broadcast them to every shard
    outputs = jnp.where(idx == n_stages - 1, outputs, jnp.zeros_like(outputs))
    return lax.psum(outputs, axis_name)


def pipeline_train_step(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    axis_name: str = "pp",
    dp_axis: str | None = None,
):
    """Build a shard_map'd (loss, grads) function for a pipelined model.

    ``stage_fn(params, x) -> y``; ``loss_fn(y, labels) -> scalar`` applied to
    final-stage outputs (mean over microbatches).  Global arrays in/out:
    ``stacked_params [n_stages, ...]``, ``x [n_micro, mb, ...]``, ``labels``
    aligned with ``x``.  Batch-dim data parallelism composes by also sharding
    the microbatch dim over ``dp_axis``.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = dict(mesh.shape)[axis_name]

    def local_step(stacked_params, x, labels):
        def loss_of(params_):
            outs = pipeline_apply(
                stage_fn, params_, x, axis_name, n_stages, broadcast=False
            )
            # LOCAL loss only — no collective inside the differentiated
            # function: every shard seeds its own scalar with 1, so a psum
            # here would transpose to an n_stages-fold cotangent.  Non-last
            # shards' losses are garbage but carry no param dependence
            # (their outputs buffer stays zero).
            return loss_fn(outs, labels)

        loss, grads = jax.value_and_grad(loss_of)(stacked_params)
        # replicate the real (last-stage) loss for reporting
        last = lax.axis_index(axis_name) == n_stages - 1
        loss = lax.psum(jnp.where(last, loss, 0.0), axis_name)
        if dp_axis is not None:
            loss = lax.pmean(loss, dp_axis)
            grads = jax.tree.map(lambda g: lax.pmean(g, dp_axis), grads)
        return loss, grads

    data_spec = P(None, dp_axis) if dp_axis else P()

    def step(stacked_params, x, labels):
        p_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(p_specs, data_spec, data_spec),
            out_specs=(P(), p_specs),
            check_vma=False,
        )(stacked_params, x, labels)

    return step


def graph_pipeline_train_step(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    axis_name: str = "pp",
    dp_axis: str | None = None,
    prefix_fn: Callable | None = None,
    suffix_fn: Callable | None = None,
    act_shape: tuple | None = None,
    act_dtype=None,
):
    """GPipe train step for a PARTITIONED GRAPH (compile-path pipeline).

    Generalizes :func:`pipeline_train_step` to the shape real graphs have
    after ``chain_partition``: K isomorphic core stages plus a non-uniform
    PREFIX (runs on stage 0, e.g. an embedding) and SUFFIX (runs on the last
    stage, e.g. head + softmax).  Prefix/suffix params are replicated over
    the pp axis; their local grads are zero off their home shard (the loss
    is masked to the last shard, and off-0 shards' prefix outputs are never
    selected), so a psum over ``axis_name`` recovers the true gradients.

    ``stage_fn(core_params, x) -> y`` (shape-preserving),
    ``prefix_fn(prefix_params, raw_mb) -> x`` (act-shaped),
    ``suffix_fn(suffix_params, y) -> logits``.
    Returns ``step(params3, x, labels) -> (loss, logits, grads3)`` over
    global arrays, with ``params3 = (core_stacked, prefix, suffix)`` and
    core leaves ``[n_stages, ...]`` sharded over ``axis_name``.
    """
    from jax.sharding import PartitionSpec as P

    n_stages = dict(mesh.shape)[axis_name]

    def local_step(core_p, pre_p, suf_p, x, labels):
        idx = lax.axis_index(axis_name)
        last = idx == n_stages - 1

        def loss_of(tr):
            core, pre, suf = tr
            feed = (lambda mb: prefix_fn(pre, mb)) if prefix_fn else None
            outs = pipeline_apply(
                stage_fn, core, x, axis_name, n_stages, broadcast=False,
                feed_fn=feed, act_shape=act_shape, act_dtype=act_dtype,
            )
            # suffix per MICROBATCH (vmap over the leading n_micro dim):
            # its ops treat dim 0 as the batch (e.g. a mean-pool over axis
            # 1), so applying it to the stacked [n_micro, mb, ...] buffer
            # directly would hit the wrong axes
            logits = (jax.vmap(lambda o: suffix_fn(suf, o))(outs)
                      if suffix_fn else outs)
            raw = loss_fn(logits, labels)
            # mask: off-last shards' outputs buffers are zeros, so their
            # "loss" would still pull garbage gradients through the suffix
            # params; zeroing the loss value kills those while the ppermute
            # transpose still routes real cotangents to earlier stages
            return jnp.where(last, raw, 0.0), logits

        (loss, logits), grads = jax.value_and_grad(loss_of, has_aux=True)(
            (core_p, pre_p, suf_p)
        )
        g_core, g_pre, g_suf = grads
        g_pre = jax.tree.map(lambda g: lax.psum(g, axis_name), g_pre)
        g_suf = jax.tree.map(lambda g: lax.psum(g, axis_name), g_suf)
        loss = lax.psum(loss, axis_name)  # only the last shard is nonzero
        logits = lax.psum(
            jnp.where(last, logits, jnp.zeros_like(logits)), axis_name
        )
        if dp_axis is not None:
            loss = lax.pmean(loss, dp_axis)
            g_core = jax.tree.map(lambda g: lax.pmean(g, dp_axis), g_core)
            g_pre = jax.tree.map(lambda g: lax.pmean(g, dp_axis), g_pre)
            g_suf = jax.tree.map(lambda g: lax.pmean(g, dp_axis), g_suf)
        return loss, logits, (g_core, g_pre, g_suf)

    data_spec = P(None, dp_axis) if dp_axis else P()

    def step(params3, x, labels):
        core_p, pre_p, suf_p = params3
        core_specs = jax.tree.map(lambda _: P(axis_name), core_p)
        rep = jax.tree.map(lambda _: P(), pre_p), \
            jax.tree.map(lambda _: P(), suf_p)
        return jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(core_specs, rep[0], rep[1], data_spec, data_spec),
            out_specs=(P(), data_spec, (core_specs, rep[0], rep[1])),
            check_vma=False,
        )(core_p, pre_p, suf_p, x, labels)

    return step
