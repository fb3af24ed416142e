"""Plan -> executable JAX functions.

The TPU-native replacement for FlexFlow's execution layer: where the reference
walks the PCG issuing Legion index launches per op task (reference:
``FFModel::forward`` in ``src/runtime/model.cc``), here the whole PCG lowers
into ONE traced JAX function that XLA compiles and fuses.  Two modes:

* ``spmd``  — ops compute on global arrays; the chosen shardings are enforced
  with ``with_sharding_constraint`` and GSPMD emits the collectives.  This is
  the default training path (XLA sees the whole step; fusion + overlap).
* ``local`` — the function body runs under ``jax.shard_map``; ops compute on
  per-device shards and parallel ops are explicit ``lax`` collectives.  Used
  where manual communication placement matters (serve, ring attention) and for
  validating that the reified parallel ops are exactly the collectives we cost.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .op import OpContext
from .pcg import Plan, Step
from .sharding import TensorSharding


def _mesh_is_trivial(mesh: Mesh) -> bool:
    return mesh.size == 1


def node_scope(node) -> str:
    """The ``jax.named_scope`` a graph node is lowered under:
    ``<OpClass>.<node name>`` (``Linear.layers_3_fc1``).  It becomes part of
    every HLO operation's ``op_name`` — metadata only, so it costs nothing
    at run time — and a device trace then says which node, and which kind
    of operator, an XLA fusion or copy belongs to."""
    return f"{type(node.op).__name__}.{node.name}"


def build_forward(plan: Plan, mode: str = "spmd") -> Callable:
    """Return ``fn(params, inputs, rng=None, training=False) -> list[out]``.

    ``params``: ``{node_name: {param_name: array}}`` (global arrays).
    ``inputs``: ``{tid: array}`` for every graph input (global arrays).
    In either mode the returned function takes and returns GLOBAL arrays and is
    safe to ``jax.jit`` / differentiate.

    Stateful execution (the serve path — KV caches): pass ``state`` (a dict
    ``{node_name: pytree}``) and optionally ``extras`` (shared values visible
    to every op, e.g. the ``BatchConfig``).  Ops marked ``stateful = True``
    receive their state at ``ctx.extras["state"]`` and publish the updated
    state to ``ctx.extras["state_out"]`` (an op with a ``state_owner`` reads
    the state that node published in this same step, and publishes none);
    the call then returns
    ``(outputs, new_state)``.  This replaces the reference's mutable per-op
    ``OpMeta`` device state (e.g. ``IncMultiHeadSelfAttentionMeta``'s KV cache)
    with explicit functional threading so the whole step stays jittable and
    the caches can be donated.
    """

    mesh = plan.mesh
    trivial = _mesh_is_trivial(mesh)

    def body(params, inputs, rng, training, state=None, extras=None):
        env: Dict[int, jax.Array] = {}
        new_state = {} if state is not None else None
        for tid, vid in plan.input_vids.items():
            env[vid] = inputs[tid]
        for i, step in enumerate(plan.steps):
            ctx = OpContext(
                mode=mode if not trivial else "spmd",
                mesh=None if trivial else mesh,
                training=training,
                rng=None if rng is None else jax.random.fold_in(rng, i),
                config=step.config,
                extras={
                    "out_sharding": step.out_shardings[0]
                    if step.out_shardings
                    else None,
                    "out_shardings": step.out_shardings,
                    "in_shardings": step.in_shardings,
                    "in_specs": step.in_specs,
                    "out_specs": step.out_specs,
                    "node_name": step.node.name,
                },
            )
            if extras:
                ctx.extras.update(extras)
            if state is not None and getattr(step.node.op, "stateful", False):
                # an op that names a ``state_owner`` reads that node's state
                # as this step has already left it (the owner comes first in
                # the graph) and keeps none of its own
                owner = getattr(step.node.op, "state_owner", None)
                ctx.extras["state"] = (new_state[owner] if owner
                                       else state.get(step.node.name))
            args = [env[v] for v in step.in_vids]
            with jax.named_scope(node_scope(step.node)):
                outs = step.node.op.lower(ctx, args,
                                          params.get(step.node.name, {}))
            if new_state is not None and "state_out" in ctx.extras:
                new_state[step.node.name] = ctx.extras["state_out"]
            if mode == "spmd" and not trivial and not step.is_parallel:
                outs = [
                    _constrain_spmd(o, sh, mesh)
                    for o, sh in zip(outs, step.out_shardings)
                ]
            for v, o in zip(step.out_vids, outs):
                env[v] = o
        outputs = [env[v] for v in plan.output_vids]
        if state is not None:
            return outputs, new_state
        return outputs

    if mode == "spmd" or trivial:

        def fn(params, inputs, rng=None, training=False, state=None, extras=None):
            return body(params, inputs, rng, training, state, extras)

        return fn

    # ---- local mode: wrap in shard_map --------------------------------
    param_pspecs = {
        name: {
            p: sh.partition_spec() for p, sh in shs.items()
        }
        for name, shs in plan.param_shardings.items()
    }

    input_pspecs = {
        tid: plan.input_shardings[tid].partition_spec()
        for tid in plan.input_vids
    }
    out_pspecs = [sh.partition_spec() for sh in plan.output_shardings]

    def fn(params, inputs, rng=None, training=False, state=None, extras=None):
        if state is not None or extras is not None:
            raise NotImplementedError(
                "stateful execution (serve) is only supported in spmd mode; "
                "local/shard_map mode would need state pspecs threaded through"
            )
        # params not listed in the plan (unused nodes) are passed replicated
        pspecs = {
            name: param_pspecs.get(
                name, jax.tree.map(lambda _: PartitionSpec(), sub)
            )
            for name, sub in params.items()
        }

        def local_body(params_, inputs_):
            return body(params_, inputs_, rng, training)

        mapped = jax.shard_map(
            local_body,
            mesh=mesh,
            in_specs=(pspecs, input_pspecs),
            out_specs=out_pspecs,
            check_vma=False,
        )
        return mapped(params, inputs)

    return fn


def _constrain_spmd(x: jax.Array, sh: TensorSharding, mesh: Mesh) -> jax.Array:
    if sh.partial_axes:
        # partial-sum state is not expressible in a PartitionSpec; leave the
        # value unconstrained and let GSPMD carry it to the reduction point
        return x
    return lax.with_sharding_constraint(x, sh.named_sharding(mesh))


# ---------------------------------------------------------------------------
# parameter initialization & placement
# ---------------------------------------------------------------------------
def init_params(
    graph, plan: Plan, rng: jax.Array, dtype=None, only=None
) -> Dict[str, Dict[str, jax.Array]]:
    """Initialize all node params as global arrays placed per plan shardings.

    ``only``: optional set of node names to materialize.  The per-param rng
    key index still advances over EVERY node of ``graph`` in order, so a
    stage-split model (pipeline-parallel serving initializes each stage
    against its own sub-plan) draws bit-identical weights to the
    single-plan initialization with the same seed.
    """
    from ..training.initializer import default_initializer_for

    mesh = plan.mesh
    params: Dict[str, Dict[str, jax.Array]] = {}
    i = 0
    for node in graph.nodes:
        ps = node.op.params()
        if not ps:
            continue
        if only is not None and node.name not in only:
            i += len(ps)
            continue
        sub = {}
        for p in ps:
            key = jax.random.fold_in(rng, i)
            i += 1
            init = p.initializer or default_initializer_for(node.op, p)
            arr = init(key, p.spec.shape,
                       p.spec.dtype if p.pin_dtype else dtype or p.spec.dtype)
            sh = plan.param_shardings.get(node.name, {}).get(p.name)
            if sh is not None and not _mesh_is_trivial(mesh):
                arr = jax.device_put(arr, sh.named_sharding(mesh))
            sub[p.name] = arr
        params[node.name] = sub
    return params


def place_inputs(plan: Plan, inputs: Dict[int, jax.Array]) -> Dict[int, jax.Array]:
    """device_put graph inputs according to their planned shardings."""
    if _mesh_is_trivial(plan.mesh):
        return inputs
    out = {}
    for tid, x in inputs.items():
        sh = plan.input_shardings.get(tid)
        if sh is None:
            out[tid] = x
        else:
            out[tid] = jax.device_put(x, sh.named_sharding(plan.mesh))
    return out
