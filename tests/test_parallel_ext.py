"""Sequence parallelism (ring attention) + pipeline parallelism tests.

Both are capabilities BEYOND the reference (SURVEY.md §2.3 marks SP absent
and PP weak there); hermetic on the 8-virtual-device CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.parallel.pipeline import pipeline_apply, pipeline_train_step
from flexflow_tpu.parallel.ring_attention import ring_attention
from flexflow_tpu.utils.platform import collective_safe_compiler_options


def full_attention(q, k, v, causal, scale):
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        sc = jnp.where(mask, sc, -1e30)
    w = jax.nn.softmax(sc, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(w.dtype)).astype(q.dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    rng = np.random.default_rng(0)
    b, t, h, d, n = 2, 32, 4, 8, 4
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    mesh = make_mesh({"sp": n}, jax.devices()[:n])

    ringed = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", n, causal, scale),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        ),
        # the collective-rendezvous deadlock class (see conftest): tests
        # that jit collective programs DIRECTLY scope the sequential CPU
        # schedule here, like the library jit sites do
        compiler_options=collective_safe_compiler_options(mesh),
    )(q, k, v)
    want = full_attention(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_sequence_parallel_attention_op():
    # MultiHeadAttention op with a "sequence" config in local (shard_map)
    # mode must equal the replicated spmd forward
    b, t, e, h = 2, 32, 16, 4

    def build(mesh_axes, strategy, mode):
        n = int(np.prod(list(mesh_axes.values())))
        mesh = make_mesh(mesh_axes, jax.devices()[:n])
        ff = FFModel(FFConfig(), mesh=mesh)
        x = ff.create_tensor((b, t, e))
        y = ff.multihead_attention(x, x, x, e, h, causal=True, use_bias=False,
                                   name="mha")
        ff.compile(strategy=strategy, mode=mode, outputs=[y],
                   loss_type="mean_squared_error")
        return ff

    rng = np.random.default_rng(1)
    x = rng.normal(size=(b, t, e)).astype(np.float32)

    ff_ref = build({"sp": 1}, {}, "spmd")
    ff_sp = build({"sp": 4}, {"mha": {"sequence": ("sp",)}}, "local")
    # same seed => same params
    for node, sub in ff_ref.params.items():
        for name, arr in sub.items():
            np.testing.assert_allclose(
                np.asarray(arr), np.asarray(ff_sp.params[node][name])
            )
    want = np.asarray(ff_ref.forward(x))
    got = np.asarray(ff_sp.forward(x))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def stage_mlp(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def test_pipeline_apply_matches_sequential():
    rng = np.random.default_rng(2)
    n_stages, n_micro, mb, dim = 4, 8, 4, 16
    params = {
        "w": jnp.asarray(rng.normal(size=(n_stages, dim, dim)) * 0.3,
                         jnp.float32),
        "b": jnp.asarray(rng.normal(size=(n_stages, dim)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(n_micro, mb, dim)), jnp.float32)

    mesh = make_mesh({"pp": n_stages}, jax.devices()[:n_stages])
    got = jax.jit(
        jax.shard_map(
            lambda p, x: pipeline_apply(stage_mlp, p, x, "pp", n_stages),
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pp"), params), P()),
            out_specs=P(),
            check_vma=False,
        ),
        compiler_options=collective_safe_compiler_options(mesh),
    )(params, x)

    want = x
    for s in range(n_stages):
        want = stage_mlp({"w": params["w"][s], "b": params["b"][s]}, want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_train_step_grads_match_sequential():
    rng = np.random.default_rng(3)
    n_stages, n_micro, mb, dim = 2, 4, 8, 8
    params = {
        "w": jnp.asarray(rng.normal(size=(n_stages, dim, dim)) * 0.3,
                         jnp.float32),
        "b": jnp.zeros((n_stages, dim), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(n_micro, mb, dim)), jnp.float32)
    labels = jnp.asarray(rng.normal(size=(n_micro, mb, dim)), jnp.float32)

    def loss_fn(y, lab):
        return jnp.mean((y - lab) ** 2)

    # pp=2 x dp=4 over 8 devices
    mesh = make_mesh({"pp": n_stages, "dp": 4}, jax.devices()[:8])
    step = pipeline_train_step(stage_mlp, loss_fn, mesh, "pp", dp_axis="dp")
    loss, grads = jax.jit(
        step, compiler_options=collective_safe_compiler_options(mesh),
    )(params, x, labels)

    def ref_loss(p):
        y = x
        for s in range(n_stages):
            y = stage_mlp({"w": p["w"][s], "b": p["b"][s]}, y)
        return loss_fn(y, labels)

    want_loss, want_grads = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for k in grads:
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(want_grads[k]),
                                   atol=1e-5, rtol=1e-4)
