"""Unity-search stack tests: simulator sanity, MCMC improvement, strategy IO,
and numerical correctness of searched strategies."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, make_mesh
from flexflow_tpu.core.interpreter import build_forward, init_params
from flexflow_tpu.core.pcg import PCG
from flexflow_tpu.models.transformer import build_transformer_classifier
from flexflow_tpu.parallel.mesh import data_parallel_strategy
from flexflow_tpu.search.machine_model import MachineModel, TPU_SPECS
from flexflow_tpu.search.search import enumerate_op_configs, graph_optimize
from flexflow_tpu.search.simulator import simulate
from flexflow_tpu.search.strategy import load_strategy, save_strategy


@pytest.fixture(scope="module")
def tf_model(devices8):
    mesh = make_mesh({"dp": 4, "tp": 2}, devices8)
    model = build_transformer_classifier(mesh=mesh, batch=8, seq=32,
                                         num_layers=2, hidden_dim=128,
                                         num_heads=8, ff_dim=512)
    return model, mesh


def test_enumerate_configs_linear(tf_model):
    model, mesh = tf_model
    node = next(n for n in model.graph.nodes if n.name == "enc0_ff1")
    in_specs = [model.graph.spec(t) for t in node.inputs]
    cfgs = enumerate_op_configs(node, in_specs, mesh)
    # includes {}, pure sample, channel_out on tp, hybrid...
    assert {} in cfgs
    assert {"sample": ("dp",)} in cfgs
    assert {"sample": ("dp",), "channel_out": ("tp",)} in cfgs
    # fused relu forbids channel_in
    assert not any("channel_in" in c for c in cfgs)


def test_simulator_prefers_sharded(tf_model):
    model, mesh = tf_model
    dp = data_parallel_strategy(model.graph, mesh)
    c_repl = simulate(PCG(model.graph, mesh, {}).plan()).total
    c_dp = simulate(PCG(model.graph, mesh, dp).plan()).total
    assert c_dp < c_repl  # sharding the batch must beat full replication


def test_search_beats_or_matches_dp(tf_model):
    model, mesh = tf_model
    dp = data_parallel_strategy(model.graph, mesh)
    c_dp = simulate(PCG(model.graph, mesh, dp).plan()).total
    best = graph_optimize(model.graph, mesh, budget=150, seed=1)
    c_best = simulate(PCG(model.graph, mesh, best).plan()).total
    assert c_best <= c_dp * 1.0001


def test_searched_strategy_correct(tf_model):
    """The searched strategy must execute and match single-device output."""
    model, mesh = tf_model
    best = graph_optimize(model.graph, mesh, budget=60, seed=2)
    plan = PCG(model.graph, mesh, best).plan()
    fwd = build_forward(plan, mode="spmd")
    params = init_params(model.graph, plan, jax.random.PRNGKey(0))

    mesh1 = make_mesh({"dp": 1}, [jax.devices("cpu")[0]])
    model1 = build_transformer_classifier(mesh=mesh1, batch=8, seq=32,
                                          num_layers=2, hidden_dim=128,
                                          num_heads=8, ff_dim=512)
    plan1 = PCG(model1.graph, mesh1, {}).plan()
    fwd1 = build_forward(plan1, mode="spmd")

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 32, 128).astype(np.float32))
    tid = model.graph.input_tids[0]
    out = np.asarray(fwd(params, {tid: x})[0])
    ref = np.asarray(fwd1(params, {model1.graph.input_tids[0]: x})[0])
    np.testing.assert_allclose(out, ref, rtol=5e-4, atol=1e-5)


def test_strategy_roundtrip(tmp_path, tf_model):
    model, mesh = tf_model
    strategy = {
        "enc0_ff1": {"sample": ("dp",), "channel_out": ("tp",)},
        "head": {"sample": ("dp", "tp")},
    }
    path = str(tmp_path / "strategy.json")
    save_strategy(path, strategy, mesh)
    loaded = load_strategy(path)
    assert loaded == strategy


def test_machine_model_collective_time(devices8):
    mesh = make_mesh({"dp": 8}, devices8)
    mm = MachineModel(TPU_SPECS["v5e"])
    t_small = mm.collective_time(1e6, ("dp",), mesh)
    t_big = mm.collective_time(1e8, ("dp",), mesh)
    assert t_big > t_small > 0
    assert mm.collective_time(0, ("dp",), mesh) == 0.0


def test_grad_allreduce_cost_counted(tf_model):
    model, mesh = tf_model
    dp = data_parallel_strategy(model.graph, mesh)
    cost = simulate(PCG(model.graph, mesh, dp).plan(), training=True)
    assert cost.grad_comm > 0  # replicated params + sharded batch => psum cost
    cost_inf = simulate(PCG(model.graph, mesh, dp).plan(), training=False)
    assert cost_inf.grad_comm == 0


def test_search_with_measured_v5e_costs_beats_dp(tf_model):
    """North-star #1 shape: with the committed v5e measured-cost artifact and
    the v5e machine model, the searched strategy beats hand-DP-over-all-axes
    in simulated step time."""
    import os

    from flexflow_tpu.search.measure import CostCache

    model, mesh = tf_model
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    costs = CostCache(os.path.join(root, "artifacts", "tpu_costs_v5e.json"))
    assert costs.data, "calibration artifact missing"
    v5e = MachineModel.for_mesh(mesh, spec_name="v5e")
    dp = data_parallel_strategy(model.graph, mesh, axes=("dp", "tp"))
    best = graph_optimize(model.graph, mesh, budget=200, machine=v5e,
                          measured=costs, seed=0, init=dp)
    c_dp = simulate(PCG(model.graph, mesh, dp).plan(), v5e,
                    measured=costs).total
    c_best = simulate(PCG(model.graph, mesh, best).plan(), v5e,
                      measured=costs).total
    assert c_best < c_dp
