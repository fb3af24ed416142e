"""North-star #1 artifact: Unity-searched strategy vs hand data-parallel.

Runs ``graph_optimize`` (MCMC over per-op mesh-axis assignments, scored by
the simulator with the **measured v5e cost cache** from
``artifacts/tpu_costs_v5e.json``) on the Transformer training config
(BASELINE config #2 analog) and reports:

* ``searched_vs_dp_sim``   — simulated v5e step-time ratio (hand-DP /
  searched; >1 means the searched strategy wins on the TPU cost model).
* ``searched_vs_dp_wallclock`` — measured step-time ratio on an 8-device
  virtual **CPU** mesh (real multi-chip TPU hardware is not available in
  this environment; the CPU mesh executes the same XLA collectives, so this
  is a semantics-faithful but not TPU-calibrated check — stated per
  VERDICT r1 item 4).  NOTE: virtual devices share one host's cores, so
  compute does NOT scale with the sharding degree there — a ratio near or
  below 1.0 on the virtual mesh is expected and does not contradict the
  simulated v5e win; it demonstrates the searched strategy compiles and
  runs multi-device, which is all the virtual mesh can attest.

The searched strategy is exported to
``artifacts/searched_transformer_strategy.json`` (the reference's
``--export`` strategy file analog).

Prints ONE JSON line; bench.py merges it into the driver metric line.
"""

import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main():
    from flexflow_tpu.utils.platform import force_cpu

    force_cpu(8)

    import sys

    import jax
    import numpy as np

    print(f"[bench_search] backend={jax.default_backend()} "
          f"devices={len(jax.devices())}", file=sys.stderr, flush=True)

    from flexflow_tpu import SGDOptimizer, make_mesh
    from flexflow_tpu.models.transformer import build_transformer_classifier
    from flexflow_tpu.parallel.mesh import data_parallel_strategy
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.measure import CostCache
    from flexflow_tpu.search.search import graph_optimize
    from flexflow_tpu.search.simulator import simulate
    from flexflow_tpu.search.strategy import save_strategy
    from flexflow_tpu.core.pcg import PCG

    mesh = make_mesh({"dp": 4, "tp": 2}, jax.devices()[:8])
    arch = dict(batch=8, seq=64, num_layers=2, hidden_dim=256,
                num_heads=8, ff_dim=1024, num_classes=16)
    model = build_transformer_classifier(mesh=mesh, **arch)
    graph = model.graph

    # hand data parallelism: batch over ALL devices (--only-data-parallel)
    dp = data_parallel_strategy(graph, mesh, axes=("dp", "tp"))

    v5e = MachineModel.for_mesh(mesh, spec_name="v5e").with_calibration(
        os.path.join(HERE, "artifacts", "tpu_calib_v5e.json")
    )
    costs = CostCache(os.path.join(HERE, "artifacts", "tpu_costs_v5e.json"))
    searched = graph_optimize(
        graph, mesh, budget=300, machine=v5e, measured=costs, seed=0, init=dp,
    )

    # joint Unity search: same walk, graph rewrites enabled (the wallclock
    # comparison below keeps the parallel-only strategy so the hand-built
    # and searched graphs stay identical)
    joint_graph, joint_strategy, _ = graph_optimize(
        graph, mesh, budget=300, machine=v5e, measured=costs, seed=0,
        init=dp, substitution=True,
        output_tids=[graph.nodes[-1].outputs[-1]],
    )
    rewrites_accepted = len(graph.nodes) - len(joint_graph.nodes)

    sim_dp = simulate(PCG(graph, mesh, dp).plan(), v5e, measured=costs).total
    sim_se = simulate(PCG(graph, mesh, searched).plan(), v5e,
                      measured=costs).total
    sim_joint = simulate(
        PCG(joint_graph, mesh, joint_strategy,
            output_tids=None).plan(), v5e, measured=costs).total

    strat_path = os.path.join(HERE, "artifacts",
                              "searched_transformer_strategy.json")
    os.makedirs(os.path.dirname(strat_path), exist_ok=True)
    save_strategy(strat_path, searched, mesh)

    # ---- error bars on the headline ratio (VERDICT r4 #4) --------------
    # One-at-a-time +/-30% perturbation of the constants the calibration
    # could plausibly be wrong about.  Two questions per point:
    #   (a) does the RATIO survive (searched still beats hand-DP in sim)?
    #   (b) does the ARGMAX survive (re-searching under the perturbed model
    #       finds a strategy no better than the nominal one, regret <= 5%)?
    import dataclasses

    def ratio_under(mm):
        d = simulate(PCG(graph, mesh, dp).plan(), mm, measured=costs).total
        s = simulate(PCG(graph, mesh, searched).plan(), mm,
                     measured=costs).total
        return d / s

    perturb_fields = ("mxu_efficiency", "overlap", "ici_bandwidth",
                      "train_step_factor")
    ratios, sens, stable = {}, {}, True
    for field in perturb_fields:
        base_val = getattr(v5e.spec, field)
        for f in (0.7, 1.3):
            mm_p = MachineModel(
                dataclasses.replace(v5e.spec, **{field: base_val * f}),
                v5e.dcn_axes,
            )
            key = f"{field}*{f}"
            ratios[key] = round(ratio_under(mm_p), 3)
            re_searched = graph_optimize(
                graph, mesh, budget=300, machine=mm_p, measured=costs,
                seed=0, init=dp,
            )
            t_nom = simulate(PCG(graph, mesh, searched).plan(), mm_p,
                             measured=costs).total
            t_re = simulate(PCG(graph, mesh, re_searched).plan(), mm_p,
                            measured=costs).total
            regret = t_nom / max(t_re, 1e-12)
            sens[key] = round(regret, 3)
            if regret > 1.05:
                stable = False
    ratio_range = [min(ratios.values()), max(ratios.values())]

    # which constants moved the r3->r4 1.868->3.511 jump: the same ratio
    # under the UNCALIBRATED spec-sheet constants (the r3-era basis)
    v5e_spec = MachineModel.for_mesh(mesh, spec_name="v5e")
    ratio_speccal = round(ratio_under(v5e_spec), 3)

    # wall-clock on the virtual CPU mesh
    def step_time(strategy, steps=6):
        import jax.numpy as jnp

        m = build_transformer_classifier(mesh=mesh, **arch)
        m.compile(optimizer=SGDOptimizer(lr=0.01), strategy=strategy)
        rng = np.random.RandomState(0)
        X = jnp.asarray(rng.randn(arch["batch"], arch["seq"],
                                  arch["hidden_dim"]).astype(np.float32))
        y = jnp.asarray(rng.randint(0, arch["num_classes"],
                                    size=arch["batch"]).astype(np.int32))
        tid = m.graph.input_tids[0]
        key = jax.random.PRNGKey(0)
        p, s = m.params, m.opt_state
        p, s, loss, _ = m._train_step(p, s, {tid: X}, y, key)
        np.asarray(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            p, s, loss, _ = m._train_step(p, s, {tid: X}, y, key)
        np.asarray(loss)
        return (time.perf_counter() - t0) / steps

    wc_dp = step_time(dp)
    wc_se = step_time(searched)

    print(json.dumps({
        "searched_vs_dp_sim": round(sim_dp / sim_se, 3),
        "searched_vs_dp_sim_range": [round(r, 3) for r in ratio_range],
        "searched_vs_dp_sim_speccal": ratio_speccal,
        "strategy_stable": stable,
        "perturbation_ratios": ratios,
        "perturbation_regret": sens,
        "perturbation_note": "one-at-a-time +/-30% on mxu_efficiency/overlap/"
                             "ici_bandwidth/train_step_factor; ratio = hand-DP"
                             "/searched under the perturbed model with the "
                             "NOMINAL searched strategy; regret = that "
                             "strategy's sim time / the re-searched optimum "
                             "under the same perturbed model (stable when "
                             "<=1.05 everywhere).  *_speccal re-scores both "
                             "strategies under UNCALIBRATED spec-sheet "
                             "constants — the r3-era basis — so the r3->r4 "
                             "headline jump is attributable to calibration "
                             "vs search",
        "joint_vs_dp_sim": round(sim_dp / sim_joint, 3),
        "rewrites_accepted": rewrites_accepted,
        "searched_vs_dp_wallclock": round(wc_dp / wc_se, 3),
        "dp_sim_ms": round(sim_dp * 1e3, 3),
        "searched_sim_ms": round(sim_se * 1e3, 3),
        "dp_cpu_step_ms": round(wc_dp * 1e3, 1),
        "searched_cpu_step_ms": round(wc_se * 1e3, 1),
        "wallclock_note": "8-device virtual CPU mesh (no multi-chip TPU "
                          "available); virtual devices share one host's "
                          "cores so compute does not scale with sharding -- "
                          "wallclock only attests multi-device execution; "
                          "sim uses measured v5e op costs",
        "sim_basis": "fusion-aware roofline + 24 measured v5e op probes + "
                     "measured machine constants (artifacts/tpu_calib_v5e"
                     ".json: mxu_eff, train factor, step overhead, VMEM "
                     "residency); single-chip validation: sim/meas within "
                     "2x on all 6 bench_cost_model variants, rank_corr "
                     "0.94 (BENCH cost_model_points); comm side is "
                     "analytic (ICI ring model), unverifiable on one chip",
        "strategy_path": "artifacts/searched_transformer_strategy.json",
    }))


if __name__ == "__main__":
    main()
