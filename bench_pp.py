"""pp_serve bench section: TP x PP serve pricing + virtual-mesh validation.

Runs in a SUBPROCESS with 8 virtual CPU devices (like bench_search.py — the
bench process itself holds the chip, and on a single chip a real pp2 cannot
be wall-clocked; the simulated table is the decision artifact and the device
fields stamp in on a multi-chip device run).  The parent starts it with
``JAX_PLATFORMS=cpu`` in its environment; the backend it initialised is
printed on stderr.

Prints ONE JSON line:
* ``pp_tpot_sim_ms`` — simulated decode TPOT at the llama2-7b 32-layer shape
  (int8 weights + int8 KV capacities registered) for pp in {1, 2} x
  micro-batch count in {1, 2, 4} on 2 v5e chips, from the calibrated
  TP x PP cost model (search/serve_search.py): weight re-streaming per
  micro-batch, KV prefix, inter-stage ICI hop, GPipe bubble.
* ``pp_plan`` — the plan ``search_serve_plan`` picks for 2 chips under the
  16 GB cap, with per-stage ``plan_memory_bytes``.
* ``pp_virtual_ok`` — a tiny-shape pp2 x tp2 PipelinedInferenceManager on
  the virtual mesh generates bit-identically to the single-stage program
  (the functional gate, mirroring tests/test_pp_serve.py).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from flexflow_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(8)

import jax  # noqa: E402
import numpy as np  # noqa: E402

print(f"[bench_pp] backend={jax.default_backend()} "
      f"devices={len(jax.devices())}", file=sys.stderr, flush=True)


def main():
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.serve_search import (
        pp_serve_cost,
        search_serve_plan,
        _boundary_bytes,
    )
    from flexflow_tpu.serve import (
        GenerationConfig,
        InferenceManager,
        PipelinedInferenceManager,
        RequestManager,
        ServeModelConfig,
        annotate_int8,
        build_model,
        serve_stage_split,
        build_stage_plans,
    )
    from flexflow_tpu.serve.inference_manager import (
        register_serve_capacities,
        tensor_parallel_strategy,
    )

    doc = {}
    here = os.path.dirname(os.path.abspath(__file__))
    calib = os.path.join(here, "artifacts", "tpu_calib_v5e.json")

    # ---- simulated TP x PP pricing at the full-depth 7B shape ----------
    full = ServeModelConfig(
        model_type="llama", vocab_size=32000, hidden_size=4096,
        intermediate_size=11008, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=32, dtype="bfloat16")
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, full, max_tokens=8)  # decode-shaped batch (bs=8)
    register_serve_capacities(ff.graph, max_requests=8, max_seq_len=2048,
                              kv_dtype="int8")
    annotate_int8(ff.graph)

    mesh1 = make_mesh({"tp": 1}, jax.devices()[:1])
    mm = MachineModel.for_mesh(mesh1, spec_name="v5e").with_calibration(calib)

    table = {}
    for pp in (1, 2):
        split = serve_stage_split(ff.graph, pp)
        plans = build_stage_plans(ff.graph, split, {}, [mesh1] * pp)
        bbytes = _boundary_bytes(ff.graph, split)
        row = {}
        for m in (1, 2, 4):
            c = pp_serve_cost(plans, mm, n_micro=m, boundary_bytes=bbytes)
            row[f"m{m}"] = {
                "tpot_ms": round(c["tpot_s"] * 1e3, 3),
                "bubble_frac": round(c["bubble_frac"], 3),
                "transfer_ms": round(c["transfer_s"] * 1e3, 4),
            }
        table[f"pp{pp}"] = row
    doc["pp_tpot_sim_ms"] = table
    doc["pp_sim_note"] = (
        "calibrated v5e steady-state cost model, llama2-7b 32L int8 "
        "weights+KV, bs=8 ctx=2048: per-request TPOT = max(m, pp) * tick, "
        "tick = stage_weights/bw + (flops+KV+tp_comm)/m + overhead + ICI "
        "hop — weights re-stream per micro-batch, so m = pp is the decode "
        "optimum (pipeline full, no re-stream excess) and m > pp pays; "
        "pp1 rows show micro-batching without stages is pure overhead. "
        "Device TPOT fields stamp in on the next multichip device run")

    # the search picks the whole (tp, pp, m) jointly for 2 chips: with 32
    # shardable kv-heads TP wins on latency (weights split per chip AND
    # never re-stream), pp1 expected here
    best = search_serve_plan(ff, n_chips=2, machine=mm,
                             n_micro=(1, 2, 4, 8))
    doc["pp_plan"] = {k: best[k] for k in
                      ("tp", "pp", "n_micro", "tpot_ms", "bubble_frac",
                       "transfer_ms", "per_stage_gb")}

    # MQA variant (kv_heads=1): head-sharded TP is inadmissible, so PP is
    # the only axis that divides the model across chips — the capacity
    # scenario PP serving exists for
    mqa = ServeModelConfig(
        model_type="llama", vocab_size=32000, hidden_size=4096,
        intermediate_size=11008, num_hidden_layers=32,
        num_attention_heads=32, num_key_value_heads=1, dtype="bfloat16")
    ffm = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ffm, mqa, max_tokens=8)
    register_serve_capacities(ffm.graph, max_requests=8, max_seq_len=2048,
                              kv_dtype="int8")
    annotate_int8(ffm.graph)
    best_mqa = search_serve_plan(ffm, n_chips=2, machine=mm,
                                 n_micro=(1, 2, 4))
    doc["pp_plan_mqa"] = {k: best_mqa[k] for k in
                          ("tp", "pp", "n_micro", "tpot_ms", "bubble_frac",
                           "transfer_ms", "per_stage_gb")}

    # ---- functional gate: pp2 x tp2 on the virtual mesh ----------------
    from flexflow_tpu.obs import Telemetry

    tiny = ServeModelConfig(
        model_type="llama", vocab_size=96, hidden_size=32,
        intermediate_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2)
    prompts = [[3, 5, 7, 9], [11, 2]]

    def serve(im, telemetry=None):
        im.init_operators_inference(rng=jax.random.PRNGKey(0))
        return RequestManager(
            im, GenerationConfig(max_new_tokens=4),
            telemetry=telemetry).generate(prompts)

    f1 = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(f1, tiny, max_tokens=16)
    want = serve(InferenceManager(
        f1, max_requests=2, max_tokens_per_batch=16, max_seq_len=64,
        use_pallas=True))
    f2 = FFModel(FFConfig(),
                 mesh=make_mesh({"pp": 2, "tp": 2}, jax.devices()[:4]))
    build_model(f2, tiny, max_tokens=16)
    pim = PipelinedInferenceManager(
        f2, max_requests=2, max_tokens_per_batch=16, max_seq_len=64,
        n_micro=2, use_pallas=True)
    # telemetry on the pp run: per-stage Perfetto trace + predicted-vs-
    # measured TPOT (virtual-CPU measured vs the cpu-spec cost model —
    # structure check here; device runs calibrate the v5e spec)
    tel = Telemetry()
    mm_cpu = MachineModel.for_mesh(pim.stage_meshes[0], spec_name="cpu")
    cost = pp_serve_cost(pim.stage_plans, mm_cpu, n_micro=pim.n_micro)
    tel.record_plan_prediction("tp2_pp2_m2", tpot_ms=cost["tpot_s"] * 1e3,
                               bubble_frac=cost["bubble_frac"])
    got = serve(pim, telemetry=tel)
    doc["pp_virtual_ok"] = bool(got == want)
    if not doc["pp_virtual_ok"]:
        doc["pp_virtual_diff"] = {"want": want, "got": got}
    tpot_snap = tel.metrics.snapshot().get("tpot_s", {})
    if tpot_snap.get("p50") is not None:
        tel.record_plan_measured("tp2_pp2_m2",
                                 tpot_ms=tpot_snap["p50"] * 1e3)
    doc["pp_calibration"] = tel.calibration.report()["plans"]
    doc["pp_calibration_note"] = (
        "virtual-mesh structure check: measured is CPU wall time incl. "
        "compile vs the cpu-spec analytic model — the error magnitude is "
        "meaningless off-device; the device pp run stamps the real pair")
    here2 = os.path.join(here, "artifacts", "telemetry")
    paths = tel.export(here2, prefix="pp_serve")
    stage_tracks = sorted({
        ev.get("args", {}).get("name") for ev in tel.trace.trace_events()
        if ev.get("ph") == "M"
        and str(ev.get("args", {}).get("name", "")).startswith("stage")})
    doc["pp_trace"] = {"jsonl": paths["jsonl"],
                       "events": tel.trace.emitted,
                       "stage_tracks": stage_tracks}

    print(json.dumps(doc))


if __name__ == "__main__":
    main()
