"""Incremental-decoding serving demo (reference: ``inference/incr_decoding``).

Serves a LLaMA-architecture model through the full stack — serve graph builder
→ InferenceManager (TP-sharded, jitted step, donated KV caches) →
RequestManager (continuous batching).  Without a checkpoint it runs a small
randomly-initialized model; pass ``--hf <name-or-path>`` (once weight import
lands) to serve real weights.

    python examples/serve_llama.py --cpu 8 --tp 2
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", type=int, default=0,
                    help="force N virtual CPU devices (0 = real TPU)")
    ap.add_argument("--tp", type=int, default=1, help="tensor-parallel degree")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (stage-split serving; "
                         "composes with --tp, needs pp*tp devices)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="decode micro-batches per macro-step (0 = pp)")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--max-requests", type=int, default=4)
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--kv-dtype", default=None, choices=["int8"],
                    help="KV-cache storage dtype (int8: quantize-on-write "
                         "caches with dequant fused into the Pallas "
                         "attention kernels)")
    ap.add_argument("--kv-page-size", type=int, default=0,
                    help="enable the paged KV cache with copy-on-write "
                         "prefix sharing (serve/kv_paged.py): pages of "
                         "this many tokens, block-table indirection in "
                         "the kernels; must divide max-seq and its "
                         "128-lane pad; 0 = slot-contiguous")
    ap.add_argument("--profile", action="store_true",
                    help="capture an XProf (jax.profiler) trace of the "
                         "serve run in a fresh timestamped dir under "
                         "artifacts/profile/, with the telemetry JSON "
                         "exported alongside it")
    ap.add_argument("--calibration-store", default="",
                    help="commit this run's predicted-vs-measured ledger "
                         "into a persisted CalibrationStore JSON (pass a "
                         "path, or 'default' for the repo artifact "
                         "artifacts/calibration_store.json) — later "
                         "search_serve_plan calls auto-apply the scales")
    ap.add_argument("--telemetry-out", default="",
                    help="export the serving telemetry (Perfetto trace "
                         "JSON + JSONL) to this directory (default: the "
                         "--profile run dir when profiling, else no "
                         "export; the summary always prints)")
    ap.add_argument("--record-trace", default="", metavar="PATH",
                    help="serve the demo prompts as an arrival stream "
                         "and capture it as a versioned traffic-trace "
                         "JSONL (obs/replay.py): gen/sampling seeds, "
                         "plan key, per-arrival prompts + hashes, "
                         "per-request outcomes — replayable with "
                         "--replay-trace")
    ap.add_argument("--replay-trace", default="", metavar="PATH",
                    help="re-drive a recorded traffic trace against "
                         "this deployment instead of the demo prompts: "
                         "pins the recorded gen config/seed, replays "
                         "the arrival stream, and verifies per-request "
                         "token streams + outcomes are bit-identical "
                         "to the recording (same plan + identical "
                         "weights; a different plan reports the "
                         "mismatches instead)")
    args = ap.parse_args()
    if args.record_trace and args.replay_trace:
        ap.error("--record-trace and --replay-trace are exclusive")

    if args.cpu:
        from flexflow_tpu.utils.platform import force_cpu

        force_cpu(args.cpu)
    else:  # persistent compile cache (not for virtual-device collectives)
        from flexflow_tpu.utils.platform import enable_compile_cache

        enable_compile_cache()
    import jax
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.mesh import make_mesh
    from flexflow_tpu.serve import (
        GenerationConfig,
        InferenceManager,
        RequestManager,
        ServeModelConfig,
        build_model,
    )

    cfg = ServeModelConfig(
        model_type="llama",
        vocab_size=args.vocab,
        hidden_size=args.hidden,
        intermediate_size=args.hidden * 3,
        num_hidden_layers=args.layers,
        num_attention_heads=args.heads,
        num_key_value_heads=args.kv_heads,
    )
    if args.pp > 1:
        from flexflow_tpu.serve import PipelinedInferenceManager

        mesh = make_mesh({"pp": args.pp, "tp": args.tp},
                         jax.devices()[: args.pp * args.tp])
        ff = FFModel(FFConfig(), mesh=mesh)
        logits = build_model(ff, cfg, args.max_tokens)
        im = PipelinedInferenceManager(
            ff,
            max_requests=args.max_requests,
            max_tokens_per_batch=args.max_tokens,
            max_seq_len=args.max_seq,
            n_micro=args.microbatches or None,
            outputs=logits,
            kv_dtype=args.kv_dtype,
            kv_page_size=args.kv_page_size or None,
        )
        gb = [round(b / 1e9, 3) for b in im.stage_memory_bytes()]
        print(f"pp{args.pp} x tp{args.tp}: per-stage plan GB {gb}")
    else:
        mesh = make_mesh({"tp": args.tp}, jax.devices()[: args.tp])
        ff = FFModel(FFConfig(), mesh=mesh)
        logits = build_model(ff, cfg, args.max_tokens)
        im = InferenceManager(
            ff,
            max_requests=args.max_requests,
            max_tokens_per_batch=args.max_tokens,
            max_seq_len=args.max_seq,
            outputs=logits,
            kv_dtype=args.kv_dtype,
            kv_page_size=args.kv_page_size or None,
        )
    im.init_operators_inference(rng=jax.random.PRNGKey(0))
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.utils.profiling import maybe_profile, run_trace_dir

    tel = Telemetry()
    rm = RequestManager(
        im, GenerationConfig(max_new_tokens=args.max_new_tokens),
        telemetry=tel)
    if args.pp > 1:
        # predicted-vs-measured: price THIS stage split with the serve cost
        # model, then let the run's measured TPOT land next to it
        from flexflow_tpu.search.machine_model import MachineModel
        from flexflow_tpu.search.serve_search import pp_serve_cost

        mm = MachineModel.for_mesh(im.stage_meshes[0])
        cost = pp_serve_cost(im.stage_plans, mm, n_micro=im.n_micro)
        plan_key = f"tp{args.tp}_pp{args.pp}_m{im.n_micro}"
        tel.record_plan_prediction(plan_key, tpot_ms=cost["tpot_s"] * 1e3,
                                   bubble_frac=cost["bubble_frac"])

    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(1, args.vocab, size=n).tolist() for n in (5, 11, 3, 17)
    ]
    out_dir = args.telemetry_out or None
    t0 = time.perf_counter()
    fidelity = None
    with maybe_profile(args.profile, trace_dir=out_dir) as prof_dir:
        if args.replay_trace:
            from flexflow_tpu.obs.replay import ReplayHarness, TrafficTrace

            trace = TrafficTrace.load(args.replay_trace)
            harness = ReplayHarness(trace, telemetry=tel)
            records = harness.replay(rm)
            fidelity = harness.verify(records)
            prompts = [a["prompt"] for a in trace.arrivals]
            outs = [records[r]["tokens"] for r in sorted(records)]
        elif args.record_trace:
            from flexflow_tpu.obs.replay import TrafficTraceRecorder

            recorder = TrafficTraceRecorder(path=args.record_trace,
                                            telemetry=tel)
            arrivals = [(0.002 * i, p, args.max_new_tokens)
                        for i, p in enumerate(prompts)]
            records = rm.serve_with_arrivals(arrivals,
                                             record_trace=recorder)
            outs = [records[r]["tokens"] for r in sorted(records)]
        else:
            outs = rm.generate(prompts)
    dt = time.perf_counter() - t0
    for p, o in zip(prompts, outs):
        print(f"prompt[{len(p)} toks] -> {o}")
    total = rm.tokens_decoded
    print(
        f"served {len(prompts)} requests, {total} tokens in {rm.steps} steps, "
        f"{dt:.2f}s ({total / dt:.1f} tok/s incl. compile)"
    )
    if args.record_trace:
        print(f"traffic trace recorded: {args.record_trace} "
              f"(replay with --replay-trace)")
    if fidelity is not None:
        verdict = ("BIT-IDENTICAL" if fidelity["bit_identical"]
                   else f"{len(fidelity['mismatches'])} MISMATCHES")
        print(f"replay fidelity: {verdict} over "
              f"{fidelity['requests']} recorded requests")

    snap = tel.metrics.snapshot()
    tpot = snap.get("tpot_s", {})
    ttft = snap.get("ttft_s", {})
    if args.pp > 1 and tpot.get("p50") is not None:
        tel.record_plan_measured(plan_key, tpot_ms=tpot["p50"] * 1e3)
    parts = [f"trace_events={tel.trace.emitted}"]
    if ttft.get("p50") is not None:
        parts.append(f"ttft_p50={1e3 * ttft['p50']:.1f}ms")
    if tpot.get("p50") is not None:
        parts.append(f"tpot_p50={1e3 * tpot['p50']:.2f}ms")
    print("telemetry:", " ".join(parts))
    if args.pp > 1 and tel.calibration:
        print("predicted-vs-measured:",
              tel.calibration.report()["plans"].get(plan_key))
    if args.calibration_store and tel.calibration:
        # the continuous-calibration write path: this measured run's
        # suggested scales EWMA-blend into the persisted store the next
        # search_serve_plan(calibration="auto") consults
        from flexflow_tpu.obs import DEFAULT_STORE_PATH, CalibrationStore

        spath = (DEFAULT_STORE_PATH
                 if args.calibration_store == "default"
                 else args.calibration_store)
        store = CalibrationStore.load(spath)
        view = tel.calibration.commit(store)
        store.save()
        tel.store = store
        print(f"calibration store updated: {spath} "
              f"({ {k: v['scale'] for k, v in view.items()} })")
    out_dir = out_dir or prof_dir
    if out_dir:
        paths = tel.export(out_dir, prefix="serve")
        print(f"telemetry exported: {paths['trace_json']} "
              f"(+ {paths['jsonl']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
