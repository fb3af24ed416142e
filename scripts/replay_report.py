"""Replay diff report: recorded traffic trace vs a replayed run.

Usage::

    # validate a trace artifact (counts + token hashes + provenance)
    python scripts/replay_report.py --check trace.jsonl

    # summarize the RECORDED run from the artifact alone
    python scripts/replay_report.py trace.jsonl

    # what-if: price tp/pp/micro-batch candidates against the recorded
    # arrival stream with NO device attached, and diff them under
    # obs.report.compare's discipline
    python scripts/replay_report.py trace.jsonl \
        --what-if tp1_pp2_m2 --what-if tp2_pp1 --fleet-size 2

Three modes over one versioned trace artifact
(:mod:`flexflow_tpu.obs.replay`, recorded via
``serve_with_arrivals(..., record_trace=TrafficTraceRecorder(path))``):

* ``--check`` — integrity validation: declared arrival/outcome counts,
  prompt/token hashes, and seed provenance (``TrafficTrace.validate``).
  Exit nonzero on any violation, same contract as
  ``trace_report.py --check``.
* default — ``under_load_summary`` of the RECORDED outcomes: the same
  reduction a live ``serve_with_arrivals`` run's records get, so a
  trace summarizes with identical accounting (goodput, per-class
  TTFT/TPOT p50/p95, outcome mix, per-replica breakdown).
* ``--what-if KEY`` (repeatable) — price candidate plans against the
  recorded stream: each ``KEY`` is a ``tp{T}_pp{P}[_m{M}]`` plan key
  priced by the calibrated component cost model
  (:func:`flexflow_tpu.search.serve_search.price_plan` on a synthetic
  2-cpu machine unless ``--calibrated`` points at real telemetry), then
  run through the harness's deterministic slot-level simulation.  The
  FIRST candidate is the baseline; every further candidate is diffed
  against it with :func:`flexflow_tpu.obs.report.compare`'s exact-counter
  / thresholded-latency discipline (``ReplayHarness.diff``).  Exit code
  reflects the LAST diff (nonzero = the later candidate regresses the
  baseline) so CI can gate on a planned downgrade.

Fidelity replay (re-driving a real deployment and asserting
bit-identity) needs a built engine, so it lives in the library
(``ReplayHarness.replay`` / ``verify``; ``tests/test_replay.py``) — not
behind this CLI.
"""

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

_PLAN_KEY_RE = re.compile(r"^tp(\d+)_pp(\d+)(?:_m(\d+))?$")


def price_candidate(key: str, ff, devices, machine=None):
    """Price one ``tp{T}_pp{P}[_m{M}]`` candidate with the calibrated
    component cost model (no device work — pure pricing)."""
    m = _PLAN_KEY_RE.match(key)
    if not m:
        raise SystemExit(
            f"--what-if {key!r}: expected tp{{T}}_pp{{P}}[_m{{M}}]")
    tp, pp, micro = int(m.group(1)), int(m.group(2)), int(m.group(3) or 1)
    from flexflow_tpu.search.serve_search import price_plan

    return price_plan(ff, tp, pp, micro, machine=machine, devices=devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Validate / summarize / what-if a traffic trace")
    ap.add_argument("trace", help="path to a TrafficTraceRecorder *.jsonl")
    ap.add_argument("--check", action="store_true",
                    help="validate artifact integrity instead of "
                         "summarizing; exit nonzero on violations")
    ap.add_argument("--what-if", action="append", default=[],
                    metavar="PLAN_KEY",
                    help="price a tp{T}_pp{P}[_m{M}] candidate against "
                         "the recorded stream (repeatable; first = "
                         "baseline, later candidates diffed against it)")
    ap.add_argument("--fleet-size", type=int, default=1,
                    help="replicate the what-if candidate N times "
                         "(default 1)")
    ap.add_argument("--default-threshold", type=float, default=0.10,
                    help="relative threshold for measured fields in the "
                         "what-if diff (default 0.10)")
    ap.add_argument("--indent", type=int, default=None)
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the report document to PATH")
    args = ap.parse_args(argv)

    from flexflow_tpu.obs.replay import ReplayHarness, TrafficTrace

    trace = TrafficTrace.load(args.trace)

    if args.check:
        errors = trace.validate()
        doc = {"ok": not errors, "path": args.trace, "errors": errors,
               "arrivals": len(trace.arrivals),
               "requests": len(trace.outcomes),
               "driver": trace.meta.get("driver")}
        print(json.dumps(doc, indent=args.indent))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
        return 1 if errors else 0

    harness = ReplayHarness(trace)
    doc = {
        "path": args.trace,
        "driver": trace.meta.get("driver"),
        "plan": trace.meta.get("plan"),
        "fault": trace.meta.get("fault"),
        "fleet": {k: v for k, v in (trace.meta.get("fleet") or {}).items()
                  if k != "plans"} or None,
        "arrivals": len(trace.arrivals),
        "recorded": harness.recorded_summary(),
    }

    rc = 0
    if args.what_if:
        # synthetic pricing scenario: tiny llama-shaped serve graph on 2
        # virtual-cpu devices — the same hermetic setup the bench's
        # calibration sections use, so what-if deltas are reproducible
        # anywhere (relative deltas are what the report prices; absolute
        # ms need real calibration).  Graph building is shape inference
        # only; nothing executes on a device.
        from flexflow_tpu.utils.platform import force_cpu

        force_cpu(2)
        import jax

        from flexflow_tpu import FFConfig, FFModel
        from flexflow_tpu.parallel.mesh import make_mesh
        from flexflow_tpu.serve import build_model
        from flexflow_tpu.serve.inference_manager import (
            register_serve_capacities,
        )
        from flexflow_tpu.serve.models.base import ServeModelConfig

        cfg = ServeModelConfig(
            model_type="llama", vocab_size=128, hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=256)
        devices = jax.devices()[:2]
        ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, devices[:1]))
        build_model(ff, cfg, max_tokens=16)
        register_serve_capacities(ff.graph, max_requests=8,
                                  max_seq_len=256)
        candidates = []
        for key in args.what_if:
            price = price_candidate(key, ff, devices)
            result = harness.what_if(price, fleet_size=args.fleet_size)
            result.pop("records", None)  # per-request detail stays off CLI
            candidates.append(result)
        doc["what_if"] = candidates
        diffs = []
        base = candidates[0]
        for cand in candidates[1:]:
            diff = harness.diff(base["summary"], cand["summary"],
                                default_threshold=args.default_threshold)
            diff["old_plan"] = base["candidate"]["plan_key"]
            diff["new_plan"] = cand["candidate"]["plan_key"]
            diffs.append(diff)
            rc = 0 if diff["ok"] else 1
        doc["diffs"] = diffs

    print(json.dumps(doc, indent=args.indent))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
