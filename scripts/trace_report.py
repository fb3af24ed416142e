"""Summarize (or validate) a serving-telemetry JSONL export.

Usage::

    python scripts/trace_report.py artifacts/telemetry/serve.jsonl
    python scripts/trace_report.py --check artifacts/telemetry/serve.jsonl

Default mode prints one JSON document: request counts, p50/p95 TTFT /
TPOT / queue-wait (derived from the request-lifecycle events), the
terminal outcome mix and resilience counters (rejected / cancelled /
timeout / preempted / failed, dispatch retries + faults, recompute
tokens), per-track span totals (pipeline stage interleave), the pp bubble
fraction, the per-plan predicted-vs-measured error table from the
calibration ledger — and the plan feedback loop's view: the live
workload-drift score + per-dimension window means, ``drift_detected`` /
``replan_recommended`` events, and the CalibrationStore scales that were
auto-applied to the search's predictions.

The ``time_budget`` section is the step-level cost attribution view
(obs/profiler.py, present when a ``StepProfiler`` was bound to the
exporting handle): per-phase host time totals/fractions (host_admit /
host_prepare / dispatch / per-stage + hop / readback), the deterministic
work counters
(flops, KV bytes touched, dispatches, jit recompiles, host syncs, pages
mapped/COW'd — the fields ``obs.report.compare`` holds exactly), and
the per-plan per-COMPONENT predicted-vs-executed error table
(``attention_ms`` ... ``host_overhead_ms``) whose ``suggested_scale``
entries feed component-level ``MachineModel``/search calibration.

The ``memory`` section is the byte-side view (obs/memory.py): live KV
watermarks (``hwm_frac`` vs capacity), occupancy p50/p95, the
``kv_*`` gauge values, per-request ``request_kv_bytes`` attribution, the
per-component predicted-vs-allocated HBM error table (the memory
ledger's analog of ``prediction_error`` — its ``suggested_scale`` feeds
``MachineModel`` memory-constant calibration), and any
``memory_pressure`` OOM-risk breach events the plan-health monitor
emitted.

The ``fleet`` section is the multi-replica view (serve/fleet.py):
per-replica health-state transitions (``replica_up`` / ``degraded`` /
``quarantined`` / ``dead``), ``request_failed_over`` events (a request
moving off a failed replica onto a survivor under its original rid),
and the exact ``FLEET_COUNTERS`` registry view (``failovers_total``,
``replica_deaths``, the ``fleet_replicas_*`` gauges).

The ``slo`` section is the serving-lanes view (serve/slo.py):
``brownout_level_changed`` ladder transitions (level, from_level, the
pressure reason), explicit ``lane_shed`` events per degradable-class
request the ladder rejected, the exact ``SLO_COUNTERS`` registry view
(deferral/shed/degrade totals, escalation/de-escalation counts, the
``brownout_level`` gauge), and the per-class ``lane_pending_depth_*``
gauges.  Per-class TTFT/TPOT attainment lives in the
``under_load_summary`` ``per_class`` breakdown.

The ``replay`` section is the time-travel view (obs/replay.py):
``trace_recorded`` artifact saves, ``replay_started`` /
``replay_completed`` harness runs (mode = fidelity|what_if, the
bit-identity verdict), per-request ``replay_mismatch`` fidelity
violations, and the exact ``REPLAY_COUNTERS`` registry view
(``traces_recorded`` / ``replays_run`` / ``replay_mismatches`` — the
last joins ``obs.report.compare``'s exact class at threshold zero).  The
recorded-vs-replayed diff itself is ``scripts/replay_report.py``.

A trace whose ring buffer dropped events is TRUNCATED — the summary is
computed from what survived — so ``dropped > 0`` prints an explicit
warning to stderr (satellite of ISSUE 6: a truncated trace must not
masquerade as a complete one), and the count is ALSO surfaced as the
``telemetry_events_dropped`` exact-class counter so a run that starts
losing events fails ``obs.report.compare`` instead of just warning
here.

``--check`` validates the JSONL against the expected event schema
(:func:`flexflow_tpu.obs.report.validate_jsonl` — line kinds, per-phase
trace-event fields, and the typed request/dispatch/plan vocabulary from
``telemetry.EVENT_SCHEMA``) and exits nonzero on unknown/missing fields,
so the emitters and this report's parser can never drift apart
silently (tests/test_trace_report.py holds every event of the vocabulary
to it).

The reduction itself lives in :mod:`flexflow_tpu.obs.report`
(``summarize_jsonl``); a tier-1 test round-trips a real export through
this CLI (tests/test_trace_report.py).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize a flexflow_tpu telemetry JSONL")
    ap.add_argument("jsonl", help="path to a Telemetry.export *.jsonl")
    ap.add_argument("--indent", type=int, default=None,
                    help="pretty-print with this JSON indent")
    ap.add_argument("--check", action="store_true",
                    help="validate the JSONL against the expected event "
                         "schema instead of summarizing; exit nonzero on "
                         "unknown/missing fields")
    args = ap.parse_args(argv)

    if args.check:
        from flexflow_tpu.obs.report import validate_jsonl

        errors = validate_jsonl(args.jsonl)
        print(json.dumps({"ok": not errors, "path": args.jsonl,
                          "errors": errors}, indent=args.indent))
        return 1 if errors else 0

    from flexflow_tpu.obs.report import summarize_jsonl

    summary = summarize_jsonl(args.jsonl)
    if summary.get("dropped"):
        print(f"WARNING: trace ring dropped {summary['dropped']} of "
              f"{summary['events']} events — this summary is computed "
              "from a TRUNCATED trace (raise Telemetry(capacity=...) to "
              "keep the full run)", file=sys.stderr)
    print(json.dumps(summary, indent=args.indent))
    return 0


if __name__ == "__main__":
    sys.exit(main())
