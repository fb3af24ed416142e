"""obs/report.py ``compare``: the hermetic comparator of two summaries.

Deterministic work counters (obs/profiler.WORK_COUNTERS) diff EXACTLY —
any increase (or a vanished counter) is a regression; measured latency /
throughput fields diff against relative thresholds with direction
(latency up = bad, throughput down = bad).  Identical documents are ok.
"""

import copy

from flexflow_tpu.obs.report import classify, compare

DOC = {
    "serving_under_load": {
        "0.5x": {
            "ttft_p50_ms": 12.0,
            "tpot_p50_ms": 7.0,
            "goodput_tokens_per_sec": 900.0,
            "work": {"flops": 1.5e9, "kv_bytes_touched": 2.0e6,
                     "dispatches": 42},
            "step_profile": {"recompiles_total": 3, "host_syncs": 17},
        },
    },
    "note": "strings and bools are ignored",
    "bit_identical": True,
}


def test_identical_artifacts_pass():
    res = compare(DOC, DOC)
    assert res["ok"]
    assert res["regressions"] == []
    assert res["compared"] > 0


def test_counter_regression_fails_exactly():
    new = copy.deepcopy(DOC)
    # one extra dispatch: deterministic counters are exact by default
    new["serving_under_load"]["0.5x"]["work"]["dispatches"] = 43
    res = compare(DOC, new)
    assert not res["ok"]
    [reg] = res["regressions"]
    assert reg["field"].endswith("work.dispatches")
    assert reg["kind"] == "counter"
    assert reg["old"] == 42 and reg["new"] == 43


def test_recompile_regression_fails():
    new = copy.deepcopy(DOC)
    new["serving_under_load"]["0.5x"]["step_profile"][
        "recompiles_total"] = 9
    res = compare(DOC, new)
    assert not res["ok"]
    assert any(r["field"].endswith("recompiles_total")
               for r in res["regressions"])


def test_counter_improvement_is_not_a_regression():
    new = copy.deepcopy(DOC)
    new["serving_under_load"]["0.5x"]["work"]["flops"] = 1.0e9  # less work
    res = compare(DOC, new)
    assert res["ok"]
    assert any(i["field"].endswith("work.flops")
               for i in res["improvements"])


def test_missing_counter_is_a_regression():
    new = copy.deepcopy(DOC)
    del new["serving_under_load"]["0.5x"]["work"]
    res = compare(DOC, new)
    assert not res["ok"]
    missing = [r for r in res["regressions"] if "new" not in r]
    assert any(r["field"].endswith("work.flops") for r in missing)


def test_latency_threshold_and_direction():
    # +5% TPOT: inside the default 10% threshold
    new = copy.deepcopy(DOC)
    new["serving_under_load"]["0.5x"]["tpot_p50_ms"] = 7.35
    assert compare(DOC, new)["ok"]
    # +20% TPOT: regression
    new["serving_under_load"]["0.5x"]["tpot_p50_ms"] = 8.4
    res = compare(DOC, new)
    assert not res["ok"]
    assert any(r["field"].endswith("tpot_p50_ms")
               for r in res["regressions"])
    # -20% TPOT: improvement, not regression
    new["serving_under_load"]["0.5x"]["tpot_p50_ms"] = 5.6
    res = compare(DOC, new)
    assert res["ok"]
    assert any(i["field"].endswith("tpot_p50_ms")
               for i in res["improvements"])


def test_throughput_direction_is_inverted():
    new = copy.deepcopy(DOC)
    new["serving_under_load"]["0.5x"]["goodput_tokens_per_sec"] = 700.0
    res = compare(DOC, new)
    assert not res["ok"]
    [reg] = [r for r in res["regressions"]
             if r["field"].endswith("goodput_tokens_per_sec")]
    assert reg["kind"] == "throughput"
    # higher goodput is fine
    new["serving_under_load"]["0.5x"]["goodput_tokens_per_sec"] = 1100.0
    assert compare(DOC, new)["ok"]


def test_per_field_threshold_override():
    new = copy.deepcopy(DOC)
    new["serving_under_load"]["0.5x"]["tpot_p50_ms"] = 7.35  # +5%
    assert not compare(DOC, new, overrides={"tpot_p50_ms": 0.03})["ok"]
    # and counters can be given slack explicitly
    new = copy.deepcopy(DOC)
    new["serving_under_load"]["0.5x"]["work"]["flops"] = 1.5e9 * 1.01
    assert not compare(DOC, new)["ok"]
    assert compare(DOC, new, counter_threshold=0.05)["ok"]


def test_compare_importable_and_measured_only_where_present():
    """Measured fields present in only one artifact are skipped (not
    regressions); deterministic counters are the strict class."""
    old = {"tpot_p50_ms": 7.0, "extra_latency_ms": 3.0}
    new = {"tpot_p50_ms": 7.0}
    res = compare(old, new)
    assert res["ok"] and res["compared"] == 1


def test_replay_and_trace_counters_join_the_exact_compare_class():
    """Time-travel serving (obs/replay.py): any replay mismatch is a
    determinism regression, and a telemetry ring that starts dropping
    events fails the diff instead of just warning in trace_report."""
    for k in ("replay_mismatches", "telemetry_events_dropped"):
        assert classify(k) == "counter", k
    # the bookkeeping counters stay unclassified (more traces recorded
    # or replays run is not monotone-bad)
    assert classify("traces_recorded") is None
    assert classify("replays_run") is None
    old = {"replay": {"counters": {"replay_mismatches": 0}},
           "telemetry_events_dropped": 0}
    assert compare(old, old)["ok"]
    worse = {"replay": {"counters": {"replay_mismatches": 1}},
             "telemetry_events_dropped": 0}
    res = compare(old, worse)
    assert not res["ok"]
    assert any(r["field"].endswith("replay_mismatches")
               for r in res["regressions"])
    dropped = {"replay": {"counters": {"replay_mismatches": 0}},
               "telemetry_events_dropped": 7}
    res = compare(old, dropped)
    assert not res["ok"]
    assert any(r["field"].endswith("telemetry_events_dropped")
               for r in res["regressions"])


def test_fleet_counters_join_the_exact_compare_class():
    """The fleet robustness counters (serve/fleet.py) diff like
    deterministic work counters: exact by default, an increase is a
    regression (more replicas failing per served token), a decrease is
    an improvement — and the health GAUGES stay unclassified (their
    direction is not monotone-bad)."""
    for k in ("failovers_total", "replica_deaths", "replica_quarantines",
              "replica_degradations"):
        assert classify(k) == "counter", k
    assert classify("fleet_replicas_healthy") is None
    old = {"fleet": {"failovers_total": 1, "replica_deaths": 1}}
    worse = {"fleet": {"failovers_total": 2, "replica_deaths": 1}}
    res = compare(old, worse)
    assert not res["ok"]
    assert any(r["field"].endswith("failovers_total")
               for r in res["regressions"])
    assert compare(old, old)["ok"]
