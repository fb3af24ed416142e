"""Time-travel serving (obs/replay.py): trace capture + deterministic replay.

The load-bearing contracts (ISSUE 19 acceptance):

* **Capture is invisible** — serving with a ``record_trace=`` handle
  attached produces bit-identical records to an unrecorded run (the
  recorder only appends to host lists; it never reads the serve clock).
* **Fidelity replay is bit-identical from the artifact alone** — for
  greedy AND seeded sampling, loading a trace file and re-driving a
  freshly built identical deployment (the harness pins the recorded gen
  config / sampling seed / fault schedule / kill schedule) reproduces
  every request's token stream, terminal outcome, and failover count —
  including a chaos fleet run with seeded dispatch faults, a mid-run
  replica kill, and a brownout ladder walking under load.
* **The artifact is integrity-stamped** — prompt/token hashes catch a
  hand-edited trace, a version bump refuses to load, and malformed
  arrival-options dicts are recorded RAW so their rejection replays
  identically.
* **What-if replay prices a different plan with no device** — the
  recorded arrival stream runs through the slot-level simulator under a
  ``price_plan``-style candidate; latencies and the OUTCOME MIX respond
  (ttl/deadline re-applied to simulated queueing), and two candidates
  diff under ``obs.report.compare``'s exact discipline.
"""

import json
import types

import numpy as np
import pytest

import jax

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.obs import Telemetry
from flexflow_tpu.obs.replay import (
    ReplayHarness,
    TRACE_VERSION,
    TrafficTrace,
    TrafficTraceRecorder,
    VirtualClock,
    token_hash,
)
from flexflow_tpu.obs.report import summarize_jsonl, validate_jsonl
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.serve import (
    BrownoutConfig,
    BrownoutController,
    FaultInjector,
    FleetRouter,
    GenerationConfig,
    InferenceManager,
    RequestManager,
    ResilienceConfig,
    SLOPolicy,
    build_model,
)

from test_serve import TINY

pytestmark = pytest.mark.replay


def fresh_im(max_tokens=16, max_requests=2, max_seq=64, seed=7):
    """A deployment with its OWN buffers/programs — same seed => identical
    weights, the fidelity-replay precondition (test_fleet's idiom)."""
    mesh = make_mesh({"tp": 1}, jax.devices()[:1])
    ff = FFModel(FFConfig(), mesh=mesh)
    build_model(ff, TINY, max_tokens)
    im = InferenceManager(
        ff, max_requests=max_requests, max_tokens_per_batch=max_tokens,
        max_seq_len=max_seq)
    im.init_operators_inference(rng=jax.random.PRNGKey(seed))
    return im


def greedy(max_new=8):
    return GenerationConfig(max_new_tokens=max_new)


def seeded(max_new=8):
    return GenerationConfig(max_new_tokens=max_new, temperature=0.8,
                            top_p=0.9, seed=5)


@pytest.fixture(scope="module")
def im_pair():
    """One engine for the recorded run, one freshly built identical
    engine for the replay side (never the same buffers)."""
    return fresh_im(), fresh_im()


ARRIVALS = [
    (0.000, [3, 5, 7, 9], 6, {"priority": 1}),
    (0.002, [2, 4, 6], 6),
    (0.004, [13, 8, 1, 5, 11], 4, {"slo_class": "batch"}),
]


# ---------------------------------------------------------------------------
# artifact round trip + integrity stamps
# ---------------------------------------------------------------------------
def test_recorder_artifact_roundtrip_and_integrity(tmp_path, im_pair):
    path = str(tmp_path / "run.trace.jsonl")
    rm = RequestManager(im_pair[0], seeded())
    recorder = TrafficTraceRecorder(path=path)
    records = rm.serve_with_arrivals(list(ARRIVALS), clock=VirtualClock(),
                                     record_trace=recorder)
    assert recorder.saved_path == path

    trace = TrafficTrace.load(path)
    assert trace.validate() == []
    meta = trace.meta
    assert meta["version"] == TRACE_VERSION
    assert meta["driver"] == "RequestManager"
    assert meta["gen"]["seed"] == 5 and meta["gen"]["temperature"] == 0.8
    assert meta["plan"]["plan_key"] == "tp1_pp1_m1"
    assert meta["plan"]["max_requests"] == 2
    assert meta["fault"] is None
    assert meta["arrivals"] == 3 and meta["requests"] == 3

    # the arrival stream round-trips VERBATIM (raw opts as a 4th element)
    assert trace.arrival_tuples() == [
        (0.000, [3, 5, 7, 9], 6, {"priority": 1}),
        (0.002, [2, 4, 6], 6),
        (0.004, [13, 8, 1, 5, 11], 4, {"slo_class": "batch"}),
    ]
    # recorded outcomes re-shape into the serve_with_arrivals schema
    recs = trace.records()
    assert sorted(recs) == sorted(records)
    for rid, rec in records.items():
        assert recs[rid]["tokens"] == rec["tokens"]
        assert recs[rid]["outcome"] == rec["outcome"]

    # integrity: a hand-edited token stream no longer matches its hash
    tampered = TrafficTrace.load(path)
    victim = next(o for o in tampered.outcomes if o["tokens"])
    victim["tokens"][0] ^= 1
    errors = tampered.validate()
    assert any("hash mismatch" in e for e in errors)

    # a future-versioned artifact refuses to load
    lines = open(path).read().splitlines()
    head = json.loads(lines[0])
    head["version"] = TRACE_VERSION + 1
    bad = tmp_path / "future.trace.jsonl"
    bad.write_text("\n".join([json.dumps(head)] + lines[1:]) + "\n")
    with pytest.raises(ValueError, match="version"):
        TrafficTrace.load(str(bad))

    # unknown line kinds are an error, not silently dropped
    junk = tmp_path / "junk.trace.jsonl"
    junk.write_text(lines[0] + "\n" + json.dumps({"kind": "mystery"}) + "\n")
    with pytest.raises(ValueError, match="unknown trace line kind"):
        TrafficTrace.load(str(junk))


# ---------------------------------------------------------------------------
# fidelity replay: greedy AND seeded, capture invisible
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("gen_fn", [greedy, seeded], ids=["greedy", "seeded"])
def test_fidelity_replay_bit_identical(tmp_path, im_pair, gen_fn):
    path = str(tmp_path / f"{gen_fn.__name__}.trace.jsonl")
    im_rec, im_play = im_pair

    # capture must be invisible: an unrecorded control run on the replay
    # engine serves the same stream first
    control = RequestManager(im_play, gen_fn()).serve_with_arrivals(
        list(ARRIVALS), clock=VirtualClock())

    rm = RequestManager(im_rec, gen_fn())
    recorder = TrafficTraceRecorder(path=path)
    recorded = rm.serve_with_arrivals(list(ARRIVALS), clock=VirtualClock(),
                                      record_trace=recorder)
    assert {r: recorded[r]["tokens"] for r in recorded} == \
        {r: control[r]["tokens"] for r in control}
    assert any(recorded[r]["tokens"] for r in recorded)

    # replay FROM THE FILE onto a fresh manager with a deliberately wrong
    # gen config — pin() must install the recorded one
    trace = TrafficTrace.load(path)
    assert trace.validate() == []
    harness = ReplayHarness(trace)
    rm2 = RequestManager(im_play, GenerationConfig(max_new_tokens=2))
    replayed = harness.replay(rm2)
    assert rm2.gen.seed == gen_fn().seed
    fidelity = harness.verify(replayed)
    assert fidelity["bit_identical"], fidelity["mismatches"]
    assert fidelity["requests"] == len(ARRIVALS)
    assert fidelity["mismatches"] == []

    if gen_fn is greedy:
        # and verify() actually bites: a perturbed replay is flagged
        broken = {r: dict(rec) for r, rec in replayed.items()}
        rid = next(r for r in broken if broken[r]["tokens"])
        broken[rid] = dict(broken[rid],
                           tokens=[t + 1 for t in broken[rid]["tokens"]])
        res = harness.verify(broken)
        assert not res["bit_identical"]
        assert any(m["field"] == "tokens" for m in res["mismatches"])
        # and a missing request is a presence mismatch
        del broken[rid]
        res = harness.verify(broken)
        assert any(m["field"] == "presence" for m in res["mismatches"])


def test_malformed_options_and_ttl_replay_their_outcomes(tmp_path, im_pair):
    """The RAW options dict rides the artifact: a malformed dict replays
    its REJECTED outcome, an aggressive ttl replays its timeout."""
    im_rec, im_play = im_pair
    arrivals = [
        (0.000, [3, 5, 7], 6),
        (0.001, [2, 4], 6, {"priority": "not-an-int"}),   # -> rejected
        (0.002, [9, 1, 5], 6, {"bogus_knob": 1}),         # -> rejected
        (0.003, [6, 2, 8, 4], 6, {"ttl_s": 1e-6}),        # -> timeout
    ]
    path = str(tmp_path / "opts.trace.jsonl")
    rm = RequestManager(im_rec, greedy())
    recorder = TrafficTraceRecorder(path=path)
    recorded = rm.serve_with_arrivals(list(arrivals), clock=VirtualClock(),
                                      record_trace=recorder)
    outcomes = sorted(r["outcome"] for r in recorded.values())
    assert outcomes.count("rejected") == 2
    assert "timeout" in outcomes

    trace = TrafficTrace.load(path)
    # the bad dicts round-trip verbatim
    tuples = trace.arrival_tuples()
    assert tuples[1][3] == {"priority": "not-an-int"}
    assert tuples[2][3] == {"bogus_knob": 1}
    harness = ReplayHarness(trace)
    replayed = harness.replay(RequestManager(im_play, greedy()))
    fidelity = harness.verify(replayed)
    assert fidelity["bit_identical"], fidelity["mismatches"]
    assert sorted(r["outcome"] for r in replayed.values()) == outcomes


# ---------------------------------------------------------------------------
# the chaos contract: fleet + seeded faults + kill + brownout, replayed
# from the artifact alone
# ---------------------------------------------------------------------------
def chaos_arrivals():
    rng = np.random.RandomState(11)
    arrivals = []
    for i in range(14):
        prompt = [int(x) for x in rng.randint(1, 63,
                                              size=rng.randint(3, 8))]
        cls = "latency_critical" if i % 3 == 0 else "batch"
        # gaps re-calibrated (0.002 -> 0.0005) against the chained decode
        # engine, which drains the old stream before the ladder moves
        arrivals.append((0.0005 * i, prompt, 8, {"slo_class": cls}))
    return arrivals


def build_chaos_fleet(gen, telemetry=None, injector=None):
    """The recorded deployment and the replay deployment are built by the
    SAME constructor — only gen/injector/kill provenance differs, and
    pin() installs those from the artifact."""
    policy = SLOPolicy.default(
        lc_reservation_frac=0.25, lc_ttft_p95_s=0.120, lc_tpot_p95_s=0.030,
        batch_max_pending=10, degraded_max_new_tokens=2)
    bo = BrownoutController(
        policy, BrownoutConfig(check_every=2, queue_depth_high=1,
                               escalate_after=2, deescalate_after=3),
        telemetry=telemetry, clock=VirtualClock())
    fleet = FleetRouter(
        [fresh_im() for _ in range(3)], gen=gen, telemetry=telemetry,
        resilience=ResilienceConfig(kv_gate=True), fault_injector=injector,
        slo=policy, brownout=bo)
    return fleet, bo


def test_fleet_chaos_replays_bit_identically_from_artifact(tmp_path):
    arrivals = chaos_arrivals()
    path = str(tmp_path / "chaos.trace.jsonl")

    # --- the recorded incident: seeded dispatch faults + replica1 killed
    # mid-run + the brownout ladder moving under the burst
    inj = FaultInjector(seed=11, p_by_site={"fleet_dispatch": 0.35},
                        max_faults=2)
    tel1 = Telemetry(clock=VirtualClock())
    fleet1, bo1 = build_chaos_fleet(seeded(), telemetry=tel1, injector=inj)
    fleet1.schedule_kill("replica1", 4)
    recorder = TrafficTraceRecorder(path=path, telemetry=tel1)
    rec = fleet1.serve_with_arrivals(list(arrivals), clock=VirtualClock(),
                                     record_trace=recorder)
    # the run actually exercised the chaos it claims to record
    assert all(r.get("outcome") for r in rec.values())
    assert sum(r.get("failovers", 0) for r in rec.values()) > 0
    assert bo1.history, "brownout ladder never moved — not a chaos run"
    levels1 = [int(level) for _, level, _ in bo1.history]

    # --- the artifact carries the full provenance
    trace = TrafficTrace.load(path)
    assert trace.validate() == []
    assert trace.meta["driver"] == "FleetRouter"
    assert trace.meta["fleet"]["replicas"] == 3
    assert trace.meta["fleet"]["kills"] == {"replica1": 4}
    assert trace.meta["fault"]["seed"] == 11
    assert trace.meta["fault"]["max_faults"] == 2
    assert trace.meta["slo"]["classes"]["latency_critical"]
    assert any("failovers" in o for o in trace.outcomes)
    assert any(o.get("replica") for o in trace.outcomes)

    # --- replay from the artifact ALONE: fresh identical fleet, no
    # injector, no scheduled kill, wrong gen — pin() installs all three
    tel2 = Telemetry(clock=VirtualClock())
    fleet2, bo2 = build_chaos_fleet(greedy(), telemetry=tel2, injector=None)
    harness = ReplayHarness(trace, telemetry=tel2)
    replayed = harness.replay(fleet2)
    assert fleet2.injector is not None and fleet2.injector.seed == 11
    assert fleet2.gen.seed == 5

    fidelity = harness.verify(replayed)
    assert fidelity["bit_identical"], fidelity["mismatches"]
    assert fidelity["requests"] == len(arrivals)
    # chaos replayed, not skipped: same failover total, same outcome mix,
    # same brownout walk
    assert sum(r.get("failovers", 0) for r in replayed.values()) == \
        sum(r.get("failovers", 0) for r in rec.values())
    mix = lambda rs: sorted(r["outcome"] for r in rs.values())  # noqa: E731
    assert mix(replayed) == mix(rec)
    assert [int(level) for _, level, _ in bo2.history] == levels1
    assert {r: replayed[r]["tokens"] for r in replayed} == \
        {r: rec[r]["tokens"] for r in rec}


# ---------------------------------------------------------------------------
# what-if replay: no device, priced latencies, outcome mix, diffs
# ---------------------------------------------------------------------------
def mk_trace():
    """A hand-built (hermetic) trace: 4 simultaneous arrivals on a
    2-slot recorded plan — slot contention is the what-if variable."""
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12], [2, 4, 6, 8]]
    opts = [{"slo_class": "latency_critical"}, {"ttl_s": 0.02}, None, None]
    arrivals, outcomes = [], []
    for i, p in enumerate(prompts):
        a = {"kind": "arrival", "offset_s": 0.0, "prompt": p,
             "prompt_len": len(p), "prompt_hash": token_hash(p),
             "max_new": 4}
        if opts[i]:
            a["opts"] = opts[i]
        arrivals.append(a)
        toks = [10 + i] * 4
        outcomes.append({"kind": "outcome", "rid": i,
                         "trace_id": f"r{i:05d}", "outcome": "ok",
                         "tokens": toks, "tokens_hash": token_hash(toks),
                         "prompt_len": len(p), "arrival_s": 0.0,
                         "queue_wait_s": 0.0, "prefill_s": 0.001,
                         "kv_bytes": 0})
    meta = {"kind": "trace_meta", "version": TRACE_VERSION,
            "driver": "RequestManager", "gen": {"max_new_tokens": 4},
            "plan": {"plan_key": "tp1_pp1_m1", "max_requests": 2},
            "fault": None, "arrivals": 4, "requests": 4}
    return TrafficTrace(meta=meta, arrivals=arrivals, outcomes=outcomes)


def test_what_if_prices_latency_outcome_mix_and_fleet_size():
    harness = ReplayHarness(mk_trace())

    fast = harness.what_if({"tpot_s": 1e-4, "plan_key": "tp2_pp1_m1"})
    assert fast["candidate"]["plan_key"] == "tp2_pp1_m1"
    assert fast["candidate"]["slots"] == 2
    assert fast["outcomes"] == {"ok": 4}
    assert fast["summary"]["goodput_tokens_per_sec"] > 0
    # the recorded streams are what the candidate serves (what-if moves
    # WHEN tokens land, never WHICH tokens)
    assert fast["records"][0]["tokens"] == [10, 10, 10, 10]
    assert fast["records"][0]["slo_class"] == "latency_critical"
    assert "latency_critical" in fast["summary"].get("per_class", {})

    # a 20ms/token candidate blows the ttl request's bound: the outcome
    # MIX responds to the candidate, not just the latencies (tpot_ms
    # spelling accepted too)
    slow = harness.what_if({"tpot_ms": 20.0, "plan_key": "tp1_pp1_m1"})
    assert slow["outcomes"] == {"ok": 3, "timeout": 1}
    assert slow["records"][1]["outcome"] == "timeout"
    assert slow["records"][1]["tokens"] == []

    # doubling the fleet halves the slot contention: total simulated
    # queue wait drops
    wait = lambda r: sum(  # noqa: E731
        rec["queue_wait_s"] for rec in r["records"].values())
    assert wait(harness.what_if({"tpot_s": 5e-3}, fleet_size=2)) < \
        wait(harness.what_if({"tpot_s": 5e-3}))

    # deltas ride obs.report.compare's discipline: identical candidates diff
    # clean, the slow candidate is a latency/throughput regression of
    # the fast one with the thresholded-field vocabulary
    assert harness.diff(fast["summary"], fast["summary"])["ok"]
    res = harness.diff(fast["summary"], slow["summary"])
    assert not res["ok"]
    assert any(r["kind"] in ("latency", "throughput")
               for r in res["regressions"])

    # the recorded side of the diff comes from the artifact alone
    recorded = harness.recorded_summary()
    assert recorded["outcomes"] == {"ok": 4}

    with pytest.raises(ValueError, match="tpot"):
        harness.what_if({"plan_key": "nocost"})


def test_spec_manager_records_draft_tree_provenance():
    """SpecInferManager's trace header extends the base with the draft
    shape — what a what-if needs to price spec on/off candidates."""
    from flexflow_tpu.serve.spec_infer import SpecInferManager

    sm = SpecInferManager.__new__(SpecInferManager)
    sm.gen = greedy()
    sm.im = types.SimpleNamespace(max_requests=2, max_seq_len=64)
    sm.ssm = types.SimpleNamespace(max_requests=2, max_seq_len=32)
    sm.width, sm.depth = 2, 3
    sm.injector = None
    sm.slo = None
    meta = sm.trace_run_meta()
    assert meta["driver"] == "SpecInferManager"
    assert meta["spec"]["width"] == 2 and meta["spec"]["depth"] == 3
    assert meta["spec"]["draft_plan"]["max_seq_len"] == 32
    assert meta["plan"]["max_seq_len"] == 64


# ---------------------------------------------------------------------------
# the telemetry vocabulary round-trips the real export schema
# ---------------------------------------------------------------------------
def test_replay_telemetry_schema_and_report(tmp_path):
    tel = Telemetry(clock=VirtualClock())
    path = str(tmp_path / "mini.trace.jsonl")
    recorder = TrafficTraceRecorder(path=path, telemetry=tel)
    recorder.begin_run({"driver": "RequestManager",
                        "gen": {"max_new_tokens": 4}})
    recorder.record_arrival(0.0, [1, 2], 4, None)
    recorder.finalize({0: {"trace_id": "r00000", "outcome": "ok",
                           "tokens": [7], "arrival_s": 0.0,
                           "prompt_len": 2}})

    trace = TrafficTrace.load(path)
    harness = ReplayHarness(trace, telemetry=tel)
    harness.what_if({"tpot_s": 1e-3})                 # started + completed
    clean = harness.verify(trace.records())           # completed, 0 miss
    assert clean["bit_identical"]
    missing = harness.verify({})                      # 1 presence mismatch
    assert not missing["bit_identical"]

    snap = tel.metrics.snapshot()
    assert snap["traces_recorded"] == 1
    # what_if + two verifies each complete a replay
    assert snap["replays_run"] == 3
    assert snap["replay_mismatches"] == 1

    paths = tel.export(str(tmp_path), prefix="replaytest")
    assert validate_jsonl(paths["jsonl"]) == []
    summary = summarize_jsonl(paths["jsonl"])
    rep = summary["replay"]
    assert rep["recorded"] and rep["recorded"][0]["arrivals"] == 1
    assert len(rep["completed"]) == 3
    assert rep["mismatches"] == [{"trace_id": "r00000",
                                  "field": "presence"}]
    assert rep["counters"]["replay_mismatches"] == 1
    # replay_mismatch carries a trace_id but must NOT create a phantom
    # per-request entry in the report
    assert summary["requests"] == 0
    assert summary["telemetry_events_dropped"] == 0


def test_healthy_replay_materializes_the_mismatch_counter():
    """A clean replay exports replay_mismatches=0 — the exact-compare
    class needs the field PRESENT in the healthy baseline to catch a
    future increase (missing-on-the-old-side is not compared)."""
    tel = Telemetry(clock=VirtualClock())
    harness = ReplayHarness(mk_trace(), telemetry=tel)
    clean = harness.verify(mk_trace().records())
    assert clean["bit_identical"]
    snap = tel.metrics.snapshot()
    assert snap["replay_mismatches"] == 0
    assert snap["replays_run"] == 1
