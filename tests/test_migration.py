"""Live plan migration (serve/migration.py): drain/rebuild/readmit,
rollback, and chaos-hardened recovery.

The load-bearing contracts (ISSUE 12 acceptance):

* **Bit-identity across the switch** — for greedy AND seeded sampling,
  every in-flight request's tokens after migrating tp1→pp2,
  contiguous→paged, and spec-on→spec-off equal the no-migration run
  (recovery is the r9 recompute path, rids — and with them the
  (rid, token_index) sample-key fold — are preserved across managers).
* **Zero lost requests** — a rebuild/readmit failure rolls back to the
  incumbent (``migration_rolled_back``), the drained requests readmit
  THERE, and every rid reaches exactly one terminal outcome; seeded
  faults injected into the migration phases retry with backoff.
* **KV refcount no-leak** — the incumbent's allocator tears down with
  zero attributed rids; the paged allocator's page pool and prefix index
  reset with the buffers.
"""

import numpy as np
import pytest

from flexflow_tpu.obs import (
    PlanHealthConfig,
    PlanHealthMonitor,
    Telemetry,
)
from flexflow_tpu.serve import (
    FaultInjector,
    GenerationConfig,
    MigrationConfig,
    MigrationController,
    RequestManager,
    RequestStatus,
    ResilienceConfig,
    RetryPolicy,
    SpecInferManager,
    TERMINAL_STATUSES,
)
from flexflow_tpu.serve.migration import base_plan_key, spec_shape

from test_serve import TINY, make_im
from test_serving_under_load import VirtualClock, poisson_arrivals

pytestmark = pytest.mark.migration

PROMPTS = [[3, 5, 7, 9, 11], [2, 4, 6], [13, 8, 1]]


def quiet(rm):
    rm._sleep = lambda s: None
    return rm


def greedy(max_new=8):
    return GenerationConfig(max_new_tokens=max_new)


def seeded(max_new=8):
    return GenerationConfig(max_new_tokens=max_new, temperature=0.8,
                            top_p=0.9, seed=5)


def midflight_ctrl(rm, build, incumbent="tp1_pp1_m1", **cfg):
    """A controller staged so the switch lands MID-DECODE: small decode
    stretches + one defer tick + one admission-closed grace tick."""
    rm.scan_chunk = 2
    kw = dict(defer_ticks=2, drain_grace_ticks=1)
    kw.update(cfg)
    return MigrationController(rm, build, plan={"plan_key": incumbent},
                               config=MigrationConfig(**kw))


def assert_clean_switch(ctrl, old_im):
    """The completed record + the incumbent's no-leak teardown."""
    rec = ctrl.history[-1]
    assert rec["outcome"] == "completed"
    assert rec["preempted_requests"] > 0, "switch was not in-flight"
    assert rec["kv_leaked_rids"] == []
    assert old_im.kv.attributed_rids() == []
    assert old_im.state is None, "incumbent buffers not torn down"


# ---------------------------------------------------------------------------
# bit-identity across the switch (the acceptance matrix)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("gen_fn", [greedy, seeded],
                         ids=["greedy", "seeded"])
def test_migrate_contiguous_to_paged_bit_identical(gen_fn):
    im = make_im(max_seq=64)
    want = RequestManager(im, gen_fn()).generate(PROMPTS)

    im = make_im(max_seq=64)
    rm = RequestManager(im, gen_fn())
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16))
    ctrl.request_migration("tp1_pp1_m1_paged")
    got = rm.generate(PROMPTS)
    assert got == want, "tokens diverged across the live switch"
    assert_clean_switch(ctrl, im)
    assert ctrl.rm is not rm and ctrl.rm.im.kv.paged
    # the successor's allocator released everything on completion too
    assert ctrl.rm.im.kv.attributed_rids() == []
    assert ctrl.rm.im.kv.pages_held() == 0


def test_successor_inherits_the_decode_pacing():
    """``scan_chunk`` and ``lifecycle_quantum`` cross the switch: a session
    pinned to its own stretch bounds does not fall back to the class
    defaults on the successor manager."""
    im = make_im(max_seq=64)
    rm = RequestManager(im, greedy())
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16))
    rm.lifecycle_quantum = 3   # beside midflight_ctrl's scan_chunk = 2
    assert RequestManager.scan_chunk != 2
    assert RequestManager.lifecycle_quantum != 3
    ctrl.request_migration("tp1_pp1_m1_paged")
    rm.generate(PROMPTS)
    assert_clean_switch(ctrl, im)
    assert ctrl.rm is not rm
    assert (ctrl.rm.scan_chunk, ctrl.rm.lifecycle_quantum) == (2, 3)


@pytest.mark.parametrize("gen_fn", [greedy, seeded],
                         ids=["greedy", "seeded"])
def test_migrate_tp1_to_pp2_bit_identical(gen_fn):
    from test_pp_serve import make_pp_im

    im = make_im(max_seq=64)
    want = RequestManager(im, gen_fn()).generate(PROMPTS)

    im = make_im(max_seq=64)
    rm = RequestManager(im, gen_fn())
    ctrl = midflight_ctrl(rm, lambda cand: make_pp_im({"pp": 2}, max_seq=64))
    ctrl.request_migration("tp1_pp2_m2")
    got = rm.generate(PROMPTS)
    assert got == want, "tokens diverged migrating onto the pp2 plan"
    assert_clean_switch(ctrl, im)
    assert ctrl.rm.im.pp == 2


@pytest.mark.spec
@pytest.mark.parametrize("gen_fn", [greedy, seeded],
                         ids=["greedy", "seeded"])
def test_migrate_spec_on_to_spec_off_full_rebuild(gen_fn):
    """Spec incumbent → plain incremental candidate via the FULL
    drain/rebuild/readmit path (fast path disabled): the greedy/seeded
    spec==incremental contract makes the switch bit-invisible."""
    from test_spec_infer import TINY_SSM

    gen = gen_fn(10)
    base = make_im(max_tokens=32, max_requests=2, max_seq=64)
    want = RequestManager(base, gen).generate(PROMPTS)

    llm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8)
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  cfg=TINY_SSM, topk=2, seed=123)
    sm = SpecInferManager(llm, ssm, gen, width=2, depth=3)
    ctrl = midflight_ctrl(
        sm, lambda cand: make_im(max_tokens=32, max_requests=2, max_seq=64),
        incumbent="tp1_pp1_m1_spec_w2d3", spec_flip_fast_path=False)
    ctrl.request_migration("tp1_pp1_m1")
    got = sm.generate(PROMPTS)
    assert got == want, "tokens diverged migrating spec -> incremental"
    rec = ctrl.history[-1]
    assert rec["outcome"] == "completed" and rec["mode"] == "rebuild"
    assert type(ctrl.rm) is RequestManager
    # BOTH incumbent deployments tore down leak-free
    assert llm.kv.attributed_rids() == [] and llm.state is None
    assert ssm.kv.attributed_rids() == [] and ssm.state is None


@pytest.mark.spec
def test_spec_off_recommendation_takes_flip_fast_path():
    """The r14 acceptance-drift candidate (same tp×pp×m, spec suffix
    dropped) needs NO rebuild: the controller flips set_spec_mode on
    every live request and the manager's default for future admissions —
    the manager object, its programs, and its caches are untouched."""
    from test_spec_infer import TINY_SSM

    gen = greedy(10)
    base = make_im(max_tokens=32, max_requests=2, max_seq=64)
    want = RequestManager(base, gen).generate(PROMPTS)

    llm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8)
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  cfg=TINY_SSM, topk=2, seed=123)
    tel = Telemetry()
    sm = SpecInferManager(llm, ssm, gen, width=2, depth=3, telemetry=tel)
    ctrl = midflight_ctrl(sm, lambda cand: pytest.fail("must not rebuild"),
                          incumbent="tp1_pp1_m1_spec_w2d3")
    ctrl.request_migration("tp1_pp1_m1", reasons=("workload_drift",))
    got = sm.generate(PROMPTS)
    assert got == want
    rec = ctrl.history[-1]
    assert rec["outcome"] == "completed" and rec["mode"] == "spec_flip"
    assert rec["preempted_requests"] == 0, "a flip must not preempt"
    assert ctrl.rm is sm, "fast path must keep the manager"
    assert sm.default_spec_mode is False
    assert llm.state is not None, "fast path must keep the caches"
    flips = [e for e in tel.trace.trace_events()
             if e.get("name") == "spec_mode_changed"]
    assert flips and all(e["args"]["spec"] is False for e in flips)
    assert [e["name"] for e in tel.trace.trace_events()
            if e.get("name", "").startswith("migration_")] \
        == ["migration_started", "migration_completed"]


def test_plan_key_helpers():
    assert base_plan_key("tp2_pp1_m1_spec_w2d3") == "tp2_pp1_m1"
    assert base_plan_key("tp1_pp2_m2") == "tp1_pp2_m2"
    assert spec_shape("tp2_pp1_m1_spec_w2d3") == (2, 3)
    assert spec_shape("tp2_pp1_m1") is None


# ---------------------------------------------------------------------------
# rollback: a failed rebuild/readmit never loses a request
# ---------------------------------------------------------------------------
def test_rollback_on_rebuild_failure_zero_lost_requests():
    im = make_im(max_seq=64)
    want = RequestManager(im, greedy()).generate(PROMPTS)

    im = make_im(max_seq=64)
    tel = Telemetry()
    rm = RequestManager(im, greedy(), telemetry=tel)

    def broken(cand):
        raise RuntimeError("candidate devices unavailable")

    ctrl = midflight_ctrl(rm, broken)
    ctrl.request_migration("tp4_pp1_m1")
    got = rm.generate(PROMPTS)
    assert got == want, "rollback must recompute bit-identically"
    rec = ctrl.history[-1]
    assert rec["outcome"] == "rolled_back" and rec["phase"] == "rebuild"
    assert ctrl.rm is rm, "rollback must keep the incumbent active"
    assert all(r.status is RequestStatus.COMPLETED
               for r in rm.requests.values())
    [ev] = [e for e in tel.trace.trace_events()
            if e.get("name") == "migration_rolled_back"]
    assert ev["args"]["candidate"] == "tp4_pp1_m1"
    assert "RuntimeError" in ev["args"]["reason"]
    assert tel.metrics.snapshot()["migrations_rolled_back"] == 1
    # admission reopened: a follow-up request serves normally
    assert rm.generate([[4, 2]])[0], "incumbent must keep serving"


def test_rollback_when_candidate_cannot_hold_a_request():
    """Readmit validation: a candidate whose max_seq_len cannot hold an
    in-flight request rolls the WHOLE migration back (losing the request
    is not an option) and tears the candidate's buffers down."""
    im = make_im(max_seq=64)
    rm = RequestManager(im, greedy())
    built = {}

    def small(cand):
        # max_seq 8 cannot hold prompt 5 + max_new 8 = 13 positions
        built["im"] = make_im(max_seq=8, max_requests=2, max_tokens=8)
        return built["im"]

    ctrl = midflight_ctrl(rm, small)
    ctrl.request_migration("tp1_pp1_m1_small")
    got = rm.generate(PROMPTS)
    rec = ctrl.history[-1]
    assert rec["outcome"] == "rolled_back" and rec["phase"] == "readmit"
    assert "does not fit" in rec["reason"]
    assert built["im"].state is None, "candidate buffers must tear down"
    assert all(r.status is RequestStatus.COMPLETED
               for r in rm.requests.values())
    assert len(got) == len(PROMPTS) and all(len(t) == 8 for t in got)


def test_reusing_the_incumbent_im_is_rejected():
    im = make_im(max_seq=64)
    rm = RequestManager(im, greedy())
    ctrl = midflight_ctrl(rm, lambda cand: im)  # the invalid builder
    ctrl.request_migration("tp1_pp1_m1_again")
    rm.generate(PROMPTS)
    rec = ctrl.history[-1]
    assert rec["outcome"] == "rolled_back" and rec["phase"] == "rebuild"
    assert "FRESH deployment" in rec["reason"]
    assert im.state is not None, "incumbent must survive its own rollback"


# ---------------------------------------------------------------------------
# chaos: seeded faults inside the migration phases
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_chaos_faults_in_migration_phases_retry_to_completion():
    im = make_im(max_seq=64)
    want = RequestManager(im, greedy()).generate(PROMPTS)

    im = make_im(max_seq=64)
    # every phase faults once (seeded, bounded): drain, rebuild, readmit
    # each retry within the budget and the switch still completes
    inj = FaultInjector(seed=3, p_by_site={"migration": 0.6}, max_faults=3)
    rm = quiet(RequestManager(
        im, greedy(), fault_injector=inj,
        resilience=ResilienceConfig(retry=RetryPolicy(max_retries=5,
                                                      backoff_s=0.0))))
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16))
    ctrl.request_migration("tp1_pp1_m1_paged")
    got = rm.generate(PROMPTS)
    assert inj.injected == 3, "seeded migration faults did not all fire"
    assert got == want, "chaos migration diverged from the fault-free run"
    assert_clean_switch(ctrl, im)


@pytest.mark.chaos
def test_chaos_unrecoverable_rebuild_rolls_back_all_terminal():
    """Faults past the retry budget at the rebuild site: the migration
    rolls back, every request still reaches a terminal outcome on the
    incumbent, and the event is schema-validated."""
    import json
    import os
    import tempfile

    from flexflow_tpu.obs.report import validate_jsonl

    im = make_im(max_seq=64)
    want = RequestManager(im, greedy()).generate(PROMPTS)

    im = make_im(max_seq=64)
    tel = Telemetry()
    inj = FaultInjector(seed=0, p_by_site={"migration_rebuild": 1.0},
                        max_faults=10)
    rm = quiet(RequestManager(
        im, greedy(), telemetry=tel, fault_injector=inj,
        resilience=ResilienceConfig(retry=RetryPolicy(max_retries=2,
                                                      backoff_s=0.0))))
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16))
    ctrl.request_migration("tp1_pp1_m1_paged")
    got = rm.generate(PROMPTS)
    assert got == want
    rec = ctrl.history[-1]
    assert rec["outcome"] == "rolled_back" and rec["phase"] == "rebuild"
    assert "retries exhausted" in rec["reason"]
    assert all(r.status in TERMINAL_STATUSES for r in rm.requests.values())
    assert all(r.outcome == "ok" for r in rm.requests.values())
    # the exported trace carries the rollback and validates clean
    with tempfile.TemporaryDirectory() as d:
        paths = tel.export(d, prefix="chaos_mig")
        assert validate_jsonl(paths["jsonl"]) == []
        names = [json.loads(line).get("name")
                 for line in open(paths["jsonl"])]
        assert "migration_rolled_back" in names


@pytest.mark.chaos
def test_chaos_migration_plus_dispatch_faults_all_terminal():
    """Faults across BOTH the migration phases and the ordinary dispatch
    sites of the two managers: the engine never crashes and every request
    ends terminal with bit-identical ok-outcome tokens."""
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8], [33, 1], [9, 8, 1, 5]]
    im = make_im(max_seq=64)
    want = RequestManager(im, greedy(6)).generate(prompts)

    im = make_im(max_seq=64)
    inj = FaultInjector(seed=7, p=0.25, max_faults=6)
    rm = quiet(RequestManager(
        im, greedy(6), fault_injector=inj,
        resilience=ResilienceConfig(retry=RetryPolicy(max_retries=6,
                                                      backoff_s=0.0))))
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16))
    # tick-paced decode: chained stretches consolidate dispatch sites, so
    # the seeded injector barely fires — this test wants MANY fault
    # opportunities interleaved with the migration phases
    rm.scan_chunk = 1
    ctrl.request_migration("tp1_pp1_m1_paged")
    got = rm.generate(prompts)
    assert inj.injected >= 4, "seeded chaos barely fired"
    active = ctrl.rm
    assert all(r.status in TERMINAL_STATUSES
               for r in active.requests.values())
    assert got == want, "chaos (migration + dispatch) diverged"
    # whatever path the run took, nothing leaked on either deployment
    assert im.kv.attributed_rids() == []
    assert active.im.kv.attributed_rids() == []


# ---------------------------------------------------------------------------
# arrivals: one open-loop session spans the switch
# ---------------------------------------------------------------------------
def test_migration_mid_arrival_session_records_complete():
    rng = np.random.RandomState(11)
    arrivals = poisson_arrivals(rng, 6, rate_per_s=40.0,
                                vocab=TINY.vocab_size, max_new=6)
    im = make_im(max_seq=64, max_requests=2)
    rm = RequestManager(im, greedy(6))
    recs0 = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    want = [recs0[rid]["tokens"] for rid in sorted(recs0)]

    im = make_im(max_seq=64, max_requests=2)
    rm = RequestManager(im, greedy(6))
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, max_requests=2,
                                 kv_page_size=16))
    ctrl.request_migration("tp1_pp1_m1_paged")
    recs = ctrl.rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    assert ctrl.history[-1]["outcome"] == "completed"
    assert ctrl.rm is not rm, "the arrival loop must hand off mid-run"
    got = [recs[rid]["tokens"] for rid in sorted(recs)]
    assert got == want, "arrival outputs diverged across the switch"
    assert sorted(recs) == sorted(recs0), "a record was lost in the handoff"
    for rec in recs.values():
        assert rec["outcome"] == "ok"
        assert "queue_wait_s" in rec and "prefill_s" in rec
        assert "finish_s" in rec


# ---------------------------------------------------------------------------
# plan-health auto path + hysteresis
# ---------------------------------------------------------------------------
def _breaching_monitor(tel, candidate, incumbent="tp1_pp1_m1"):
    """A monitor whose first check breaches (absurd prediction + zero
    drift threshold) and recommends ``candidate``."""
    return PlanHealthMonitor(
        tel, {"plan_key": incumbent, "tpot_ms": 0.0001},
        reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=1, max_tpot_error_frac=0.01,
                                drift_min_samples=1, drift_threshold=0.0),
        search_fn=lambda: dict(candidate))


def test_auto_migration_consumes_replan_recommendation():
    """The closed loop end to end: PlanHealthMonitor breaches on the live
    run, emits replan_recommended, and the controller ACTS — the switch
    completes mid-serve with no operator call, and the monitor is rebased
    onto the new plan."""
    im = make_im(max_seq=64)
    want = RequestManager(im, greedy()).generate(PROMPTS)

    im = make_im(max_seq=64)
    tel = Telemetry()
    candidate = {"plan_key": "tp1_pp1_m1_paged", "tpot_ms": 1.0}
    mon = _breaching_monitor(tel, candidate)
    rm = RequestManager(im, greedy(), telemetry=tel, plan_health=mon)
    rm.health_check_every = 1
    rm.scan_chunk = 2
    ctrl = MigrationController(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16),
        config=MigrationConfig(defer_ticks=0, drain_grace_ticks=1))
    got = rm.generate(PROMPTS)
    assert got == want
    rec = ctrl.history[-1]
    assert rec["outcome"] == "completed"
    assert rec["candidate"] == "tp1_pp1_m1_paged"
    assert rec["incumbent"] == "tp1_pp1_m1"
    # the monitor now watches the NEW plan with fresh edge-trigger state
    assert mon.plan["plan_key"] == "tp1_pp1_m1_paged"
    assert mon.recommendation is None
    assert ctrl.rm.plan_health is mon
    assert mon.kv_allocator is ctrl.rm.im.kv
    snap = tel.metrics.snapshot()
    assert snap["migrations_completed"] == 1
    assert snap["migration_preempted_requests"] > 0


def test_controller_cooldown_prevents_flapping():
    """After a completed migration the controller ignores fresh
    recommendations for cooldown_ticks — an oscillating candidate pair
    cannot whipsaw the deployment."""
    im = make_im(max_seq=64)
    tel = Telemetry()
    flip = {"n": 0}

    def search_fn():
        flip["n"] += 1
        key = "tp1_pp1_m1_paged" if flip["n"] % 2 else "tp1_pp1_m1"
        return {"plan_key": key, "tpot_ms": 1.0}

    mon = PlanHealthMonitor(
        tel, {"plan_key": "tp1_pp1_m1", "tpot_ms": 0.0001},
        reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=1, max_tpot_error_frac=0.01,
                                drift_min_samples=1, drift_threshold=0.0),
        search_fn=search_fn)
    rm = RequestManager(im, greedy(12), telemetry=tel, plan_health=mon)
    rm.health_check_every = 1
    rm.scan_chunk = 1

    def build(cand):
        return make_im(max_seq=64, kv_page_size=16) \
            if "paged" in cand["plan_key"] else make_im(max_seq=64)

    ctrl = MigrationController(
        rm, build, config=MigrationConfig(defer_ticks=0,
                                          drain_grace_ticks=0,
                                          cooldown_ticks=1000))
    rm.generate(PROMPTS)
    completed = [h for h in ctrl.history if h["outcome"] == "completed"]
    assert len(completed) == 1, (
        f"cooldown failed: {len(completed)} migrations in one short run")


def test_manual_migration_while_idle_executes_at_loop_exit():
    """A migration staged while the loop has no work executes in the idle
    window (zero preemptions) and the successor serves the next calls."""
    im = make_im(max_seq=64)
    rm = RequestManager(im, greedy())
    ctrl = MigrationController(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16),
        plan={"plan_key": "tp1_pp1_m1"},
        config=MigrationConfig(defer_ticks=0, drain_grace_ticks=2))
    first = rm.generate(PROMPTS)          # completes before any staging
    ctrl.request_migration("tp1_pp1_m1_paged")
    second = ctrl.rm.serve_incr_decoding()  # no work: idle switch
    assert ctrl.history[-1]["outcome"] == "completed"
    assert ctrl.history[-1]["preempted_requests"] == 0
    assert ctrl.rm is not rm and ctrl.rm.im.kv.paged
    # the successor serves fresh work, with all old results intact
    assert len(first) == len(PROMPTS)
    assert sorted(second) == sorted(r for r in rm.requests)
    out = ctrl.rm.generate([[6, 2, 4]])
    assert len(out[0]) == 8


def test_downtime_ticks_count_admission_closed_window():
    im = make_im(max_seq=64)
    tel = Telemetry()
    rm = RequestManager(im, greedy(12), telemetry=tel)
    rm.scan_chunk = 1
    ctrl = midflight_ctrl(
        rm, lambda cand: make_im(max_seq=64, kv_page_size=16),
        defer_ticks=1, drain_grace_ticks=3)
    ctrl.request_migration("tp1_pp1_m1_paged")
    rm.generate(PROMPTS)
    rec = ctrl.history[-1]
    # the 3 grace ticks ran with admission closed (+ the execute boundary)
    assert rec["downtime_ticks"] == 3
    assert rec["downtime_s"] > 0
    assert tel.metrics.snapshot()["migration_downtime_ticks"] == 3
    [ev] = [e for e in tel.trace.trace_events()
            if e.get("name") == "migration_completed"]
    assert ev["args"]["downtime_ticks"] == 3
    assert ev["args"]["preempted_requests"] == rec["preempted_requests"]
