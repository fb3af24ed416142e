"""Plan-health monitoring (obs/plan_health.py): SLO / prediction-error /
drift checks, the replan recommendation, and the ISSUE 6 acceptance
contract — serve outputs BIT-IDENTICAL with the drift/plan-health layer
on vs off (tokens, logits, caches), including a pp2 virtual-mesh config.
"""

import numpy as np

from flexflow_tpu.obs import (
    NULL_TELEMETRY,
    PlanHealthConfig,
    PlanHealthMonitor,
    Telemetry,
)
from flexflow_tpu.serve import GenerationConfig, RequestManager

from test_calibration_loop import calibration_scenario
from test_serve import TINY, make_im


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _plan(tpot_ms=1.0, key="tp1_pp1_m1", ttft_ms=None):
    p = {"plan_key": key, "tpot_ms": tpot_ms}
    if ttft_ms is not None:
        p["ttft_ms"] = ttft_ms
    return p


def _warm(tel, n=10, ttft_s=0.01, tpot_s=0.001, prompt_len=16, out_len=8):
    for i in range(n):
        tid = f"h{i:05d}"
        tel.request_enqueued(tid, prompt_len=prompt_len)
        tel.request_first_token(tid, ttft_s=ttft_s)
        tel.request_finished(tid, n_tokens=out_len, tpot_s=tpot_s)


# ---------------------------------------------------------------------------
# monitor semantics
# ---------------------------------------------------------------------------
def test_healthy_plan_stays_quiet():
    tel = Telemetry(clock=ManualClock())
    _warm(tel, tpot_s=0.001)
    mon = PlanHealthMonitor(tel, _plan(tpot_ms=1.0),
                            reference=tel.workload.snapshot(),
                            config=PlanHealthConfig(min_requests=5),
                            search_fn=lambda: _plan(key="other"))
    rep = mon.check()
    assert rep["healthy"] and rep["reasons"] == []
    assert "candidate" not in rep
    assert tel.metrics.snapshot()["plan_health_ok"] == 1.0
    assert not [e for e in tel.trace.trace_events()
                if e.get("name") == "replan_recommended"]


def test_prediction_error_breach_recommends_replan():
    tel = Telemetry(clock=ManualClock())
    _warm(tel, tpot_s=0.003)  # measured 3x the predicted 1ms
    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=1.0), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=5, max_tpot_error_frac=0.5),
        search_fn=lambda: _plan(tpot_ms=2.9, key="tp2_pp1_m1"))
    rep = mon.check()
    assert rep["reasons"] == ["prediction_error"]
    assert rep["tpot_error_frac"] == 2.0
    assert rep["replan_recommended"]
    assert rep["candidate"]["plan_key"] == "tp2_pp1_m1"
    assert mon.recommendation["incumbent"] == "tp1_pp1_m1"
    evs = [e for e in tel.trace.trace_events()
           if e.get("name") == "replan_recommended"]
    assert len(evs) == 1
    assert evs[0]["args"]["candidate"] == "tp2_pp1_m1"
    assert "prediction_error" in evs[0]["args"]["reasons"]
    # a second check with the SAME candidate does not spam the ring
    mon.check()
    assert len([e for e in tel.trace.trace_events()
                if e.get("name") == "replan_recommended"]) == 1
    assert tel.metrics.snapshot()["replans_recommended"] == 1


def test_slo_breach_reasons():
    tel = Telemetry(clock=ManualClock())
    _warm(tel, ttft_s=0.5, tpot_s=0.001)
    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=1.0), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=5, slo_ttft_p95_s=0.1,
                                slo_tpot_p95_s=0.1))
    rep = mon.check()
    assert "slo_ttft" in rep["reasons"]
    assert "slo_tpot" not in rep["reasons"]
    assert not rep["healthy"]


def test_too_few_requests_skips_latency_checks():
    tel = Telemetry(clock=ManualClock())
    _warm(tel, n=2, tpot_s=1.0)   # horrid latency but only 2 requests
    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=0.001), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=8, slo_tpot_p95_s=0.01))
    assert mon.check()["healthy"]


def test_drift_reason_searches_on_live_profile():
    tel = Telemetry(clock=ManualClock(), workload_window=20)
    _warm(tel, n=20, prompt_len=16)
    ref = tel.workload.snapshot()
    seen = {}

    def search_fn():
        seen["features"] = tel.workload.features()
        return _plan(key="tp4_pp1_m1")

    mon = PlanHealthMonitor(
        tel, _plan(), reference=ref,
        config=PlanHealthConfig(min_requests=10_000, drift_threshold=0.25,
                                drift_min_samples=16),
        search_fn=search_fn)
    assert mon.check()["healthy"]
    _warm(tel, n=20, prompt_len=2048)  # the mix shifts
    rep = mon.check()
    assert rep["reasons"] == ["workload_drift"]
    assert rep["replan_recommended"]
    # the re-search saw the DRIFTED window, not the reference
    assert seen["features"]["mean_prompt_len"] > 1000


def test_failing_search_fn_degrades_to_report():
    tel = Telemetry(clock=ManualClock())
    _warm(tel, tpot_s=0.005)

    def boom():
        raise RuntimeError("no devices")

    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=1.0), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=5), search_fn=boom)
    rep = mon.check()
    assert not rep["healthy"]
    assert "RuntimeError" in rep["replan_error"]
    assert "candidate" not in rep


# ---------------------------------------------------------------------------
# acceptance: bit-identity with the drift/plan-health layer on vs off
# ---------------------------------------------------------------------------
def _monitored_rm(im, tel):
    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=0.0001),    # absurd prediction: always breaches
        reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=1, max_tpot_error_frac=0.01,
                                drift_min_samples=1, drift_threshold=0.0),
        search_fn=lambda: _plan(key="candidate_x"))
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                        telemetry=tel, plan_health=mon)
    rm.health_check_every = 1          # poll every tick: maximum exposure
    return rm, mon


def test_serve_bit_identical_with_plan_health_layer():
    prompts = [[3, 5, 7, 9, 11], [2, 4], [13, 6, 1]]
    im = make_im(max_seq=64)
    im.telemetry = NULL_TELEMETRY
    want = RequestManager(im, GenerationConfig(max_new_tokens=6)) \
        .generate(prompts)

    im = make_im(max_seq=64)
    tel = Telemetry()
    rm, mon = _monitored_rm(im, tel)
    try:
        got = rm.generate(prompts)
    finally:
        im.telemetry = NULL_TELEMETRY
    assert got == want, "plan-health layer changed serve outputs"
    assert mon.checks > 0, "monitor never polled"
    assert mon.recommendation["candidate"] == "candidate_x"


def test_step_logits_and_caches_bit_identical_with_monitor():
    from flexflow_tpu.serve.batch_config import BatchConfig

    def run(monitored):
        im = make_im(max_seq=64)
        im.telemetry = NULL_TELEMETRY
        if monitored:
            tel = Telemetry()
            rm, _ = _monitored_rm(im, tel)
        else:
            rm = RequestManager(im, GenerationConfig(max_new_tokens=6))
        rm.generate([[3, 5, 7, 9]])
        seq = np.zeros(im.max_requests, np.int32)
        seq[0] = 3
        bc = BatchConfig.build([3, 5, 7], [0, 0, 0], [0, 1, 2], seq,
                               max_tokens=im.max_tokens,
                               max_requests=im.max_requests)
        r = im.step(bc)
        caches = {
            name: {buf: np.asarray(arr).copy()
                   for buf, arr in bufs.items()}
            for name, bufs in im.state.items()
        }
        out = (np.asarray(r.token_ids).copy(),
               np.asarray(r.logits_max).copy(), caches)
        im.telemetry = NULL_TELEMETRY
        return out

    tok0, lg0, cache0 = run(False)
    tok1, lg1, cache1 = run(True)
    np.testing.assert_array_equal(tok1, tok0)
    np.testing.assert_array_equal(lg1, lg0)
    assert set(cache0) == set(cache1)
    for name in cache0:
        for buf in cache0[name]:
            np.testing.assert_array_equal(cache0[name][buf],
                                          cache1[name][buf], err_msg=buf)


def test_pp2_serve_bit_identical_with_plan_health_layer():
    """ISSUE 6 acceptance: the pp2 virtual-mesh config serves bit-identical
    tokens with the full drift/plan-health layer attached."""
    from test_pp_serve import make_pp_im

    prompts = [[3, 5, 7, 9], [11, 2]]
    pim = make_pp_im({"pp": 2})
    pim.telemetry = NULL_TELEMETRY
    want = RequestManager(pim, GenerationConfig(max_new_tokens=4)) \
        .generate(prompts)

    pim = make_pp_im({"pp": 2})
    tel = Telemetry()
    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=0.0001, key="tp1_pp2_m2"),
        reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=1, max_tpot_error_frac=0.01),
        search_fn=lambda: _plan(key="tp2_pp1_m1"))
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=4),
                        telemetry=tel, plan_health=mon)
    rm.health_check_every = 1
    try:
        got = rm.generate(prompts)
    finally:
        pim.telemetry = NULL_TELEMETRY
    assert got == want, "plan-health layer changed pp2 serve outputs"
    assert mon.checks > 0
    # and the layer actually observed/recommended on this run
    assert mon.recommendation["candidate"] == "tp2_pp1_m1"


def test_arrivals_bit_identical_with_plan_health_layer():
    from test_serving_under_load import VirtualClock, poisson_arrivals

    rng = np.random.RandomState(11)
    arrivals = poisson_arrivals(rng, 5, rate_per_s=30.0,
                                vocab=TINY.vocab_size, max_new=4)
    im = make_im(max_seq=64, max_requests=2)
    im.telemetry = NULL_TELEMETRY
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4))
    recs0 = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    want = [recs0[rid]["tokens"] for rid in sorted(recs0)]

    im = make_im(max_seq=64, max_requests=2)
    clk = VirtualClock()
    tel = Telemetry(clock=clk)
    rm, mon = _monitored_rm(im, tel)
    try:
        recs1 = rm.serve_with_arrivals(arrivals, clock=clk)
    finally:
        im.telemetry = NULL_TELEMETRY
    got = [recs1[rid]["tokens"] for rid in sorted(recs1)]
    assert got == want
    assert mon.checks > 0


# ---------------------------------------------------------------------------
# acceptance drift -> recommend flipping speculation off (ISSUE 11)
# ---------------------------------------------------------------------------
def test_acceptance_drift_recommends_non_spec_plan():
    """Hermetic ISSUE 11 satellite: the incumbent is a SPEC plan searched
    while the draft tracked the target (acceptance >> break-even); live
    acceptance then degrades below break-even, the spec_acceptance
    dimension's PSI crosses the drift threshold, and the monitor's
    re-search on the LIVE profile recommends the NON-SPEC plan."""
    from flexflow_tpu.search.serve_search import search_serve_plan

    scen = calibration_scenario()
    ff, devices, mm = scen["ff"], scen["devices"], scen["mm_true"]
    be = mm.spec.spec_break_even_acceptance

    tel = Telemetry(clock=ManualClock(), workload_window=24)

    def search_fn():
        return search_serve_plan(
            ff, n_chips=2, machine=mm, devices=devices, calibration=None,
            workload=dict(scen["ref_feats"],
                          mean_spec_acceptance=tel.workload.features()
                          ["mean_spec_acceptance"]),
            spec="auto")

    depth = 3
    # healthy phase: acceptance ~0.83 >> break-even -> spec incumbent
    for _ in range(24):
        tel.spec_acceptance(5, depth * 2)
    incumbent = search_fn()
    assert "_spec_" in incumbent["plan_key"], incumbent["plan_key"]

    mon = PlanHealthMonitor(
        tel, incumbent, reference=tel.workload.snapshot(),
        config=PlanHealthConfig(drift_threshold=0.25, drift_min_samples=16,
                                min_requests=1_000_000),
        search_fn=search_fn)
    healthy = mon.check()
    assert healthy["healthy"]

    # the draft stops tracking the target: acceptance collapses to ~0.17
    for _ in range(24):
        tel.spec_acceptance(1, depth * 2)
    assert tel.workload.features()["mean_spec_acceptance"] < be
    drifted = mon.check()
    assert "workload_drift" in drifted["reasons"]
    assert drifted["drift"]["per_dim"].get("spec_acceptance", 0.0) >= 0.25
    assert drifted["replan_recommended"]
    # the recommendation is the SAME tp x pp shape with speculation OFF
    assert "_spec_" not in drifted["candidate"]["plan_key"]
    assert mon.recommendation["incumbent"] == incumbent["plan_key"]
    evs = [e for e in tel.trace.trace_events()
           if e.get("name") == "replan_recommended"]
    assert len(evs) == 1
    assert "_spec_" not in evs[0]["args"]["candidate"]


# ---------------------------------------------------------------------------
# ISSUE 12 satellite: the replan flap guard (replan_cooldown_ticks)
# ---------------------------------------------------------------------------
def test_oscillating_candidates_without_cooldown_emit_every_check():
    """The historical dedup is once-per-DISTINCT-candidate: an A/B/A/B
    oscillation defeats it (every check's candidate differs from the
    last) — the baseline the cooldown knob exists to fix."""
    tel = Telemetry(clock=ManualClock())
    _warm(tel, tpot_s=0.005)
    flip = {"n": 0}

    def search_fn():
        flip["n"] += 1
        return _plan(key="plan_A" if flip["n"] % 2 else "plan_B")

    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=1.0), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=5, max_tpot_error_frac=0.5),
        search_fn=search_fn)
    for _ in range(6):
        mon.check()
    evs = [e for e in tel.trace.trace_events()
           if e.get("name") == "replan_recommended"]
    assert len(evs) == 6, "without a cooldown every oscillation emits"


def test_replan_cooldown_ticks_suppresses_flapping():
    tel = Telemetry(clock=ManualClock())
    _warm(tel, tpot_s=0.005)
    flip = {"n": 0}

    def search_fn():
        flip["n"] += 1
        return _plan(key="plan_A" if flip["n"] % 2 else "plan_B")

    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=1.0), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=5, max_tpot_error_frac=0.5,
                                replan_cooldown_ticks=10),
        search_fn=search_fn)
    reports = [mon.check() for _ in range(6)]
    # one emission, then suppression: the recommendation payload stays
    # pinned to the first candidate instead of whipsawing
    assert reports[0]["replan_recommended"]
    assert all(r.get("replan_suppressed") for r in reports[1::2]), \
        "the oscillating candidate must be suppressed inside the window"
    evs = [e for e in tel.trace.trace_events()
           if e.get("name") == "replan_recommended"]
    assert len(evs) == 1
    assert tel.metrics.snapshot()["replans_recommended"] == 1
    assert mon.recommendation["candidate"] == "plan_A"
    # past the window a NEW candidate may emit again
    for _ in range(6):
        mon.check()
    evs = [e for e in tel.trace.trace_events()
           if e.get("name") == "replan_recommended"]
    assert len(evs) == 2, "cooldown must expire, not silence forever"


def test_rebase_repoints_monitor_at_new_plan():
    """After a live migration the controller rebases the monitor: the
    candidate becomes the incumbent, drift re-references the CURRENT
    window, and stale recommendation/edge state clears."""
    tel = Telemetry(clock=ManualClock(), workload_window=20)
    _warm(tel, n=20, prompt_len=16)
    mon = PlanHealthMonitor(
        tel, _plan(tpot_ms=0.0001), reference=tel.workload.snapshot(),
        config=PlanHealthConfig(min_requests=5, max_tpot_error_frac=0.01),
        search_fn=lambda: _plan(key="tp2_pp1_m1", tpot_ms=5.0))
    rep = mon.check()
    assert rep["replan_recommended"]
    assert mon.recommendation["candidate_plan"]["plan_key"] == "tp2_pp1_m1"

    class FakeKV:  # allocator stand-in whose caches are unallocated
        def bytes_per_token(self):
            return None

    fake = FakeKV()
    _warm(tel, n=20, prompt_len=2048)  # the mix the NEW plan was priced for
    mon.rebase({"plan_key": "tp2_pp1_m1", "tpot_ms": 5.0},
               kv_allocator=fake)
    assert mon.plan["plan_key"] == "tp2_pp1_m1"
    assert mon.recommendation is None
    assert mon.kv_allocator is fake
    # the drifted window became the reference: no drift breach against it
    rep = mon.check()
    assert "workload_drift" not in rep["reasons"]
