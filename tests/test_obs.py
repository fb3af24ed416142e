"""Telemetry subsystem tests: hermetic virtual-clock coverage of the trace
recorder / metrics registry / calibration ledger, plus the overhead
contract — serve outputs are BIT-IDENTICAL with telemetry on or off
(telemetry is host-side only; nothing enters a jitted program).
"""

import json

import jax
import numpy as np

from flexflow_tpu.obs import (
    NULL_TELEMETRY,
    CalibrationLedger,
    MetricsRegistry,
    Telemetry,
    TraceRecorder,
    summarize_jsonl,
)
from flexflow_tpu.serve import GenerationConfig, RequestManager

from test_serve import TINY, make_im


class ManualClock:
    """Clock that only moves when told to — exact-timestamp assertions."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# trace recorder
# ---------------------------------------------------------------------------
def test_span_nesting_and_virtual_timestamps():
    clk = ManualClock()
    rec = TraceRecorder(clock=clk)
    with rec.span("outer", track="serve"):
        clk.advance(1.0)
        with rec.span("inner", track="serve"):
            clk.advance(0.25)
        clk.advance(0.5)
    evs = {e["name"]: e for e in rec.trace_events() if e["ph"] == "X"}
    outer, inner = evs["outer"], evs["inner"]
    # exact virtual times (µs): inner [1.0, 1.25] nested in outer [0, 1.75]
    assert outer["ts"] == 0.0 and outer["dur"] == 1.75e6
    assert inner["ts"] == 1.0e6 and inner["dur"] == 0.25e6
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["tid"] == inner["tid"]  # same named track


def test_ring_buffer_wraparound():
    rec = TraceRecorder(capacity=4, clock=ManualClock())
    for i in range(10):
        rec.instant(f"ev{i}")
    assert rec.emitted == 10
    assert rec.dropped == 6
    names = [e["name"] for e in rec.trace_events() if e["ph"] == "i"]
    assert names == ["ev6", "ev7", "ev8", "ev9"]  # oldest dropped
    # export still well-formed after wraparound
    json.dumps(rec.to_chrome_json())


def test_perfetto_trace_event_schema():
    clk = ManualClock()
    rec = TraceRecorder(clock=clk)
    with rec.span("work", cat="pp", track="stage0", stage=0):
        clk.advance(0.001)
    rec.instant("hop", cat="pp", track="stage1", stage=1)
    rec.counter("occupancy", 0.5)
    doc = rec.to_chrome_json()
    assert isinstance(doc["traceEvents"], list)
    tracks = {}
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        if ev["ph"] == "M":
            assert ev["name"] == "thread_name"
            tracks[ev["args"]["name"]] = ev["tid"]
            continue
        assert isinstance(ev["ts"], float)
        if ev["ph"] == "X":
            assert "dur" in ev
        if ev["ph"] == "i":
            assert ev["s"] == "t"
        if ev["ph"] == "C":
            assert "value" in ev["args"]
    assert {"stage0", "stage1", "counters"} <= set(tracks)
    # the JSON round-trips
    assert json.loads(json.dumps(doc)) == doc


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_metrics_registry():
    reg = MetricsRegistry()
    reg.counter("tokens").inc(5)
    reg.counter("tokens").inc(2)
    reg.gauge("occ").set(0.75)
    h = reg.histogram("lat")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    snap = reg.snapshot()
    assert snap["tokens"] == 7
    assert snap["occ"] == 0.75
    assert snap["lat"]["count"] == 5
    assert snap["lat"]["min"] == 1.0 and snap["lat"]["max"] == 100.0
    assert snap["lat"]["p50"] == 3.0  # nearest-rank: sorted[int(.5*5)]
    assert snap["lat"]["p95"] == 100.0
    # a name keeps its type
    import pytest

    with pytest.raises(TypeError):
        reg.gauge("tokens")


def test_histogram_window_bounds_memory():
    reg = MetricsRegistry()
    h = reg.histogram("w", window=4)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100              # lifetime count survives the window
    assert h.percentile(0.0) == 96.0   # window holds only the newest 4


# ---------------------------------------------------------------------------
# calibration ledger
# ---------------------------------------------------------------------------
def test_calibration_report():
    led = CalibrationLedger()
    led.predict("tp2_pp1_m1", tpot_ms=7.0, memory_gb=12.0)
    led.measure("tp2_pp1_m1", tpot_ms=7.7)
    rep = led.report()
    e = rep["plans"]["tp2_pp1_m1"]["tpot_ms"]
    assert e["predicted"] == 7.0 and e["measured"] == 7.7
    assert abs(e["ratio"] - 1.1) < 1e-9
    assert abs(e["error_frac"] - 0.1) < 1e-9
    # one-sided fields stay visible, no ratio
    m = rep["plans"]["tp2_pp1_m1"]["memory_gb"]
    assert m["measured"] is None and m["ratio"] is None
    assert rep["components"]["tpot_ms"]["suggested_scale"] == 1.1
    assert "memory_gb" not in rep["components"]


# ---------------------------------------------------------------------------
# null handle
# ---------------------------------------------------------------------------
def test_null_telemetry_is_noop():
    t = NULL_TELEMETRY
    assert not t.enabled
    with t.span("x", cat="y", anything=1):
        pass
    assert t.instant("x") == 0.0
    assert t.request_enqueued("r0", prompt_len=3) == 0.0
    t.batch_composition(1, 2, 3, 4, 5, 6)
    t.record_plan_prediction("p", tpot_ms=1.0)
    assert t.snapshot() == {} and t.export("/nonexistent") == {}


# ---------------------------------------------------------------------------
# overhead contract: bit-identity with telemetry on vs off
# ---------------------------------------------------------------------------
def test_serve_bit_identical_with_telemetry():
    prompts = [[3, 5, 7, 9, 11], [2, 4], [13, 6, 1]]
    im = make_im(max_seq=64)
    im.telemetry = NULL_TELEMETRY  # order-independence vs the im cache
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6))
    want = rm.generate(prompts)

    im = make_im(max_seq=64)  # same cached manager, re-initialized
    tel = Telemetry()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                        telemetry=tel)
    try:
        got = rm.generate(prompts)
    finally:
        im.telemetry = NULL_TELEMETRY
    assert got == want, "telemetry changed serve outputs"
    # and the handle actually observed the run
    snap = tel.metrics.snapshot()
    assert snap["requests_enqueued"] == 3
    assert snap["requests_finished"] == 3
    assert snap["ttft_s"]["count"] == 3
    assert snap["tpot_s"]["count"] == 3
    assert tel.trace.emitted > 0
    assert rm.requests[0].trace_id == "r00000"


def test_step_logits_bit_identical_with_telemetry():
    # the jitted step itself: logits_max / token_ids untouched by a handle
    from flexflow_tpu.serve.batch_config import BatchConfig

    im = make_im(max_seq=64)
    seq = np.zeros(im.max_requests, np.int32)
    seq[0] = 3
    bc = BatchConfig.build([3, 5, 7], [0, 0, 0], [0, 1, 2], seq,
                           max_tokens=im.max_tokens,
                           max_requests=im.max_requests)
    r0 = im.step(bc)
    want_tok = np.asarray(r0.token_ids).copy()
    want_lg = np.asarray(r0.logits_max).copy()

    im = make_im(max_seq=64)
    im.telemetry = Telemetry()
    bc = BatchConfig.build([3, 5, 7], [0, 0, 0], [0, 1, 2], seq,
                           max_tokens=im.max_tokens,
                           max_requests=im.max_requests)
    try:
        r1 = im.step(bc)
    finally:
        im.telemetry = NULL_TELEMETRY
    np.testing.assert_array_equal(np.asarray(r1.token_ids), want_tok)
    np.testing.assert_array_equal(np.asarray(r1.logits_max), want_lg)


def test_arrivals_bit_identical_with_telemetry():
    # telemetry's clock reads perturb a virtual clock's schedule; outputs
    # must still be invariant (continuous batching reorders work, never
    # results) and the records must carry the TTFT decomposition
    from test_serving_under_load import VirtualClock, poisson_arrivals

    rng = np.random.RandomState(7)
    arrivals = poisson_arrivals(rng, 5, rate_per_s=30.0,
                                vocab=TINY.vocab_size, max_new=4)
    im = make_im(max_seq=64, max_requests=2)
    im.telemetry = NULL_TELEMETRY
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4))
    recs0 = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    want = [recs0[rid]["tokens"] for rid in sorted(recs0)]

    im = make_im(max_seq=64, max_requests=2)
    clk = VirtualClock()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4),
                        telemetry=Telemetry(clock=clk))
    try:
        recs1 = rm.serve_with_arrivals(arrivals, clock=clk)
    finally:
        im.telemetry = NULL_TELEMETRY
    got = [recs1[rid]["tokens"] for rid in sorted(recs1)]
    assert got == want
    for rec in recs1.values():
        assert rec["trace_id"]
        # ttft decomposition: queue wait + prefill == host-visible ttft
        ttft = rec["first_token_s"] - rec["arrival_s"]
        assert abs(rec["queue_wait_s"] + rec["prefill_s"] - ttft) < 1e-9
        assert rec["prefill_s"] >= 0.0


# ---------------------------------------------------------------------------
# pipeline-parallel: per-stage spans + calibration report
# ---------------------------------------------------------------------------
def test_pp2_trace_stage_spans_and_calibration(tmp_path):
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.serve_search import pp_serve_cost

    from test_pp_serve import make_pp_im

    pim = make_pp_im({"pp": 2})
    tel = Telemetry()
    mm = MachineModel.for_mesh(pim.stage_meshes[0], spec_name="cpu")
    cost = pp_serve_cost(pim.stage_plans, mm, n_micro=pim.n_micro)
    tel.record_plan_prediction("tp1_pp2_m2", tpot_ms=cost["tpot_s"] * 1e3,
                               bubble_frac=cost["bubble_frac"])
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=4),
                        telemetry=tel)
    try:
        out = rm.generate([[3, 5, 7, 9], [11, 2]])
    finally:
        pim.telemetry = NULL_TELEMETRY
    assert all(len(o) == 4 for o in out)

    tpot = tel.metrics.snapshot()["tpot_s"]
    tel.record_plan_measured("tp1_pp2_m2", tpot_ms=tpot["p50"] * 1e3)

    # Perfetto export: stage0/stage1 tracks exist and both carry spans
    doc = tel.trace.to_chrome_json()
    tracks = {e["args"]["name"]: e["tid"] for e in doc["traceEvents"]
              if e["ph"] == "M"}
    assert {"stage0", "stage1"} <= set(tracks)
    for s in ("stage0", "stage1"):
        spans = [e for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["tid"] == tracks[s]
                 and e["name"] == "stage_dispatch"]
        assert spans, f"no dispatch spans on {s}"
    assert tel.metrics.snapshot()["pp_hops"] > 0

    # calibration report carries the predicted-vs-measured TPOT pair
    rep = tel.calibration.report()
    e = rep["plans"]["tp1_pp2_m2"]["tpot_ms"]
    assert e["predicted"] is not None and e["measured"] is not None
    assert e["error_frac"] is not None

    # full export + report round trip through the file
    paths = tel.export(str(tmp_path))
    summary = summarize_jsonl(paths["jsonl"])
    assert summary["requests"] == 2 and summary["completed"] == 2
    assert "tp1_pp2_m2" in summary["prediction_error"]
    assert any(k.startswith("stage") for k in summary["span_ms_by_track"])


# ---------------------------------------------------------------------------
# schema consistency: no emitter can bypass trace_report --check (ISSUE 8)
# ---------------------------------------------------------------------------
def test_every_emitted_typed_event_is_in_event_schema():
    """Grep-based CI gate: every typed instant (cat request/dispatch/plan)
    emitted anywhere in flexflow_tpu/ must appear
    in ``telemetry.EVENT_SCHEMA`` — new instrumentation that skips the
    schema would silently dodge ``trace_report.py --check``."""
    import os
    import re

    from flexflow_tpu.obs.telemetry import EVENT_SCHEMA

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # name + the cat right after it, positional or keyword, across lines
    pat = re.compile(
        r"""\.instant\(\s*["'](\w+)["']\s*,\s*(?:cat\s*=\s*)?["'](\w+)["']""",
        re.S)
    sources = []
    for root, _dirs, files in os.walk(os.path.join(repo, "flexflow_tpu")):
        sources += [os.path.join(root, f) for f in files
                    if f.endswith(".py")]
    emitted = set()
    for path in sources:
        with open(path) as f:
            for name, cat in pat.findall(f.read()):
                if cat in ("request", "dispatch", "plan", "fleet", "slo",
                           "replay"):
                    emitted.add((name, cat))
    assert emitted, "grep found no typed emitters — the pattern broke"
    unknown = {(n, c) for n, c in emitted
               if EVENT_SCHEMA.get(n) is None or EVENT_SCHEMA[n][0] != c}
    assert not unknown, (
        f"typed events emitted but missing from EVENT_SCHEMA: {unknown}")
    # and the vocabulary this PR added is actually reachable
    assert ("memory_pressure", "plan") in emitted
    # fleet serving (serve/fleet.py): the replica health vocabulary
    assert ("replica_dead", "fleet") in emitted
    assert ("request_failed_over", "request") in emitted
    # SLO-class lanes + brownout (serve/slo.py): the new "slo" category
    assert ("brownout_level_changed", "slo") in emitted
    assert ("lane_shed", "slo") in emitted
    # time-travel serving (obs/replay.py): the "replay" category
    assert ("trace_recorded", "replay") in emitted
    assert ("replay_completed", "replay") in emitted
    assert ("replay_mismatch", "replay") in emitted


# ---------------------------------------------------------------------------
# one vocabulary on the profiler's clock (ISSUE 27): the scheduler's spans
# reach a jax.profiler session with NO handle, and the device's operations
# carry the graph node that made them
# ---------------------------------------------------------------------------
_ARRIVALS = [(0.0, [1, 2, 3, 4, 5], 9), (0.0, [3, 4, 5], 6),
             (0.01, [7, 8, 9, 10], 5)]


def _serve_toy(telemetry=None, profiler=None, trace_dir=None):
    """The toy arrival loop once; under a ``jax.profiler`` session when
    ``trace_dir`` is given (host tracer level 1, as benchmark/run.py)."""
    from flexflow_tpu.obs import NULL_PROFILER

    im = make_im(max_tokens=16, max_requests=2, max_seq=64)
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=telemetry, profiler=profiler)
    try:
        if trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            recs = rm.serve_with_arrivals(list(_ARRIVALS))
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    finally:
        im.telemetry, im.profiler = NULL_TELEMETRY, NULL_PROFILER
    return [recs[rid]["tokens"] for rid in sorted(recs)]


def test_profiler_session_sees_scheduler_spans_without_a_handle(tmp_path):
    from benchmark import trace_reduce, xplane_spans

    want = _serve_toy()
    got = _serve_toy(trace_dir=tmp_path)
    # a session attached or not: the same tokens
    assert got == want and [len(t) for t in got] == [9, 6, 5]
    trace = xplane_spans.load(trace_reduce.find_xplane(str(tmp_path)))
    spans = xplane_spans.program_spans(trace)
    by_name = {}
    for h in spans:
        by_name.setdefault(h.name, []).append(h)
    assert {"decode_stretch", "serve_step", "host_admit", "host_prepare",
            "readback", "commit", "loop_arrivals",
            "loop_bookkeep"} <= set(by_name)
    # the loop's reads of its caller's clock are a span of their own
    # (ISSUE 46; not in the yardstick's closed list, so read off the plane)
    clocks = [h for h in trace.host_spans() if h.name == "loop_clock"]
    arrivals = by_name["loop_arrivals"]
    assert len(clocks) >= 2 * len(arrivals)
    assert all(any(a.start_ns <= c.start_ns < a.start_ns + a.dur_ns
                   for c in clocks) for a in arrivals)
    # the wait for a readback's last result is a span of its own INSIDE
    # the ``readback`` (ISSUE 59; not in the closed list either, so the
    # yardstick's idle table keeps charging the time to ``readback``)
    waits = [h for h in trace.host_spans() if h.name == "device_wait"]
    assert len(waits) == len(by_name["readback"]) > 0
    assert "device_wait" not in by_name
    assert all(any(r.start_ns <= w.start_ns and w.start_ns + w.dur_ns
                   <= r.start_ns + r.dur_ns for r in by_name["readback"])
               for w in waits)
    # every tick carries the perf_counter reading that links the clocks
    for tick in by_name["decode_stretch"] + by_name["serve_step"]:
        assert tick.args["pc_ns"] > 0
    # counts at the launch, as ints the scheduler held
    scans = by_name["decode_scan_dispatch"]
    assert all(h.args["kind"] == "decode_scan" and h.args["rows"] >= 1
               and h.args["n_steps"] >= 2 and h.args["ctx_sum"] > 0
               for h in scans)
    steps = by_name["step_dispatch"]
    assert sum(h.args["prompt_tokens"] for h in steps) == 5 + 3 + 4
    # prompts of 5 and 3 tokens fed from position 0: 15 + 6 contexts
    assert steps[0].args["prompt_ctx_sum"] == 21
    # the commit spans say which program made every token returned
    made = {k: xplane_spans.committed_tokens(trace, [k])
            for k in xplane_spans.TOKENS}
    assert sum(made.values()) == sum(len(t) for t in got)
    assert made["step_tokens"] == 3 and made["scan_tokens"] == 17
    # spans nest: a launch lies inside its tick
    tick = by_name["decode_stretch"][0]
    assert any(tick.start_ns <= h.start_ns
               and h.start_ns + h.dur_ns <= tick.start_ns + tick.dur_ns
               for h in scans)


def test_outputs_identical_with_session_telemetry_or_profiler(tmp_path):
    from flexflow_tpu.obs import StepProfiler

    want = _serve_toy()
    tel, prof = Telemetry(), StepProfiler()
    assert _serve_toy(telemetry=tel, profiler=prof,
                      trace_dir=tmp_path) == want
    # the ring holds the same spans the session saw, arguments included
    ring = {e["name"]: e for e in tel.trace.trace_events() if e["ph"] == "X"}
    assert {"decode_stretch", "decode_scan_dispatch", "commit",
            "host_prepare"} <= set(ring)
    assert ring["decode_scan_dispatch"]["args"]["kind"] == "decode_scan"
    assert "scan_tokens" in ring["commit"]["args"]
    # one ``with`` per boundary feeds the profiler too: the old phases,
    # the same meaning; a launch span is one counted dispatch; the spans
    # this issue added (commit, loop_*) are no phases
    assert {"dispatch", "host_admit", "host_prepare", "readback"} \
        == set(prof.phase_s)
    assert prof.work["dispatches"] == prof.phase_counts["dispatch"] > 0
    assert prof.work["host_syncs"] == prof.phase_counts["readback"]


def test_build_log_files_a_program_s_three_builds_under_one_name():
    """What jax 0.9.0 reports of a program that is built — its trace, its
    lowering, its backend compile — is in the journal's build log under
    the function's own name (obs/journal.py, ISSUE 59)."""
    import jax.numpy as jnp

    from flexflow_tpu.obs import journal

    assert sorted(journal.BUILD_WHATS.values()) == [
        "compile", "lower", "trace"]
    assert all(e.startswith("/jax/core/compile/") and e.endswith("_duration")
               for e in journal.BUILD_WHATS)
    log = journal.build_log()
    n0 = log.emitted

    def _a_program_of_test_obs(x):
        return x * 3 + 1

    fn = jax.jit(_a_program_of_test_obs)
    fn(jnp.ones(7))
    mine = [b for b in log.since(n0)
            if b.fun_name == "_a_program_of_test_obs"]
    assert [b.what for b in mine] == ["trace", "lower", "compile"]
    assert all(b.dur_ns > 0 and b.t_ns > 0 for b in mine)
    # ... and nothing else: the ``multiply`` and ``add`` it traced on the
    # way are inside its own trace, not events
    assert journal.builds(after_ns=mine[0].t_ns - mine[0].dur_ns,
                          before_ns=mine[-1].t_ns) == mine
    assert journal.builds(programs=["_a_program_of_test_obs"]) == mine
    # steady state: nothing is built, nothing is logged
    n1 = log.emitted
    fn(jnp.ones(7))
    assert log.emitted == n1
    # a new shape is a new build of the same program
    fn(jnp.ones(9))
    again = journal.builds(programs=["_a_program_of_test_obs"],
                           after_ns=mine[-1].t_ns + 1)
    assert [b.what for b in again] == ["trace", "lower", "compile"]


def test_lowered_programs_carry_node_and_stage_scopes():
    import re

    from flexflow_tpu.serve.batch_config import BatchConfig

    im = make_im()
    bc = BatchConfig.build([1, 2], [0, 1], [3, 4],
                           np.array([4, 5], np.int32), max_tokens=16,
                           max_requests=2)

    def scopes(fn, *args, **kw):
        """The ``op_name`` of every instruction of the compiled HLO: what
        a device trace shows as an event's ``tf_op``."""
        hlo = jax.jit(fn, static_argnames=tuple(kw)).lower(
            *args, **kw).compile().as_text()
        return set(re.findall(r'op_name="([^"]*)"', hlo))

    step = scopes(im._step_impl, im.params, im.state, bc)
    scan = scopes(im._decode_scan_impl, im.params, im.state, bc, None, None,
                  None, n_steps=2, eos=None)
    attn = "IncMultiHeadSelfAttention.model.layers.0.self_attn"
    for names in (step, scan):
        assert any(f"{attn}/attend/kv_write/" in n for n in names)
        assert any(f"{attn}/qkv_proj/" in n for n in names)
        assert any(f"{attn}/o_proj/" in n for n in names)
        assert any("/Linear.lm_head/" in n for n in names)
        assert any("/sample/" in n for n in names)
        # nothing a node lowers escapes its scope: every dot_general sits
        # under a node
        assert all(re.search(r"/[A-Z]\w*\.[\w.]+/", n) for n in names
                   if n.endswith("dot_general"))
    assert any("/advance/" in n for n in scan)
    assert not any("/advance/" in n for n in step)
