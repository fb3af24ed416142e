"""Step-level cost attribution tests (obs/profiler.py).

Three contracts:

* **bit-identity** — serve outputs are EXACTLY the same with the
  StepProfiler on or off, across the whole serving matrix (step,
  generate, arrivals, pp2, int8 KV, paged KV, speculative serving, and
  across a live plan migration) — the profiler is host-side only.
* **deterministic counters** — the work counters are pure functions of
  the workload and the compiled plan, cross-checked here against the
  independent ``Linear.flops``/``_step_flops``/``plan_memory_parts``/
  ``bytes_per_token`` arithmetic they must agree with.
* **perf guards** — zero steady-state jit recompiles (decode stretches,
  micro-batch population changes that hit the same padded program, a
  spec<->plain flip) and exactly ONE host sync per multi-step decode
  stretch (the r7 "never host-syncs" claim, now a pinned counter).
"""

import numpy as np
import pytest

import jax

from flexflow_tpu.obs import (
    NULL_PROFILER,
    NULL_TELEMETRY,
    StepProfiler,
    Telemetry,
)
from flexflow_tpu.obs.profiler import plan_cost_card
from flexflow_tpu.serve import GenerationConfig, RequestManager

from test_serve import TINY, make_im

PROMPTS = [[3, 5, 7, 9, 11], [2, 4], [13, 6, 1]]


# ---------------------------------------------------------------------------
# bit-identity matrix: profiler on vs off
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "kv_dtype,kv_page_size",
    [(None, None), ("int8", None), pytest.param(None, 16, marks=pytest.mark.paged)],
    ids=["plain", "int8", "paged"])
def test_generate_bit_identical_with_profiler(kv_dtype, kv_page_size):
    im = make_im(max_seq=64, kv_dtype=kv_dtype, kv_page_size=kv_page_size)
    im.profiler = NULL_PROFILER
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6))
    want = rm.generate(PROMPTS)

    im = make_im(max_seq=64, kv_dtype=kv_dtype, kv_page_size=kv_page_size)
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                        profiler=prof)
    try:
        got = rm.generate(PROMPTS)
    finally:
        im.profiler = NULL_PROFILER
    assert got == want, "profiler changed serve outputs"
    # ...and the handle actually observed the run
    assert prof.ticks > 0
    assert prof.work["flops"] > 0
    assert prof.work["dispatches"] > 0
    assert prof.work["kv_bytes_touched"] > 0
    assert prof.work["host_syncs"] > 0
    assert len(prof.per_request) == len(PROMPTS)
    if kv_page_size:
        assert prof.work["pages_mapped"] > 0


def test_step_logits_bit_identical_with_profiler():
    from flexflow_tpu.serve.batch_config import BatchConfig

    im = make_im(max_seq=64)
    im.profiler = NULL_PROFILER
    seq = np.zeros(im.max_requests, np.int32)
    seq[0] = 3
    bc = BatchConfig.build([3, 5, 7], [0, 0, 0], [0, 1, 2], seq,
                           max_tokens=im.max_tokens,
                           max_requests=im.max_requests)
    r0 = im.step(bc)
    want_tok = np.asarray(r0.token_ids).copy()
    want_lg = np.asarray(r0.logits_max).copy()

    im = make_im(max_seq=64)
    im.profiler = prof = StepProfiler()
    bc = BatchConfig.build([3, 5, 7], [0, 0, 0], [0, 1, 2], seq,
                           max_tokens=im.max_tokens,
                           max_requests=im.max_requests)
    try:
        r1 = im.step(bc)
    finally:
        im.profiler = NULL_PROFILER
    np.testing.assert_array_equal(np.asarray(r1.token_ids), want_tok)
    np.testing.assert_array_equal(np.asarray(r1.logits_max), want_lg)
    assert prof.work["dispatches"] == 1  # the direct-step launch counted


def test_arrivals_bit_identical_and_records_carry_work():
    from flexflow_tpu.obs.report import under_load_summary

    from test_serving_under_load import VirtualClock, poisson_arrivals

    rng = np.random.RandomState(7)
    arrivals = poisson_arrivals(rng, 5, rate_per_s=30.0,
                                vocab=TINY.vocab_size, max_new=4)
    im = make_im(max_seq=64, max_requests=2)
    im.profiler = NULL_PROFILER
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4))
    recs0 = rm.serve_with_arrivals(list(arrivals), clock=VirtualClock())
    want = [recs0[rid]["tokens"] for rid in sorted(recs0)]

    im = make_im(max_seq=64, max_requests=2)
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4),
                        profiler=prof)
    recs1 = rm.serve_with_arrivals(list(arrivals), clock=VirtualClock())
    got = [recs1[rid]["tokens"] for rid in sorted(recs1)]
    assert got == want
    # satellite: every record carries the deterministic per-request work
    # counters, and the under-load reduction totals them
    for rec in recs1.values():
        assert set(rec["work"]) == {"flops", "kv_bytes_touched",
                                    "dispatches"}
        assert rec["work"]["flops"] > 0
    summ = under_load_summary(recs1)
    assert summ["work"]["flops"] == pytest.approx(
        sum(r["work"]["flops"] for r in recs1.values()))
    assert summ["work"]["dispatches"] > 0
    # the profiler-off reduction has no work section (no fake zeros)
    assert "work" not in under_load_summary(recs0)


def test_pp2_bit_identical_with_profiler():
    from test_pp_serve import make_pp_im

    pim = make_pp_im({"pp": 2})
    pim.profiler = NULL_PROFILER
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=4))
    want = rm.generate([[3, 5, 7, 9], [11, 2]])

    pim = make_pp_im({"pp": 2})
    prof = StepProfiler()
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=4),
                        profiler=prof)
    try:
        got = rm.generate([[3, 5, 7, 9], [11, 2]])
    finally:
        pim.profiler = NULL_PROFILER
    assert got == want
    # per-stage dispatch phases + the hop phase were timed, and every
    # stage program launch counted into the deterministic dispatch count
    assert "stage0" in prof.phase_s and "stage1" in prof.phase_s
    assert "hop" in prof.phase_s
    assert prof.work["dispatches"] > 0


def test_spec_bit_identical_with_profiler():
    from flexflow_tpu.serve import SpecInferManager

    from test_spec_infer import TINY_SSM

    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]

    def rig():
        llm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8)
        ssm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                      cfg=TINY_SSM, topk=2, seed=123)
        return llm, ssm

    llm, ssm = rig()
    llm.profiler = ssm.profiler = NULL_PROFILER
    sm = SpecInferManager(llm, ssm, GenerationConfig(max_new_tokens=8),
                          width=2, depth=3)
    want = sm.generate(prompts)

    llm, ssm = rig()
    prof = StepProfiler()
    sm = SpecInferManager(llm, ssm, GenerationConfig(max_new_tokens=8),
                          width=2, depth=3, profiler=prof)
    try:
        got = sm.generate(prompts)
    finally:
        llm.profiler = ssm.profiler = NULL_PROFILER
    assert got == want
    # both deployments' work accumulated under one handle
    assert prof.work["flops"] > 0
    assert prof.work["dispatches"] > 0
    assert prof.ticks > 0


@pytest.mark.migration
def test_migration_bit_identical_with_profiler():
    """The profiler handle crosses a live plan switch like telemetry:
    rids are preserved, so one attribution table spans managers, and the
    successor's tokens stay bit-identical to the unmigrated run."""
    from flexflow_tpu.serve import MigrationConfig, MigrationController

    gen = GenerationConfig(max_new_tokens=8)
    im = make_im(max_seq=64)
    im.profiler = NULL_PROFILER
    want = RequestManager(im, gen).generate(PROMPTS)

    im = make_im(max_seq=64)
    prof = StepProfiler()
    rm = RequestManager(im, gen, profiler=prof)
    rm.scan_chunk = 2  # keep ticks small so the switch lands mid-decode
    ctrl = MigrationController(
        rm,
        build_manager=lambda cand: make_im(max_seq=64, kv_page_size=16),
        plan={"plan_key": "tp1_pp1_m1"},
        config=MigrationConfig(defer_ticks=1, drain_grace_ticks=1))
    ctrl.request_migration({"plan_key": "tp1_pp1_m1_paged"},
                           reasons=("test",))
    try:
        got = rm.generate(PROMPTS)
    finally:
        im.profiler = NULL_PROFILER
        ctrl.rm.im.profiler = NULL_PROFILER
    assert got == want, "tokens diverged across the profiled switch"
    # the successor carries the SAME handle and kept accumulating
    assert ctrl.rm is not rm
    assert ctrl.rm.profiler is prof
    assert prof.work["pages_mapped"] > 0  # successor's paged work counted
    assert len(prof.per_request) == len(PROMPTS)


# ---------------------------------------------------------------------------
# counter arithmetic: cross-check against the search's own cost model
# ---------------------------------------------------------------------------
def test_counter_arithmetic_matches_plan_cost_model():
    """The deterministic counters must equal the reference arithmetic:
    per-token flops from ``_step_flops`` (i.e. ``Linear.flops`` + the
    attention op's flops, shard-scaled), KV bytes from the allocator's
    ``bytes_per_token``, weight bytes from ``_step_param_bytes`` — the
    documented accounting model applied to this run's host bookkeeping."""
    from flexflow_tpu.search.simulator import (
        HEAVY_OPS,
        _step_flops,
        _step_param_bytes,
        plan_memory_parts,
    )

    # max_seq 128 = the cache lane-pad quantum, so bytes_per_token * R * S
    # equals the full buffer bytes and the plan's kv_state reconciles
    im = make_im(max_seq=128)
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4),
                        profiler=prof)
    out = rm.generate([[3, 5, 7, 9]])
    assert len(out[0]) == 4

    # ---- reference per-token flops (independent walk of the plan) ----
    rows = im.max_tokens
    attn = mlp = lm = 0.0
    lm_rows = 0
    wbytes = 0.0
    for step in im.plan.steps:
        if step.is_parallel:
            continue
        op = step.node.op
        wbytes += _step_param_bytes(step, im.plan, im.plan.mesh)
        if op.type_name not in HEAVY_OPS:
            continue
        fl = _step_flops(step, im.plan.mesh)
        if op.type_name.endswith("multihead_self_attention"):
            attn += fl
        elif getattr(op, "cost_logit_rows", None) is not None:
            lm += fl
            lm_rows = min(rows, op.cost_logit_rows)
        else:
            mlp += fl

    # the run's host bookkeeping: prefill feeds 4 tokens (one flat
    # chunk), the first decode stretch runs 2 steps (power-of-two cap of
    # the 3 remaining tokens), the last token is a single mixed step
    tokens_fed = 4 + 2 + 1
    expected_flops = (tokens_fed * (attn + mlp) / rows
                      + tokens_fed * lm / lm_rows)
    assert prof.work["flops"] == pytest.approx(expected_flops, rel=1e-9)

    # ---- KV bytes: logical positions priced at the allocator's rate ----
    bpt = im.kv.bytes_per_token()
    writes = tokens_fed
    # reads: prefill chunk reads its 4-deep prefix; the 2-step stretch
    # starts at depth 5 (2*5 + 1); the final step reads depth 7
    reads = 4 + (2 * 5 + 1) + 7
    assert prof.work["hbm_bytes_written"] == pytest.approx(writes * bpt)
    assert prof.work["kv_bytes_touched"] == pytest.approx(
        (writes + reads) * bpt)

    # weight stream: one pass for the prefill chunk, two for the scan
    # steps, one for the final step
    passes = 1 + 2 + 1
    assert prof.work["hbm_bytes_read"] == pytest.approx(
        passes * wbytes + reads * bpt)

    # the allocator's byte price reconciles with plan_memory_parts'
    # kv_state at the pad-aligned shape (same contract the memory
    # ledger's dry-run pins)
    parts = plan_memory_parts(im.plan, training=False)
    cap_bytes = bpt * im.max_requests * im.max_seq_len
    assert cap_bytes == pytest.approx(parts["kv_state"], rel=0.02)

    # the card the profiler actually used is the same arithmetic
    card = plan_cost_card(im)
    assert card.attn_flops_per_token == pytest.approx(attn / rows)
    assert card.mlp_flops_per_token == pytest.approx(mlp / rows)
    assert card.lm_head_flops_per_row == pytest.approx(lm / lm_rows)
    assert card.weight_bytes == pytest.approx(wbytes)
    assert card.kv_bytes_per_token == pytest.approx(bpt)

    # per-request attribution sums to the totals for a 1-request run
    req = prof.request_work(0)
    assert req["flops"] == pytest.approx(prof.work["flops"])
    assert req["kv_bytes_touched"] == pytest.approx(
        prof.work["kv_bytes_touched"])
    assert req["dispatches"] == passes


def test_counters_are_deterministic_across_runs():
    """Two identical sessions produce bit-identical work counters — the
    property ``obs.report.compare``'s exact counter diff rests on."""
    def run():
        im = make_im(max_seq=64)
        prof = StepProfiler()
        rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                            profiler=prof)
        rm.generate(PROMPTS)
        w = dict(prof.work)
        w.pop("recompiles_total")  # cache-warmth-relative, not workload
        return w

    assert run() == run()


# ---------------------------------------------------------------------------
# recompile guard (satellite): zero steady-state jit cache misses
# ---------------------------------------------------------------------------
def test_zero_steady_state_recompiles_decode():
    im = make_im(max_seq=64)
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                        profiler=prof)
    rm.generate(PROMPTS)          # warm every program this workload uses
    before = prof.work["recompiles_total"]
    rm2 = RequestManager(im, GenerationConfig(max_new_tokens=6),
                         profiler=prof)
    rm2.generate([[9, 1, 2], [6, 4], [33, 20, 5]])  # same shapes
    assert prof.work["recompiles_total"] == before, \
        "steady-state decode recompiled a jitted program"


def test_recompiles_are_the_build_log_s_compiles_of_the_programs():
    """The guard's count is not blind: a deployment that IS built counts
    a compile for each program its workload ran, under the program's name
    in the journal's build log (ISSUE 59: no poll of ``_cache_size()``),
    and nothing for a program that is not the deployment's."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.obs import journal

    im = make_im(max_seq=72)   # a deployment no other test has built
    prof = StepProfiler()
    n0 = journal.build_log().emitted
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6),
                        profiler=prof)
    try:
        assert prof.recompiles() == 0
        rm.generate(PROMPTS)
    finally:
        im.profiler = NULL_PROFILER
    built = prof.recompiles()
    compiles = [b for b in journal.build_log().since(n0)
                if b.what == "compile" and b.fun_name in prof._programs]
    assert built == len(compiles) == prof.work["recompiles_total"]
    assert {"_step_impl", "_decode_scan_impl"} <= {
        b.fun_name for b in compiles}
    jax.jit(lambda x: x + 59)(jnp.ones(5))
    assert prof.recompiles() == built


def test_zero_recompiles_pp_microbatch_population_change():
    """A pp decode with fewer live requests pads to the SAME micro-batch
    shapes — serving 1 request after 2 must hit the compiled programs."""
    from test_pp_serve import make_pp_im

    pim = make_pp_im({"pp": 2})
    prof = StepProfiler()
    rm = RequestManager(pim, GenerationConfig(max_new_tokens=4),
                        profiler=prof)
    try:
        rm.generate([[3, 5, 7, 9], [11, 2]])
        # fresh serving session: caches re-allocate (the guard pins the
        # POPULATION change; reusing the prior session's donated output
        # buffers as inputs is a layout-keyed cache miss on XLA:CPU the
        # guard itself surfaced — real sessions start from allocate())
        pim.reset()
        before = prof.work["recompiles_total"]
        rm2 = RequestManager(pim, GenerationConfig(max_new_tokens=4),
                             profiler=prof)
        rm2.generate([[8, 6, 4, 2]])   # one request: same padded shapes
    finally:
        pim.profiler = NULL_PROFILER
    assert prof.work["recompiles_total"] == before, \
        "a micro-batch population change recompiled a stage program"


@pytest.mark.spec
def test_zero_recompiles_spec_plain_flip():
    """Serving the same shapes spec -> plain -> spec -> plain must
    compile each path once: the flip itself may never trigger a silent
    steady-state recompile."""
    from flexflow_tpu.serve import SpecInferManager

    from test_spec_infer import TINY_SSM

    llm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8)
    ssm = make_im(max_tokens=32, max_requests=2, max_seq=64, max_spec=8,
                  cfg=TINY_SSM, topk=2, seed=123)
    prof = StepProfiler()
    prompts = [[3, 11, 25, 40, 7], [2, 4, 6, 8]]

    def serve(spec):
        llm.reset()
        ssm.reset()
        sm = SpecInferManager(llm, ssm, GenerationConfig(max_new_tokens=6),
                              width=2, depth=3, profiler=prof)
        rids = [sm.register_new_request(p, spec=spec) for p in prompts]
        sm._serve()
        return rids

    try:
        serve(True)    # warm the speculative macro-step path
        serve(False)   # warm the incremental fast path
        before = prof.work["recompiles_total"]
        serve(True)
        serve(False)
    finally:
        llm.profiler = ssm.profiler = NULL_PROFILER
    assert prof.work["recompiles_total"] == before, \
        "a spec<->plain flip recompiled a jitted program"


# ---------------------------------------------------------------------------
# host-sync guard (satellite): multi-step decode syncs exactly once
# ---------------------------------------------------------------------------
def test_decode_stretch_performs_exactly_one_host_sync():
    im = make_im(max_seq=64)
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=10),
                        profiler=prof)
    rm.register_new_request([3, 5, 7])
    saw_stretch = False
    while rm.has_work():
        syncs0 = prof.work["host_syncs"]
        scans0, steps0 = rm.scan_runs, rm.steps
        rm._serve_tick()
        if rm.scan_runs == scans0 + 1 and rm.steps - steps0 > 1:
            saw_stretch = True
            n = rm.steps - steps0
            assert n > 1
            assert prof.work["host_syncs"] - syncs0 == 1, (
                f"a {n}-step decode stretch performed "
                f"{prof.work['host_syncs'] - syncs0} host syncs "
                "(contract: only the final readback)")
    assert saw_stretch, "no multi-step decode stretch ran"


# ---------------------------------------------------------------------------
# per-component pricing decomposition (search side)
# ---------------------------------------------------------------------------
def test_pp_serve_cost_components_sum_to_tpot():
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.serve_search import pp_serve_cost

    from test_pp_serve import make_pp_im

    pim = make_pp_im({"pp": 2})
    mm = MachineModel.for_mesh(pim.stage_meshes[0], spec_name="cpu")
    cost = pp_serve_cost(pim.stage_plans, mm, n_micro=2,
                         boundary_bytes=1e6)
    comps = cost["components"]
    assert set(comps) == {"attention_ms", "mlp_ms", "lm_head_ms",
                          "kv_stream_ms", "comms_ms", "hop_ms",
                          "host_overhead_ms"}
    assert sum(comps.values()) == pytest.approx(cost["tpot_s"] * 1e3,
                                                rel=1e-4)
    assert comps["hop_ms"] > 0  # pp2 with boundary bytes pays the hop

    # a component scale corrects ONLY its own term
    scaled = pp_serve_cost(pim.stage_plans, mm, n_micro=2,
                           boundary_bytes=1e6,
                           component_scales={"hop_ms": 2.5})
    assert scaled["components"]["hop_ms"] == pytest.approx(
        2.5 * comps["hop_ms"], rel=1e-4)
    for c in comps:
        if c != "hop_ms":
            assert scaled["components"][c] == pytest.approx(comps[c])
    assert scaled["tpot_s"] == pytest.approx(
        sum(scaled["components"].values()) / 1e3, rel=1e-4)


@pytest.mark.paged
def test_first_tick_page_activity_is_counted():
    """The paged counters baseline at install time, so pages mapped in
    the very FIRST tick (prefill — where most mapping happens) count;
    the profiler's cumulative view agrees exactly with the allocator's
    own counter over the profiled window."""
    im = make_im(max_seq=64, kv_page_size=16)
    base = im.kv.pages_mapped          # pre-existing history is excluded
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=2),
                        profiler=prof)
    rm.generate([[3, 5, 7]])
    assert prof.work["pages_mapped"] == im.kv.pages_mapped - base > 0


def test_profiler_uninstall_releases_retired_deployment():
    """A live migration retires the incumbent through
    ``profiler.uninstall``: the profiler holds nothing of it any more —
    its programs were only ever held by NAME, the names the build log
    counts compiles under (no unbounded growth across switches) — while
    the compiles it performed stay in the monotonic counter."""
    im = make_im(max_seq=64)
    prof = StepProfiler()
    RequestManager(im, GenerationConfig(max_new_tokens=2), profiler=prof)
    assert id(im) in prof._installed
    assert {"_step_impl", "_decode_scan_impl", "_prefill_scan_impl",
            "_join_impl"} <= prof._programs
    assert all(isinstance(name, str) for name in prof._programs)
    before = prof.recompiles()
    prof.uninstall(im)
    assert id(im) not in prof._installed and id(im) not in prof._cards
    assert prof.recompiles() == before  # counted, not lost
    im.profiler = NULL_PROFILER


def test_component_store_converges_to_true_scale_not_sqrt():
    """The ledger records the RAW (un-corrected) component decomposition
    (``components_raw``): across repeated calibrate-and-apply cycles the
    stored scale stays at the TRUE correction instead of EWMA-decaying
    toward sqrt(truth) — which is what recording the already-corrected
    prediction would cause."""
    from flexflow_tpu.obs import CalibrationLedger, CalibrationStore, StoreConfig
    from flexflow_tpu.search.machine_model import MachineModel
    from flexflow_tpu.search.serve_search import (
        pp_serve_cost,
        store_component_scales,
    )

    from test_pp_serve import make_pp_im

    pim = make_pp_im({"pp": 2})
    mm = MachineModel.for_mesh(pim.stage_meshes[0], spec_name="cpu")
    true_hop_scale = 2.5
    store = CalibrationStore("/tmp/unused_component_store.json",
                             StoreConfig(min_samples=2, ewma_alpha=0.5))

    def cycle():
        led = CalibrationLedger()
        scales = store_component_scales(store)
        for m in (1, 2):
            cost = pp_serve_cost(pim.stage_plans, mm, n_micro=m,
                                 boundary_bytes=1e6,
                                 component_scales=scales)
            # the search records the RAW decomposition as the prediction
            led.predict(f"m{m}", **cost["components_raw"])
            # "reality": the hop costs true_hop_scale x the raw model
            meas = dict(cost["components_raw"])
            meas["hop_ms"] *= true_hop_scale
            led.measure(f"m{m}", **meas)
        led.commit(store)

    cycle()
    assert store.scale_for("hop_ms") == pytest.approx(true_hop_scale)
    cycle()   # applied scales now active — the record must stay raw
    assert store.scale_for("hop_ms") == pytest.approx(true_hop_scale), \
        "stored scale decayed: the ledger recorded corrected predictions"
    # and the CORRECTED pricing really lands on reality
    cost = pp_serve_cost(pim.stage_plans, mm, n_micro=1,
                         boundary_bytes=1e6,
                         component_scales=store_component_scales(store))
    assert cost["components"]["hop_ms"] == pytest.approx(
        cost["components_raw"]["hop_ms"] * true_hop_scale)


def test_step_profile_instants_and_export(tmp_path):
    """Binding a Telemetry handle makes each tick emit a validated
    ``step_profile`` instant and the export carry the profile line +
    time-budget section."""
    from flexflow_tpu.obs.report import summarize_jsonl, validate_jsonl

    im = make_im(max_seq=64)
    tel = Telemetry()
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4),
                        telemetry=tel, profiler=prof)
    try:
        rm.generate([[3, 5, 7]])
    finally:
        # the cached im is shared with other test files in this worker
        # (test_serve.make_im): hand it back with the no-op handle, not None
        im.telemetry = NULL_TELEMETRY
        im.profiler = NULL_PROFILER
    assert tel.profiler is prof
    paths = tel.export(str(tmp_path))
    assert validate_jsonl(paths["jsonl"]) == []
    s = summarize_jsonl(paths["jsonl"])
    tb = s["time_budget"]
    assert tb is not None
    assert tb["ticks"] == prof.ticks
    assert tb["work"]["flops"] == prof.work["flops"]
    assert "dispatch" in tb["phases"]
    # the registry carries the recompile gauge
    assert tel.metrics.snapshot()["recompiles_total"] == \
        prof.work["recompiles_total"]


def test_null_profiler_is_noop():
    p = NULL_PROFILER
    assert not p.enabled
    with p.phase("x"):
        pass
    p.count("dispatches")
    p.host_sync()
    p.account(None, [(0, 1, 1)])
    p.tick_begin()
    p.tick_end()
    assert p.report() == {} and p.request_work(0) == {}
