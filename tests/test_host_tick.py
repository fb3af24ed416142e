"""Host-tick elimination: on-device continuous batching tests.

The decode engine (``RequestManager._decode_stretch``) fuses admission,
slot joins, and lifecycle exit into the device dispatch chain:
``decode_scan_async`` segments run back to back with no readback between
them, per-row ``allowed`` budgets freeze each slot ON DEVICE at its own
max-new (per-slot exit codes report why), and arrivals landing
mid-stretch splice into the running batch at a segment boundary via
``join_slot``.  The contract pinned
here: exactly ONE host sync per decode stretch, and token streams
bit-identical to the tick-paced flat per-step loop (``scan_chunk = 1``)
— greedy AND seeded — under the same Poisson arrival stream.
"""

import numpy as np

from flexflow_tpu.obs import NULL_TELEMETRY, StepProfiler, Telemetry
from flexflow_tpu.serve import GenerationConfig, RequestManager
from flexflow_tpu.serve.inference_manager import (
    EXIT_BUDGET,
    EXIT_EOS,
    EXIT_RUNNING,
)

from test_serve import TINY, make_im
from test_serving_under_load import VirtualClock, poisson_arrivals


def _sampled_stretches(rm, prof):
    """Wrap ``_decode_stretch`` to record exact host syncs / dispatches
    attributable to each decode stretch."""
    syncs, disp = [], []
    inner = rm._decode_stretch

    def wrapper(n):
        s0, d0 = prof.work["host_syncs"], prof.work["dispatches"]
        inner(n)
        syncs.append(prof.work["host_syncs"] - s0)
        disp.append(prof.work["dispatches"] - d0)

    rm._decode_stretch = wrapper
    return syncs, disp


def _serve_both(gen, arrivals):
    """Same arrival stream through the tick-paced reference (the flat
    per-step loop, ``scan_chunk = 1``) and the chained engine; returns
    (reference records, chained records, per-stretch sync counts,
    per-stretch dispatch counts, reference profiler, chained profiler)."""
    im = make_im(max_seq=64, max_requests=2)
    im.reset()
    prof_a = StepProfiler()
    rm_a = RequestManager(im, gen, profiler=prof_a)
    rm_a.scan_chunk = 1   # the tick-paced reference: one flat step a token
    rec_a = rm_a.serve_with_arrivals(list(arrivals), clock=VirtualClock())
    im.reset()
    prof_b = StepProfiler()
    rm_b = RequestManager(im, gen, profiler=prof_b)
    syncs, disp = _sampled_stretches(rm_b, prof_b)
    rec_b = rm_b.serve_with_arrivals(list(arrivals), clock=VirtualClock())
    return rec_a, rec_b, syncs, disp, prof_a, prof_b


def test_the_tick_paced_reference_runs_no_scan():
    # what every comparison against ``scan_chunk = 1`` compares with: the
    # flat per-step loop — no decode scan is launched, each token after a
    # request's first is one flat step of its own, and the tokens are the
    # default engine's
    prompt, n_new = [3, 11, 25, 40, 7], 9
    gen = GenerationConfig(max_new_tokens=n_new)
    im = make_im(max_seq=64, max_requests=2)
    want = RequestManager(im, gen).serve_with_arrivals(
        [(0.0, prompt, n_new)], clock=VirtualClock())
    im.reset()
    tel = Telemetry()
    rm = RequestManager(im, gen, telemetry=tel)
    rm.scan_chunk = 1
    try:
        got = rm.serve_with_arrivals([(0.0, prompt, n_new)],
                                     clock=VirtualClock())
    finally:
        im.telemetry = NULL_TELEMETRY
    assert [r["tokens"] for r in got.values()] \
        == [r["tokens"] for r in want.values()]
    assert rm.scan_runs == 0

    def spans(name):
        return [e["args"] for e in tel.trace.trace_events()
                if e["ph"] == "X" and e["name"] == name]

    assert spans("decode_scan_dispatch") == []
    decode_steps = [a for a in spans("step_dispatch")
                    if a["prompt_tokens"] == 0]
    assert len(decode_steps) == n_new - 1
    assert all(a["rows"] == 1 for a in decode_steps)


def test_quantum1_vs_unbounded_bit_identical_greedy():
    # THE acceptance pin: same Poisson stream, host-ticked per-step loop
    # vs unbounded chained stretches -> bit-identical per-request streams,
    # and every chained stretch costs exactly one host sync
    rng = np.random.RandomState(3)
    arrivals = poisson_arrivals(rng, 6, rate_per_s=40.0,
                                vocab=TINY.vocab_size)
    gen = GenerationConfig(max_new_tokens=6)
    rec_a, rec_b, syncs, disp, prof_a, prof_b = _serve_both(gen, arrivals)
    assert sorted(rec_a) == sorted(rec_b)
    for rid in rec_a:
        assert rec_a[rid]["tokens"] == rec_b[rid]["tokens"], \
            f"rid {rid} diverged between tick-paced and chained serving"
    assert syncs, "chained run never took the stretch path"
    assert all(s == 1 for s in syncs), \
        f"a stretch took more than one host sync: {syncs}"
    # each stretch's dispatches = its segments (+ any join prefills) —
    # always amortized strictly below one dispatch per emitted token
    assert all(d >= 1 for d in disp)
    assert prof_b.work["host_syncs"] < prof_a.work["host_syncs"]
    assert prof_b.work["dispatches"] < prof_a.work["dispatches"]


def test_quantum1_vs_unbounded_bit_identical_seeded():
    rng = np.random.RandomState(9)
    arrivals = poisson_arrivals(rng, 6, rate_per_s=40.0,
                                vocab=TINY.vocab_size)
    gen = GenerationConfig(max_new_tokens=6, temperature=0.8, top_p=0.9,
                           seed=11)
    rec_a, rec_b, syncs, _, _, _ = _serve_both(gen, arrivals)
    for rid in rec_a:
        assert rec_a[rid]["tokens"] == rec_b[rid]["tokens"], \
            f"rid {rid} diverged (seeded) between tick-paced and chained"
    assert syncs and all(s == 1 for s in syncs)


def test_midstretch_join_commits_and_matches_solo():
    # the join mechanism in isolation: a request REGISTERED mid-stretch
    # (via the arrival pump at a segment boundary) splices into the
    # running batch, its tokens commit in the stretch's single readback,
    # and its final stream equals serving it alone
    im = make_im(max_seq=64, max_requests=2)
    gen = GenerationConfig(max_new_tokens=10)
    P0, P1 = [3, 5, 7], [2, 4, 6, 8]
    im.reset()
    want0 = RequestManager(im, gen).generate([P0])[0]
    im.reset()
    want1 = RequestManager(im, gen).generate([P1])[0]
    im.reset()
    prof = StepProfiler()
    rm = RequestManager(im, gen, profiler=prof)
    r0 = rm.register_new_request(P0)
    while not rm.requests[r0].generated:
        rm._serve_tick()          # prefill + first token on the tick path
    joined = []

    def pump():
        if not joined:
            joined.append(rm.register_new_request(P1))

    rm._arrival_pump = pump
    n = rm._scan_steps_possible()
    assert n >= 2
    s0 = prof.work["host_syncs"]
    rm._decode_stretch(n)
    rm._arrival_pump = None
    assert prof.work["host_syncs"] - s0 == 1, \
        "the mid-stretch join forced an extra host sync"
    r1 = joined[0]
    got1 = rm.requests[r1].generated
    assert got1, "joined request committed nothing in the stretch"
    assert got1 == want1[:len(got1)]
    while rm.has_work():
        rm._serve_tick()
    assert rm.requests[r0].generated == want0
    assert rm.requests[r1].generated == want1


def test_exit_codes_budget_and_eos():
    # device-side lifecycle exit: the readback's per-slot exit codes say
    # WHY a row froze — max-new exhaustion vs EOS — with no host check
    # per token
    im = make_im(max_seq=64, max_requests=2)
    im.reset()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=5))
    toks = rm.generate([[3, 5, 7]])[0]
    assert len(toks) == 5
    # prefill emits token 0; the stretch covers the remaining 4 exactly,
    # so the device reports the budget exit
    assert list(rm.last_exit_codes.values()) == [EXIT_BUDGET]

    # EOS: re-serve greedily with eos set to a mid-stream token — the
    # device truncates after it and reports the EOS exit
    e = toks[2]
    im.reset()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=5,
                                             eos_token_id=e))
    toks2 = rm.generate([[3, 5, 7]])[0]
    assert toks2 == toks[:3]
    assert EXIT_EOS in rm.last_exit_codes.values()


def test_exit_code_running_when_scan_chunk_bounds():
    # a row that outlives the stretch (scan_chunk-bounded, budget left)
    # must read RUNNING, not BUDGET — the emission budget rides the
    # row's full remaining, not the segment cap
    im = make_im(max_seq=64, max_requests=2)
    im.reset()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=20))
    rm.scan_chunk = 8
    r0 = rm.register_new_request([3, 5, 7])
    while not rm.requests[r0].generated:
        rm._serve_tick()
    runs0 = rm.scan_runs
    while rm.scan_runs == runs0:
        rm._serve_tick()
    assert rm.last_exit_codes == {r0: EXIT_RUNNING}
    while rm.has_work():
        rm._serve_tick()
    assert len(rm.requests[r0].generated) == 20


def test_stretch_scheduling_stamped_into_step_profile():
    # S1: the chosen decode quantum and the stretch's realized shape
    # (total steps, segments, joins) land in the tick's step_profile
    im = make_im(max_seq=64, max_requests=2)
    im.reset()
    prof = StepProfiler()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=8),
                        profiler=prof)
    r0 = rm.register_new_request([3, 5, 7])
    while not rm.requests[r0].generated:
        prof.tick_begin()
        rm._serve_tick()
        prof.tick_end()
    runs0 = rm.scan_runs
    while rm.scan_runs == runs0:
        prof.tick_begin()
        rm._serve_tick()
        prof.tick_end()
    notes = prof.last_tick.get("notes")
    assert notes is not None
    assert notes["decode_quantum"] >= 2
    assert notes["stretch_segments"] >= 1
    assert notes["stretch_steps"] >= notes["stretch_segments"]
    assert notes["stretch_joins"] == 0


def test_mixed_budgets_ride_one_stretch():
    # rows of UNEQUAL remaining budgets share one stretch: the shorter
    # row exits ON DEVICE (frozen by its allowed mask) while the longer
    # row keeps decoding in later chained segments — one readback total
    im = make_im(max_seq=64, max_requests=2)
    gen = GenerationConfig(max_new_tokens=12)
    P0, P1 = [3, 5, 7], [2, 4, 6]
    im.reset()
    want0 = RequestManager(im, gen).generate([P0], max_new_tokens=4)[0]
    im.reset()
    want1 = RequestManager(im, gen).generate([P1], max_new_tokens=12)[0]
    im.reset()
    prof = StepProfiler()
    rm = RequestManager(im, gen, profiler=prof)
    r0 = rm.register_new_request(P0, 4)
    r1 = rm.register_new_request(P1, 12)
    syncs, disp = _sampled_stretches(rm, prof)
    while rm.has_work():
        rm._serve_tick()
    assert rm.requests[r0].generated == want0
    assert rm.requests[r1].generated == want1
    assert syncs and all(s == 1 for s in syncs)
    # at least one stretch chained multiple segments (the short row's
    # device-side exit did NOT end the stretch)
    assert max(disp) >= 2, f"no stretch chained segments: {disp}"
