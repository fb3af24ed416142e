"""Arrival-driven serving (open-loop load) tests.

The invariant that makes the ``serving_under_load`` bench meaningful:
continuous batching under arbitrary arrival timing only reorders WORK, never
RESULTS — every request's generated tokens equal what serving it alone
produces, whatever mix of admits/retires/scan-stretches its lifetime spans.
Hermetic small-shape variant of the bench path (Poisson arrivals into
``RequestManager.serve_with_arrivals``), virtual-clock driven so the
schedule itself is deterministic too.
"""

import numpy as np

from flexflow_tpu.serve import GenerationConfig, RequestManager

from test_serve import TINY, make_im, ref_greedy_decode


class VirtualClock:
    """Deterministic clock: advances a fixed tick per call, plus manual
    jumps — arrival timing becomes a pure function of the step count."""

    def __init__(self, tick=0.01):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def poisson_arrivals(rng, n, rate_per_s, vocab, plen=(3, 9), max_new=6):
    t = 0.0
    out = []
    for _ in range(n):
        t += rng.exponential(1.0 / rate_per_s)
        prompt = rng.randint(1, vocab - 1,
                             size=rng.randint(*plen)).tolist()
        out.append((t, prompt, max_new))
    return out


def test_arrival_driven_outputs_match_sequential():
    im = make_im(max_seq=64, max_requests=2)
    rng = np.random.RandomState(3)
    arrivals = poisson_arrivals(rng, 6, rate_per_s=20.0,
                                vocab=TINY.vocab_size)
    # sequential oracle: each prompt served ALONE on the same manager
    # (the satellite's exact invariant — arrival-driven admit/retire must
    # preserve per-request outputs vs sequential serving); one of them is
    # spot-checked against the independent full-context reference
    want = []
    for _, prompt, _ in arrivals:
        im.reset()
        solo = RequestManager(im, GenerationConfig(max_new_tokens=6))
        want.append(solo.generate([prompt])[0])
    assert want[0] == ref_greedy_decode(im.params, TINY,
                                        arrivals[0][1], 6)
    im.reset()
    rm = RequestManager(im, GenerationConfig(max_new_tokens=6))
    records = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    assert len(records) == 6
    got = [records[rid]["tokens"] for rid in sorted(records)]
    assert got == want, "outputs diverged under arrival-driven serving"


def test_arrival_records_are_complete_and_ordered():
    im = make_im(max_seq=64, max_requests=2)
    rng = np.random.RandomState(5)
    arrivals = poisson_arrivals(rng, 5, rate_per_s=50.0,
                                vocab=TINY.vocab_size, max_new=4)
    rm = RequestManager(im, GenerationConfig(max_new_tokens=4))
    records = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    for rec in records.values():
        assert rec["outcome"] == "ok"  # terminal outcome always emitted
        assert rec["arrival_s"] <= rec["admitted_s"]
        assert rec["admitted_s"] < rec["first_token_s"] <= rec["finish_s"]
        assert len(rec["tokens"]) == 4
        # TTFT decomposition (the queue-wait/prefill split): queue wait
        # ends at the start of the step that fed the first prompt token
        assert rec["trace_id"]
        ttft = rec["first_token_s"] - rec["arrival_s"]
        assert abs(rec["queue_wait_s"] + rec["prefill_s"] - ttft) < 1e-9
        assert 0.0 <= rec["queue_wait_s"] <= ttft
        assert rec["prefill_s"] >= 0.0
        assert rec["prefill_start_s"] >= rec["arrival_s"]
    # queueing visible: with 2 slots and 5 near-simultaneous arrivals,
    # later requests admit strictly later than the first two
    admits = sorted(r["admitted_s"] for r in records.values())
    assert admits[-1] > admits[0]
    # and the slot-starved requests' queue wait dominates the early ones'
    qw = [r["queue_wait_s"] for r in records.values()]
    assert max(qw) > min(qw)


def test_under_load_metrics_helper():
    # the record reduction (obs/report.py), hermetically
    from flexflow_tpu.obs.report import under_load_summary

    im = make_im(max_seq=64, max_requests=2)
    rng = np.random.RandomState(11)
    arrivals = poisson_arrivals(rng, 6, rate_per_s=30.0,
                                vocab=TINY.vocab_size, max_new=5)
    rm = RequestManager(im, GenerationConfig(max_new_tokens=5))
    records = rm.serve_with_arrivals(arrivals, clock=VirtualClock())
    m = under_load_summary(records)
    assert m["requests"] == 6 and m["completed"] == 6
    assert m["ttft_p50_ms"] <= m["ttft_p95_ms"]
    assert m["tpot_p50_ms"] <= m["tpot_p95_ms"]
    assert m["goodput_tokens_per_sec"] > 0
    # TTFT splits into queue wait + prefill
    assert m["queue_wait_p50_ms"] is not None
    assert m["queue_wait_p50_ms"] <= m["ttft_p50_ms"]
    assert m["prefill_p50_ms"] is not None
    assert m["outcomes"] == {"ok": 6}
