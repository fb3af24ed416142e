"""costs.py against hand-worked operations and bytes; the percentile; the
traffic generator's promises."""

import json
import os
import statistics

import pytest

from conftest import ROOT

from benchmark import costs, stats, traffic_gen


def test_decode_attention_cost_by_hand():
    # 2 rows, contexts 3 and 5, 4 query heads on 1 kv head, head size 8,
    # bf16: per position QK^T is 2*4*8 ops and PV another 2*4*8 -> 128
    ops, nbytes = costs.decode_attention_cost([3, 5], 4, 1, 8)
    assert ops == 128 * 8
    # K and V: 2 * 1 head * 8 * 2 bytes per position = 32 B x 8 positions;
    # q in and o out: 2 * 2 rows * 4 heads * 8 * 2 bytes = 256 B
    assert nbytes == 32 * 8 + 256


def test_prefill_attention_cost_by_hand():
    # 4 queries starting at position 2: they attend 3, 4, 5, 6 positions
    ops, nbytes = costs.prefill_attention_cost(4, 2, 4, 1, 8)
    assert ops == 4 * 4 * 8 * (3 + 4 + 5 + 6)
    # K and V of 6 positions (32 B each) + q and o of 4 queries (64 B each
    # way)
    assert nbytes == 32 * 6 + 2 * 4 * 4 * 8 * 2


def test_roofline_picks_the_binding_roof():
    peak = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.roofline_seconds(1000, 10, peak) == (10.0, "compute")
    assert costs.roofline_seconds(10, 1000, peak) == (100.0, "memory")


def test_percentile_is_nearest_rank():
    xs = list(range(1, 21))
    assert stats.percentile(xs, 0.95) == 20
    assert stats.percentile(xs, 0.5) == 11
    assert stats.percentile([], 0.95) is None
    vals = [10, 11, 12, 13, 14, 15]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == (q3 - q1) / 12.5


def _records(spans, outcome="ok"):
    """Serving records with the fields the pace reads: ``(first token's
    stamp, last token's stamp, tokens)`` each."""
    return [{"outcome": outcome, "first_token_s": a, "finish_s": b,
             "tokens": [7] * n} for a, b, n in spans]


# ten requests of 16 tokens, stamps at the ends of 0.3 s stretches: four end
# in the stretch they joined (a pace of exactly 0), one needs one stretch
# more, five need two
TEN = [(1.0, 1.0, 16)] * 4 + [(1.0, 1.3, 16)] + [(1.0, 1.6, 16)] * 5


def test_pooled_pace_moves_by_a_request_s_share_where_p50_jumps():
    spans = stats.decode_spans(_records(TEN))
    assert len(spans) == 10 and stats.zero_pace_share(spans) == 0.4
    pooled = stats.pooled_pace(spans)
    assert pooled == pytest.approx((0.3 + 5 * 0.6) / 150)
    p50 = lambda sp: stats.percentile([s / n for s, n in sp], 0.5)
    assert p50(spans) == pytest.approx(0.04)
    # one request gets away with a stretch less: the median, which has two
    # values to choose from, halves; the pooled pace moves by that
    # request's share of all the waiting, a stretch over 150 tokens
    flipped = stats.decode_spans(_records(TEN[:5] + [(1.0, 1.3, 16)]
                                          + TEN[6:]))
    assert p50(flipped) == pytest.approx(0.02)
    assert stats.pooled_pace(flipped) == pytest.approx(pooled - 0.3 / 150)
    # weighted by tokens: a long answer carries it
    assert stats.pooled_pace([(0.0, 1), (1.0, 99)]) == 0.01


def test_pooled_pace_leaves_out_what_has_no_pace_and_reports_no_zero():
    # one token has no pace; a request that failed has no finish stamp
    odd = _records([(1.0, 1.0, 1)]) + _records([(1.0, 9.0, 16)], "cancelled")
    odd[-1].pop("finish_s")
    assert stats.decode_spans(_records(TEN) + odd) == stats.decode_spans(
        _records(TEN))
    assert stats.decode_spans(odd) == []
    assert stats.pooled_pace([]) is None
    assert stats.zero_pace_share([]) is None


def test_run_py_reports_the_pooled_pace_the_whole_time_or_nothing():
    import types

    from benchmark import run

    clock = types.SimpleNamespace(closed=None)
    mix = {"loop": "open", "reports": ["tpot_pooled_ms", "latency_mean_ms"]}
    records = {i: dict(r, arrival_s=0.5)
               for i, r in enumerate(_records(TEN))}
    out, counts = run.end_to_end(mix, records, clock, 2.0)
    assert out == {"tpot_pooled_ms": (pytest.approx(22.0), "ms"),
                   "latency_mean_ms": (pytest.approx(830.0), "ms")}
    assert counts["zero-pace share"] == 0.4 and counts["tpot samples"] == 10
    assert counts["tpot ms"]["p50_ms"] == 40.0
    assert counts["latency ms"]["mean_ms"] == pytest.approx(830.0)
    # a request that failed waited until the loop ended (2.0 s here)
    records[10] = {"outcome": "cancelled", "arrival_s": 0.9, "tokens": []}
    out, _ = run.end_to_end(mix, records, clock, 2.0)
    assert out["latency_mean_ms"][0] == pytest.approx(
        (10 * 830.0 + 1100.0) / 11)
    # an empty denominator: the metric is left out, not reported as 0
    single = {0: dict(_records([(1.0, 1.0, 1)])[0], arrival_s=0.5)}
    out, counts = run.end_to_end(mix, single, clock, 2.0)
    assert set(out) == {"latency_mean_ms"}
    assert "zero-pace share" not in counts


def test_the_pooled_pace_reader_leaves_out_what_the_tracer_delayed():
    import types

    from benchmark import run

    reader = run.load_module(os.path.join(
        ROOT, "benchmark", "layer_metrics", "records_pooled_pace.py"))
    Stamp = types.SimpleNamespace
    clock = types.SimpleNamespace(t0=100.0,
                                  trace_at=(Stamp(t=101.5), Stamp(t=103.5)))
    # the span opens 1.5 s into the loop: the five requests that finish at
    # 1.6 s are the tracer's, the other five count
    records = dict(enumerate(_records(TEN)))
    assert reader.read({"clock": clock, "records": records}) == (
        pytest.approx(1e3 * 0.3 / 75))
    clock.trace_at = None
    assert reader.read({"clock": clock, "records": records}) is None
    clock.trace_at = (Stamp(t=100.5), Stamp(t=102.5))
    assert reader.read({"clock": clock, "records": records}) is None


def _mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_every_seed_gets_the_same_schedule_and_other_token_ids():
    mix = _mix("code-complete")
    a = traffic_gen.make_requests(mix, 7, 49152, 40, 8192)
    b = traffic_gen.make_requests(mix, 2 ** 31 + 12345, 49152, 40, 8192)
    assert a == traffic_gen.make_requests(mix, 7, 49152, 40, 8192)
    shape = lambda reqs: [(t, len(p), o) for t, p, o in reqs]
    assert shape(a) == shape(b)
    assert [p for _, p, _ in a] != [p for _, p, _ in b]  # token ids differ
    assert len(a) == round(mix["arrivals"]["rate_per_s"] * 40)
    lo, hi = mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]
    assert all(lo <= len(p) <= hi for _, p, _ in a)
    assert all(4 <= t < 49152 for _, p, _ in a for t in p)
    # stratified: every block of arrivals holds the whole grid of lengths
    # and spans exactly block / rate seconds
    n = mix["block"]
    grid = sorted(traffic_gen.length_grid(mix["prompt_len"], n))
    assert sorted(len(p) for _, p, _ in a[:n]) == grid
    assert sorted(len(p) for _, p, _ in a[n:2 * n]) == grid
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(a[2 * n - 1][0] - a[n - 1][0] - n / rate) < 1e-9
    # the rehearsal's stream: the same schedule, other token ids
    r = traffic_gen.make_requests(mix, 7, 49152, 40, 8192, stream=1)
    assert shape(r) == shape(a) and r != a


def test_closed_queue_is_deep_and_due_at_zero():
    for name, max_seq in (("decode-heavy", 2048), ("long-prompt", 2048)):
        mix = _mix(name)
        reqs = traffic_gen.make_requests(mix, 3, 50272, 40, max_seq)
        assert len(reqs) == mix["queue_depth"]
        assert all(t == 0.0 for t, _, _ in reqs)
        assert all(len(p) + o <= max_seq for _, p, o in reqs)
        # drawn in rounds (the last may be cut short), each a whole number
        # of blocks, and every block holds the whole grid of both lengths
        per_round, block = mix["round"], mix["block"]
        assert mix["queue_depth"] % block == 0 == per_round % block
        assert mix["queue_depth"] >= 2 * per_round
        prompts = sorted(traffic_gen.length_grid(mix["prompt_len"], block))
        answers = sorted(traffic_gen.length_grid(mix["output_len"], block))
        for at in range(0, len(reqs), block):
            assert sorted(len(p) for _, p, _ in reqs[at:at + block]) == prompts
            assert sorted(o for _, _, o in reqs[at:at + block]) == answers
        # a queue of fewer rounds is the beginning of a deeper one, ids and
        # all; the rounds differ from each other
        for rounds in (1, 2):
            fewer = dict(mix, queue_depth=rounds * per_round)
            assert traffic_gen.make_requests(
                fewer, 3, 50272, 40, max_seq) == reqs[:rounds * per_round]
        shape = lambda rs: [(len(p), o) for _, p, o in rs]
        assert shape(reqs[:per_round]) != shape(reqs[per_round:2 * per_round])


def test_rounds_are_drawn_whole_and_one_round_is_the_old_draw():
    mix = {"loop": "closed", "queue_depth": 20, "block": 4,
           "prompt_len": {"dist": "uniform", "lo": 8, "hi": 120},
           "output_len": {"dist": "uniform", "lo": 4, "hi": 40}}
    shape = lambda rs: [(len(p), o) for _, p, o in rs]
    whole = traffic_gen.make_requests(mix, 1, 512, 1, 256)
    # no ``round``, or one as deep as the queue: the same draw
    assert traffic_gen.make_requests(dict(mix, round=20), 1, 512, 1,
                                     256) == whole
    # rounds of 8: a queue cut inside a round is still the beginning of a
    # deeper one (the round's draw does not depend on where the queue ends)
    deep = traffic_gen.make_requests(dict(mix, round=8, queue_depth=40), 1,
                                     512, 1, 256)
    for depth in (3, 8, 13, 20):
        cut = traffic_gen.make_requests(
            dict(mix, round=8, queue_depth=depth), 1, 512, 1, 256)
        assert cut == deep[:depth]
    # without rounds a deeper queue shifts the answers' order: the defect
    # the rounds are there to mend
    assert shape(traffic_gen.make_requests(
        dict(mix, queue_depth=40), 1, 512, 1, 256))[:20] != shape(whole)


def test_what_no_cell_brings_is_refused():
    with pytest.raises(ValueError):
        traffic_gen.gap_grid({"process": "gamma", "cv": 3.0,
                              "rate_per_s": 2.0}, 16)
    with pytest.raises(ValueError):
        traffic_gen.length_grid({"dist": "fixed", "value": 5}, 4)
