"""A closed queue outlasts its window at the chip's roofline (headroom.py),
deepening it does not move what the window sees (the prefix pins), and the
window's own bookkeeping does not walk the backlog."""

import hashlib
import importlib
import json
import os
import types

import pytest

from conftest import ROOT

from benchmark import costs, headroom, serve_loop, traffic_gen


def _mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _digest(reqs):
    m = hashlib.sha256()
    for t, ids, answer in reqs:
        m.update(json.dumps([t, ids, answer]).encode())
    return m.hexdigest()


# what the generator of PR 27 (commit 9017c5a) made of the traffic files of
# PR 27 — queues of 96 and 768, no rounds, 51 s of the open loop — at two
# seeds: sha256 over every request's (due time, token ids, answer length).
# The deepened files must begin with exactly these requests.
PINNED = {
    ("decode-heavy", 96, 3):
        "e47b34b62408a541eb2e8b76511953404ac285bc4201dc1803196ded9785b5f7",
    ("decode-heavy", 96, 2 ** 31 + 12345):
        "fa04afeb0da8a88a2cdb9fb62f829023bcfb28041665c8b4a81b8b5776d1d8ae",
    ("long-prompt", 768, 3):
        "855aaaa2cb81674608dc5064cdb3e39b72d4df64efb69b2e62e3aab1a6b9b060",
    ("long-prompt", 768, 2 ** 31 + 12345):
        "93b17d69321634ccdfb493f9a1ae4f5eb5e2021e2a8fadb7c147be1804636ce3",
    ("code-complete", 184, 3):
        "b33ac64a409abb56bfd99da4c26fdcc67c711ce971b2ced3abe5d6b86e67d298",
    ("code-complete", 184, 2 ** 31 + 12345):
        "9f5476667ebed0d90dd8e01e93936c82394de131916d75b744cd28852c7f0976",
}
# the rehearsal's stream of the same two closed files, seed 3
PINNED_REHEARSAL = {
    ("decode-heavy", 96):
        "22378231eba19d8b8d303720fad1332e1558fdf37c7914f5b190acd00a5d934c",
    ("long-prompt", 768):
        "eb30f0a33c323fb5578a8e133cd1c7a7faa8e4dd23b37967ca9c04af1508ae39",
}
SIZES = {"decode-heavy": (50272, 2048), "long-prompt": (50272, 2048),
         "code-complete": (49152, 8192)}
# PR 36 re-seated the open loop's rate; offered the old one, the generator
# still makes the old schedule: its construction did not move
OLD_RATE = {"code-complete": 3.6}


@pytest.mark.parametrize("name,first,seed", sorted(PINNED))
def test_the_first_requests_are_the_ones_the_old_files_made(name, first,
                                                            seed):
    vocab, max_seq = SIZES[name]
    mix = _mix(name)
    if name in OLD_RATE:
        mix["arrivals"]["rate_per_s"] = OLD_RATE[name]
    reqs = traffic_gen.make_requests(mix, seed, vocab, 51, max_seq)
    assert len(reqs) >= first
    assert _digest(reqs[:first]) == PINNED[name, first, seed]


@pytest.mark.parametrize("name,first", sorted(PINNED_REHEARSAL))
def test_the_rehearsal_begins_as_it_did(name, first):
    vocab, max_seq = SIZES[name]
    reqs = traffic_gen.make_requests(_mix(name), 3, vocab, 4, max_seq,
                                     stream=1)
    assert _digest(reqs[:first]) == PINNED_REHEARSAL[name, first]


# what a re-seated rate may not move (PR 36 moved ``rate_per_s``, ``reports``
# and ``trace_span_s`` of both open mixes and nothing else): the lengths,
# the grid, the arrival process, the drain
OPEN_MIXES = {
    "code-complete": ({"dist": "lognormal", "median": 1024, "sigma": 0.85,
                       "lo": 64, "hi": 6144},
                      {"dist": "lognormal", "median": 16, "sigma": 1.0,
                       "lo": 1, "hi": 128}),
    "chat-open": ({"dist": "lognormal", "median": 512, "sigma": 0.8,
                   "lo": 32, "hi": 1500},
                  {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "lo": 8, "hi": 400}),
}


@pytest.mark.parametrize("name", sorted(OPEN_MIXES))
def test_an_open_loop_file_keeps_its_lengths_and_says_where_its_rate_is_from(
        name):
    mix = _mix(name)
    assert (mix["prompt_len"], mix["output_len"]) == OPEN_MIXES[name]
    assert (mix["loop"], mix["block"], mix["drain_s"], mix["rehearse_s"],
            mix["arrivals"]["process"]) == ("open", 16, 20, 4, "poisson")
    # the rate is 0.8 of a knee that the file names, to one decimal
    knee = float(mix["rate_from"].split("knee of THIS schedule, ")[1]
                 .split(" req/s")[0])
    assert mix["arrivals"]["rate_per_s"] == round(0.8 * knee, 1)


def test_dense_step_cost_by_hand():
    # 10 parameters, 3 rows: each parameter is one multiply-add per row,
    # and is read once, 2 bytes, however many rows share the step
    assert costs.dense_step_cost(10, 3) == (60, 20)
    assert costs.dense_step_cost(10, 512) == (10240, 20)
    assert costs.dense_step_cost(10, 1, weight_bytes=1) == (20, 10)


def test_model_shape_from_the_reference_tables():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "opt-6.7b-d12.json")) as f:
        conf = json.load(f)
    ref = importlib.import_module("benchmark.reference.opt")
    shape = headroom.model_shape(ref, conf)
    # q, k, v, out: 4 x 4096^2; fc1, fc2: 2 x 4096 x 16384
    assert shape["layer_params"] == 4 * 4096 ** 2 + 2 * 4096 * 16384
    assert shape["head_params"] == 50272 * 4096
    assert (shape["layers"], shape["q_heads"], shape["kv_heads"],
            shape["head_dim"]) == (12, 32, 32, 128)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "starcoderbase-3b.json")) as f:
        conf = json.load(f)
    ref = importlib.import_module("benchmark.reference.gpt_bigcode")
    shape = headroom.model_shape(ref, conf)
    assert (shape["layers"], shape["q_heads"], shape["kv_heads"],
            shape["head_dim"]) == (36, 22, 1, 128)
    assert shape["layer_params"] == (2 * 2816 ** 2 + 2 * 2816 * 128
                                     + 2 * 2816 * 11264)


def test_one_request_at_the_roofline_by_hand():
    # one layer of 1000 matrix parameters, an LM head of 100, 2 query heads
    # on 1 kv head of size 4; 2 slots; a chip of 1000 FLOP/s and 100 B/s
    shape = {"layers": 1, "layer_params": 1000, "head_params": 100,
             "q_heads": 2, "kv_heads": 1, "head_dim": 4}
    peak = {"flops_bf16": 1000.0, "hbm_bytes_per_s": 100.0}
    prefill, decode = headroom.request_least_seconds(3, 5, shape, 2, peak)
    # prefill: 2*1000*3 + 2*100*1 dense ops, attention 4*2*4*(1+2+3) = 192
    # -> 6392 ops = 6.392 s; bytes (2000 + 200)/2 + (16*3 + 2*3*2*4*2) =
    # 1100 + 144 -> 12.44 s: memory-bound
    assert prefill == pytest.approx(12.44)
    # decode: 4 rows (the first answer token is the prefill's) at contexts
    # 4, 5, 6, 7: dense 2*1100*4 = 8800 ops, attention 4*2*4*22 = 704 ->
    # 9.504 s; bytes 2200 * 4 / 2 + (16*22 + 2*4*2*4*2) = 4400 + 480 ->
    # 48.8 s
    assert decode == pytest.approx(48.8)


def test_every_closed_cell_outlasts_its_window_at_the_roofline(capsys):
    cells = headroom.closed_cells()
    assert cells, "no closed-loop cell in BENCHMARK.json"
    for name, kind, h in cells:
        with capsys.disabled():
            print(f"\nheadroom: {name} on {kind}: {h['requests']} requests, "
                  f"{h['tokens']} tokens, roofline "
                  f"{h['roofline_tok_s']:.0f} tokens/s, ratio "
                  f"{h['ratio']:.2f} (needs {headroom.HEADROOM})")
        assert h["ratio"] >= headroom.HEADROOM, (name, kind, h)


@pytest.mark.parametrize("name,old_depth", [("decode-heavy", 96),
                                            ("long-prompt", 768)])
def test_the_old_depths_fail_the_rule(name, old_depth):
    """The depths PR 26 picked by hand emptied at 0.5 and 0.66 of a window
    at the roofline: the rule has to refuse them."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "opt-6.7b-d12.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)["TPU v5 lite"]
    ref = importlib.import_module("benchmark.reference.opt")
    mix = dict(_mix(name), queue_depth=old_depth)
    h = headroom.queue_headroom(mix, headroom.model_shape(ref, conf), 8, peak,
                                51)
    assert h["requests"] == old_depth
    assert h["ratio"] < 1.0 < headroom.HEADROOM


class _Counting(dict):
    """A ``requests`` table that counts how often a request is looked at."""

    looked = 0

    def __getitem__(self, rid):
        self.looked += 1
        return dict.__getitem__(self, rid)


def _fake_manager(depth, slots, finished=0):
    from flexflow_tpu.serve.request_manager import RequestStatus

    def request(status, generated, fed):
        return types.SimpleNamespace(status=status, prompt=[7] * 100,
                                     generated=[5] * generated,
                                     prefill_offset=fed)

    table = _Counting()
    for rid in range(depth):
        if rid < finished:
            table[rid] = request(RequestStatus.COMPLETED, 10, 100)
        elif rid < finished + slots:
            table[rid] = request(RequestStatus.DECODING, 3, 100)
        else:
            table[rid] = request(RequestStatus.PENDING, 0, 0)
    live = list(range(finished, finished + slots))
    return types.SimpleNamespace(
        requests=table, _next_rid=depth, steps=0, slots=live,
        pending=list(range(finished + slots, depth)),
        cancel=lambda rid: None)


def test_totals_does_not_walk_the_backlog():
    rm = _fake_manager(depth=2304, slots=8)
    rm._next_rid = 0
    clock = serve_loop.WindowClock(rm, "closed", 51)
    rm._next_rid = 2304
    assert clock._totals() == (8 * 3, 8 * 100, 8 * 100)
    assert rm.requests.looked <= 16
    assert sorted(clock._lengths()) == list(range(8))
    # 5 requests finish, 5 more leave the queue: the finished are looked
    # at once more and retired, the new ones enter the walk
    from flexflow_tpu.serve.request_manager import RequestStatus

    for rid in range(5):
        rm.requests[rid].status = RequestStatus.COMPLETED
        rm.requests[rid].generated = [5] * 10
    for rid in range(8, 13):
        rm.requests[rid].generated = [5] * 2
        rm.requests[rid].prefill_offset = 100
    rm.pending = rm.pending[5:]
    rm.requests.looked = 0
    assert clock._totals() == (5 * 10 + 3 * 3 + 5 * 2, 13 * 100, 13 * 100)
    assert rm.requests.looked <= 16
    rm.requests.looked = 0
    clock._totals()
    assert rm.requests.looked == 8


def test_totals_counts_what_came_and_went_between_two_looks():
    """A request that left the queue AND finished between two boundaries
    was never seen in a slot: its tokens still count."""
    rm = _fake_manager(depth=100, slots=4, finished=20)
    rm._next_rid = 0
    clock = serve_loop.WindowClock(rm, "closed", 51)
    rm._next_rid = 100
    assert clock._totals() == (20 * 10 + 4 * 3, 24 * 100, 24 * 100)


def test_totals_without_a_pending_list_walks_everything():
    rm = _fake_manager(depth=50, slots=4)
    del rm.pending
    rm._next_rid = 0
    clock = serve_loop.WindowClock(rm, "closed", 51)
    rm._next_rid = 50
    assert clock._totals() == (4 * 3, 4 * 100, 4 * 100)
    assert rm.requests.looked == 50


def _ticking(monkeypatch, rm, seconds):
    """A closed-loop clock on a manager whose every slot decodes, opened at
    t = 1, with the host's clock in the test's hands."""
    now = [0.0]
    monkeypatch.setattr(serve_loop, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0]))
    rm._next_rid = 0
    clock = serve_loop.WindowClock(rm, "closed", seconds)
    rm._next_rid = len(rm.requests)

    def tick(t):
        rm.steps += 1
        now[0] = t
        clock()

    clock()                      # the loop's own zero: no boundary yet
    assert clock.opened is None
    tick(1.0)
    assert clock.opened.t == 1.0
    return clock, tick


def test_the_window_closes_at_a_boundary_of_the_kind_that_opened_it(
        monkeypatch):
    rm = _fake_manager(depth=40, slots=4)
    clock, tick = _ticking(monkeypatch, rm, 51)
    tick(51.9)                   # not due yet
    assert clock.closed is None
    first = rm.slots[0]
    rm.slots[0] = None           # due, but a slot is between two requests
    tick(52.3)
    assert clock.closed is None
    rm.slots[0] = first          # every slot decodes again: the window's end
    tick(52.9)
    assert clock.closed.t == 52.9 and clock.cancelled
    assert clock.ticks == [51.9, 52.3, 52.9]


def test_a_window_that_finds_no_such_boundary_closes_a_tenth_late(
        monkeypatch):
    rm = _fake_manager(depth=40, slots=4)
    clock, tick = _ticking(monkeypatch, rm, 51)
    rm.slots[0] = None
    tick(55.0)                   # 3 s overdue: still waiting
    assert clock.closed is None
    tick(57.2)                   # more than 5.1 s overdue: closed as it is
    assert clock.closed.t == 57.2 and clock.cancelled


class _Tracer:
    def __init__(self):
        self.calls = []

    def start(self):
        self.calls.append("start")

    def stop(self):
        self.calls.append("stop")


def test_an_open_loop_s_trace_is_stopped_after_the_loop_a_closed_one_s_in_it(
        monkeypatch):
    """``stop_trace`` stalls the host for seconds: arrivals of an open loop
    would wait behind it and outlast the drain (failed requests in traced
    runs only), a closed queue just waits."""
    now = [0.0]
    monkeypatch.setattr(serve_loop, "time", types.SimpleNamespace(
        perf_counter=lambda: now[0]))
    for loop, stopped_inside in (("open", False), ("closed", True)):
        rm = _fake_manager(depth=40, slots=4)
        tracer = _Tracer()
        clock = serve_loop.WindowClock(rm, loop, 51, tracer=tracer,
                                       trace_after_s=2.0, trace_span_s=1.0)
        now[0] = 0.0
        clock()
        for t in (1.0, 3.0, 3.5, 4.5, 5.0):
            rm.steps += 1
            now[0] = t
            clock()
        assert tracer.calls[0] == "start"
        assert ("stop" in tracer.calls) is stopped_inside
        now[0] = 6.0
        clock.finish()
        assert tracer.calls == ["start", "stop"]
        opened, closed = clock.trace_at
        assert opened.t == 3.0
        assert closed.t == (4.5 if stopped_inside else 6.0)
