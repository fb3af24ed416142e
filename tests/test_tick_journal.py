"""The scheduler's tick journal (obs/journal.py, ISSUE 46): one record per
tick of the serving loops, always on, fed by the spans that are there.

Pinned at toy size on a virtual clock, kernels interpreted (so prompts ride
the tiled feed and joiners splice into running stretches): the records of
a loop tile it and split every extent into self time by span; their launch
and token sums are the serving records' and the telemetry counters'; the
ring drops the oldest; the tokens served are the parent commit's; a clock
that jumps inside one ``readback`` is reported, in one line that names it
— ``device_wait`` if the wait for the last result was late, ``readback``
if the copies were (ISSUE 59) — with what the next tick waited; what JAX
built while a record was open is in the record and on its line, and in the
process's build log wherever it happened.
"""

import logging

import jax.monitoring
import numpy as np
import pytest

from flexflow_tpu.obs import Telemetry
from flexflow_tpu.obs import journal as J
from flexflow_tpu.obs.journal import TickJournal
from flexflow_tpu.obs.trace import Span
from flexflow_tpu.serve import GenerationConfig, RequestManager

from test_serve import TINY, make_im

CAP, SLOTS, SEQ = 24, 4, 64        # tile 8: three tiles a chunk
STEP_NS = 10_000                   # one read of the virtual clock
_RNG = np.random.RandomState(46)
# six requests on four slots: prompts shorter than a tile, of whole tiles
# and of several chunks; answers of 3 to 14 tokens
PROMPTS = [_RNG.randint(1, TINY.vocab_size, size=n).tolist()
           for n in (5, 16, 3, 2 * CAP + 3, 7, CAP)]
ANSWERS = [9, 14, 3, 6, 12, 5]
# what the parent commit (72ec002: no journal) serves for them, the same
# whenever they arrive (continuous batching reorders work, not results)
PARENT_TOKENS = [
    [19, 29, 64, 0, 37, 43, 22, 16, 7],
    [65, 1, 31, 43, 49, 2, 35, 33, 10, 53, 53, 53, 53, 53],
    [52, 30, 15],
    [4, 63, 54, 53, 40, 58],
    [43, 43, 64, 26, 37, 43, 64, 26, 37, 43, 64, 64],
    [17, 24, 33, 44, 3],
]
# offsets of the open loop's arrivals on the virtual clock: two at once,
# three while those decode, one after the deployment has run empty
OPEN_AT = [0.0, 0.0, 0.0005, 0.0012, 0.002, 0.03]


class VirtualClock:
    """Every read advances time by ``STEP_NS``; ``jump`` adds nanoseconds
    at given reads.  ``ns`` is the journal's clock, ``s`` the loop's."""

    def __init__(self, jump=None):
        self.t, self.reads, self.jump = 0, 0, dict(jump or {})

    def ns(self):
        self.reads += 1
        self.t += STEP_NS + self.jump.get(self.reads, 0)
        return self.t

    def s(self):
        return self.ns() / 1e9


def manager(vc, telemetry=None, capacity=4096, journal=TickJournal):
    im = make_im(max_tokens=CAP, max_requests=SLOTS, max_seq=SEQ,
                 use_pallas=True)
    im.reset()
    rm = RequestManager(im, GenerationConfig(stop_on_eos=False),
                        telemetry=telemetry)
    # the virtual clock: the journal is always there, its clock is real
    rm.journal = im.journal = journal(
        capacity=capacity, chunk_width=im.max_tokens, clock_ns=vc.ns)
    return rm


def serve(rm, vc, mode):
    """The six requests through one serving loop; ``(tokens per request,
    virtual nanoseconds the call took)``."""
    t = vc.t
    if mode == "generate":
        rids = [rm.register_new_request(p, n)
                for p, n in zip(PROMPTS, ANSWERS)]
        out = rm.serve_incr_decoding()
        return [out[r] for r in rids], vc.t - t
    at = [0.0] * len(PROMPTS) if mode == "closed" else OPEN_AT
    recs = rm.serve_with_arrivals(
        [(a, p, n) for a, p, n in zip(at, PROMPTS, ANSWERS)], clock=vc.s)
    return [recs[r]["tokens"] for r in sorted(recs)], vc.t - t


MODES = ["generate", "closed", "open"]


@pytest.mark.parametrize("mode", MODES)
def test_records_tile_the_loop_and_split_every_extent(mode):
    vc = VirtualClock()
    rm = manager(vc)
    tokens, wall_ns = serve(rm, vc, mode)
    assert tokens == PARENT_TOKENS, "the journal changed what was served"
    recs = rm.journal.records()
    assert rm.journal.dropped == 0 and len(recs) >= 4
    # records abut: no hole between the first entry and the loop's return
    for a, b in zip(recs, recs[1:]):
        assert a["t1_ns"] == b["t0_ns"] and a["seq"] + 1 == b["seq"]
    total = sum(r["t1_ns"] - r["t0_ns"] for r in recs)
    assert total == recs[-1]["t1_ns"] - recs[0]["t0_ns"]
    # ... and all of the call but a few reads on either side of the loop
    assert 0 <= wall_ns - total <= 8 * STEP_NS
    kinds = {r["kind"] for r in recs}
    assert {"decode_stretch", "prefill_stretch"} <= kinds
    assert ("idle" in kinds) == (mode == "open")
    for r in recs:
        extent = r["t1_ns"] - r["t0_ns"]
        split = sum(r[f"{n}_ns"] for n in J.SPLIT)
        # self times: nested spans are counted once, so the split never
        # exceeds the extent and the rest is what no span covers
        assert split + r["unattributed_ns"] == extent
        assert 0 <= r["unattributed_ns"] < extent
        assert (r["tick_ns"] > 0) == (r["kind"] != "idle")
        assert r["t0_ns"] <= r["tick_ns"] < r["t1_ns"] or not r["tick_ns"]
    if mode != "generate":
        # the loop's own spans are in the split, the clock hook by name
        assert sum(r["loop_clock_ns"] for r in recs) > 0
        assert sum(r["loop_arrivals_ns"] for r in recs) > 0
    idle = [r for r in recs if r["kind"] == "idle"]
    # consecutive idle polls are ONE record
    assert all(a["kind"] != "idle" or b["kind"] != "idle"
               for a, b in zip(recs, recs[1:]))
    assert all(r["loop_idle_ns"] > 0 and r["polls"] > 1 for r in idle[:-1])


@pytest.mark.parametrize("mode", MODES)
def test_sums_are_the_records_and_the_counters(mode):
    vc, tel = VirtualClock(), Telemetry()
    rm = manager(vc, telemetry=tel)
    tokens, _ = serve(rm, vc, mode)
    assert tokens == PARENT_TOKENS
    rows = rm.journal.array()
    total = {name: int(rows[:, i].sum()) for i, name in enumerate(J.FIELDS)}
    snap = tel.metrics.snapshot()
    # tokens, by the program that made them
    assert (total["scan_tokens"] + total["join_tokens"]
            + total["step_tokens"] + total["prefill_tokens"]
            == sum(len(t) for t in tokens) == rm.tokens_decoded)
    assert total["admitted"] == len(PROMPTS)
    # the prompt feed: every token through the tiled scan, chunk by chunk
    fed = sum(len(p) for p in PROMPTS)
    assert total["prompt_tokens"] == total["chunk_tokens"] == fed
    assert snap["prompt_feed.tiled_tokens"] == total["chunk_tokens"]
    assert snap["prompt_feed.tiled_chunks"] == total["chunks"]
    assert (snap["prompt_feed.tiled_padded_rows"]
            == total["chunk_rows"] - total["chunk_tokens"])
    assert total["chunk_rows"] == total["chunks"] * CAP
    assert "prompt_feed.flat_tokens" not in snap
    # joins and decode scans
    assert snap.get("stretch_joins", 0) == total["join_tokens"]
    assert total["joins"] <= total["join_tokens"] <= total["joiners"]
    assert snap["decode_scan_steps"] == total["decode_steps"]
    assert total["row_steps"] <= total["width_steps"]
    assert total["joins"] > 0 and total["prefill_tokens"] > 0
    # the launches are the ring's launch spans, the stamps its tick spans'
    ring = [e for e in tel.trace.trace_events() if e["ph"] == "X"]
    count = {}
    for e in ring:
        count[e["name"]] = count.get(e["name"], 0) + 1
    assert count["decode_scan_dispatch"] == total["decode_scans"]
    assert count["prefill_scan_dispatch"] == total["prefill_scans"]
    assert count["join_dispatch"] == total["joins"]
    assert count.get("step_dispatch", 0) == total["step_launches"]
    stamps = sorted(e["args"]["pc_ns"] for e in ring
                    if "pc_ns" in e.get("args", {}))
    assert stamps == sorted(r for r in rows[:, J.FIELDS.index("tick_ns")]
                            if r)
    # a tick's first decoding launch gives the depth it ran at
    first = [e["args"] for e in ring if e["name"] == "decode_scan_dispatch"]
    assert first[0]["ctx_sum"] in rows[:, J.FIELDS.index("ctx_sum")]


def test_nested_spans_give_self_time():
    vc = VirtualClock()
    jr = TickJournal(clock_ns=vc.ns, chunk_width=CAP)
    jr.begin(pending=3, live=2)
    with Span("decode_stretch", {"pc_ns": jr.clock_ns()}, jr=jr):
        with Span("join", {"rid": 7}, jr=jr):
            with Span("host_prepare", jr=jr):
                vc.t += 1_000
            with Span("prefill_scan_dispatch",
                      {"n_steps": 2, "prompt_tokens": 30, "joiners": 1,
                       "rows": 1}, jr=jr):
                vc.t += 5_000
        with Span("stage_dispatch", jr=jr):      # not in the vocabulary
            with Span("readback", jr=jr):
                vc.t += 2_000
        with Span("commit", jr=jr) as sp:
            sp.set(scan_tokens=4, join_tokens=1)
    jr.end()
    (r,) = jr.records()
    s = STEP_NS
    assert r["kind"] == "decode_stretch" and r["tick_ns"] == r["t0_ns"] + s
    assert (r["pending"], r["live"]) == (3, 2)
    assert r["host_prepare_ns"] == s + 1_000
    assert r["prefill_scan_dispatch_ns"] == s + 5_000
    # ``join`` keeps what its two children do not cover
    assert r["join_ns"] == 5 * s + 6_000 - (2 * s + 6_000)
    assert r["readback_ns"] == s + 2_000 and r["commit_ns"] == s
    # the tick span's own time and the unknown span's go unattributed
    assert r["unattributed_ns"] == r["t1_ns"] - r["t0_ns"] - sum(
        r[f"{n}_ns"] for n in J.SPLIT)
    assert r["unattributed_ns"] == 8 * s
    assert (r["chunks"], r["chunk_rows"], r["chunk_tokens"], r["joiners"]) \
        == (2, 2 * CAP, 30, 1)
    assert (r["scan_tokens"], r["join_tokens"]) == (4, 1)
    # outside a record a span is no event at all
    with Span("host_prepare", jr=jr):
        pass
    assert jr.emitted == 1


def test_a_prompt_launchs_arguments_are_summed_by_name():
    """Every name of ``_PROMPT_LAUNCH`` is a field that sums the launch's
    argument of that name — of a flat step and of a prefill scan, whatever
    the name; a decode scan's adds to none of them, an all-pad scan's
    neither; and the ``commit`` span's routed-expert load is set by name."""
    assert set(J._PROMPT_LAUNCH) | set(J._EXPERT_LOAD) <= set(J.FIELDS)
    vc = VirtualClock()
    jr = TickJournal(clock_ns=vc.ns, chunk_width=CAP)
    jr.begin(pending=0, live=1)
    args = {name: 3 + i for i, name in enumerate(J._PROMPT_LAUNCH)}
    with Span("serve_step", {"pc_ns": jr.clock_ns()}, jr=jr):
        with Span("step_dispatch", {"rows": 1, **args}, jr=jr):
            pass
        with Span("prefill_scan_dispatch", {"n_steps": 1, **args}, jr=jr):
            pass
        with Span("prefill_scan_dispatch",
                  {"n_steps": 1, "pad": 1, **args}, jr=jr):
            pass
        with Span("decode_scan_dispatch",
                  {"n_steps": 4, "rows": 1, **args}, jr=jr):
            pass
        with Span("commit", jr=jr) as sp:
            sp.set(**{name: 7 for name in J._EXPERT_LOAD})
            sp.set(**{name: 1 for name in J._EXPERT_LOAD})
    jr.end()
    (r,) = jr.records()
    assert (r["step_launches"], r["prefill_scans"], r["decode_scans"]) == \
        (1, 2, 1)
    assert {name: r[name] for name in J._PROMPT_LAUNCH} == {
        name: 2 * n for name, n in args.items()}
    assert all(r[name] == 8 for name in J._EXPERT_LOAD)


def test_ring_drops_the_oldest_and_counts_them():
    vc = VirtualClock()
    rm = manager(vc, capacity=2)
    serve(rm, vc, "closed")
    jr = rm.journal
    recs = jr.records()
    assert jr.emitted > 2 and len(recs) == 2
    assert jr.dropped == jr.emitted - 2
    assert [r["seq"] for r in recs] == [jr.emitted - 2, jr.emitted - 1]
    with pytest.raises(ValueError):
        TickJournal(capacity=0)


class Probe(TickJournal):
    """Notes the clock read at which each span was entered."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.entered = []

    def _enter(self, name, args):
        ok = super()._enter(name, args)
        self.entered.append((name, self.clock_ns.__self__.reads))
        return ok


def paced(vc, journal=TickJournal):
    """Two requests of 41 tokens in stretches of 4 steps: ten ticks of one
    class, (``decode_stretch``, 4 steps, no chunk)."""
    rm = manager(vc, journal=journal)
    rm.scan_chunk = 4
    out = rm.generate(PROMPTS[:2], 41)
    assert [len(t) for t in out] == [41, 41]
    return rm


# where the 3 s pass, counted in clock reads after ``device_wait`` was
# entered: its own exit (the wait for the last result), or the exit of the
# ``readback`` around it (the copies)
@pytest.mark.parametrize("late,reads_after,self_reads",
                         [("device_wait", 1, 1), ("readback", 2, 2)])
def test_a_stall_inside_one_readback_is_one_line_that_names_it(
        caplog, late, reads_after, self_reads):
    caplog.set_level(logging.WARNING, logger="flexflow_tpu.serve")
    probe = paced(VirtualClock(), journal=Probe).journal
    assert not caplog.records, "a steady run reported a slow tick"
    stretches = [r for r in probe.records() if r["kind"] == "decode_stretch"]
    assert len(stretches) == 10 and {r["decode_steps"]
                                     for r in stretches} == {4}
    # one ``device_wait`` a ``readback``, entered right after it
    reads = [at for name, at in probe.entered if name == "device_wait"]
    assert [at - 1 for at in reads] == [
        at for name, at in probe.entered if name == "readback"]
    vc = VirtualClock(jump={reads[6] + reads_after: 3_000_000_000})
    jr = paced(vc).journal
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 1, lines
    assert lines[0].startswith("slow tick: decode_stretch at ")
    assert f"split ms: {late} 3000.0 " in lines[0]
    assert "launches: decode_scans 1 decode_steps 4" in lines[0]
    # the stretch after the stalled one waited its usual time
    assert lines[0].endswith("; next device_wait 0.0")
    (slow,) = jr.slowest()
    assert slow[f"{late}_ns"] == 3_000_000_000 + self_reads * STEP_NS
    other = "readback" if late == "device_wait" else "device_wait"
    assert slow[f"{other}_ns"] == (3 - self_reads) * STEP_NS
    assert slow["next_device_wait_ns"] == STEP_NS
    assert slow["median_ns"] < 100 * STEP_NS
    # the share the benchmark reads is the same rule's
    rows = jr.array()
    excess = J.slow_excess_ns(rows)
    assert np.count_nonzero(excess) == 1
    assert excess.sum() == (slow["t1_ns"] - slow["t0_ns"]
                            - slow["median_ns"])


@pytest.mark.parametrize("mode", MODES)
def test_device_wait_and_readback_are_the_parents_readback(mode):
    """At the parent a ``readback`` span was one clock read long (its
    exit); the span now nested in it adds its own entry and exit: the
    wait takes the read at its exit, the copies keep the other two."""
    vc = VirtualClock()
    rm = manager(vc, journal=Probe)
    tokens, _ = serve(rm, vc, mode)
    assert tokens == PARENT_TOKENS
    n = sum(name == "readback" for name, _ in rm.journal.entered)
    assert n == sum(name == "device_wait" for name, _ in rm.journal.entered)
    recs = rm.journal.records()
    parent_readback_ns = n * STEP_NS
    wait = sum(r["device_wait_ns"] for r in recs)
    copies = sum(r["readback_ns"] for r in recs)
    assert wait == n * STEP_NS
    assert wait + copies - 2 * n * STEP_NS == parent_readback_ns
    for r in recs:
        assert r["readback_ns"] == 2 * r["device_wait_ns"]
        # still tiled, the new field in the split
        assert r["t1_ns"] - r["t0_ns"] == r["unattributed_ns"] + sum(
            r[f"{name}_ns"] for name in J.SPLIT)
    assert "device_wait" in J.SPLIT and "device_wait_ns" in J.FIELDS


def test_device_wait_takes_whatever_a_readback_will_copy():
    """Device arrays, host arrays (a stage that answered on the host) and
    tuples of them: one span, entered once, inside the open record."""
    import jax.numpy as jnp

    vc = VirtualClock()
    rm = manager(vc, journal=Probe)
    rm.journal.begin(0, 0)
    with rm._span("readback"):
        rm._device_wait([(jnp.arange(4), np.arange(3)), np.zeros(2),
                         jnp.ones((2, 2))])
    rm.journal.end()
    assert [n for n, _ in rm.journal.entered] == ["readback", "device_wait"]
    (rec,) = rm.journal.records()
    assert rec["device_wait_ns"] == STEP_NS
    assert rec["readback_ns"] == 2 * STEP_NS


def _tick(jr, vc, wait_ns, body=None):
    jr.begin(0, 2)
    with Span("decode_stretch", {"pc_ns": jr.clock_ns()}, jr=jr):
        with Span("decode_scan_dispatch", {"n_steps": 32, "rows": 2}, jr=jr):
            if body is not None:
                body()
        with Span("readback", jr=jr):
            with Span("device_wait", jr=jr):
                vc.t += wait_ns


def test_the_last_tick_of_a_loop_has_no_next_device_wait(caplog):
    caplog.set_level(logging.WARNING, logger="flexflow_tpu.serve")
    vc = VirtualClock()
    jr = TickJournal(clock_ns=vc.ns, chunk_width=CAP)
    for wait_ms in [300] * 9 + [3_300]:
        _tick(jr, vc, wait_ms * 1_000_000)
    jr.end()
    (line,) = [r.getMessage() for r in caplog.records]
    assert "split ms: device_wait 3300.0 " in line
    assert "next device_wait" not in line and "built:" not in line
    # a stall that outlives its tick: the next one waits long too, and an
    # idle poll between them is not "the next"
    caplog.clear()
    for wait_ms in [300] * 9 + [3_300, -1, 2_100]:
        if wait_ms < 0:
            with Span("loop_arrivals", jr=jr):
                pass
            continue
        _tick(jr, vc, wait_ms * 1_000_000)
    jr.end()
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 2 and "; next device_wait 2100.0" in lines[0]


TRACE, LOWER, COMPILE = J.BUILD_WHATS


def _built(what, secs, fun_name):
    """An event as jax reports it (the public recorder of its listeners)."""
    if fun_name is None:
        jax.monitoring.record_event_duration_secs(what, secs)
    else:
        jax.monitoring.record_event_duration_secs(what, secs,
                                                  fun_name=fun_name)


def test_a_build_inside_a_record_is_in_it_and_on_its_line(caplog,
                                                          monkeypatch):
    caplog.set_level(logging.WARNING, logger="flexflow_tpu.serve")
    vc = VirtualClock()
    jr = TickJournal(clock_ns=vc.ns, chunk_width=CAP)
    log = J.build_log()
    # the log stamps arrivals on the virtual clock too (without a read)
    monkeypatch.setattr(log, "clock_ns", lambda: vc.t)
    n0 = log.emitted
    # outside a loop: in the log alone
    _built(COMPILE, 0.25, "jit(_step_impl)")
    assert log.emitted == n0 + 1 and jr.emitted == 0

    def recompile():
        # jax reports a build's start as a scalar under the event's name:
        # ``multiply`` is traced INSIDE the scan's trace, and is no event
        jax.monitoring.record_scalar(TRACE, 0.0, fun_name="_decode_scan_impl")
        jax.monitoring.record_scalar(TRACE, 0.0, fun_name="multiply")
        _built(TRACE, 0.001, "multiply")
        _built(TRACE, 0.5, "_decode_scan_impl")
        _built(LOWER, 0.125, "jit(_decode_scan_impl)")
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        _built(COMPILE, 2.0, "jit(_decode_scan_impl)")
        _built(COMPILE, 0.0625, None)
        vc.t += 2_700_000_000

    for i in range(10):
        _tick(jr, vc, 300_000_000, body=recompile if i == 6 else None)
    jr.end()
    recs = jr.records()
    assert [r["builds"] for r in recs] == [0] * 6 + [4] + [0] * 3
    assert recs[6]["build_ns"] == 2_687_500_000
    # an overlay: the time lies under the launch span, the record tiles
    assert recs[6]["decode_scan_dispatch_ns"] == 2_700_000_000 + STEP_NS
    (line,) = [r.getMessage() for r in caplog.records]
    assert line.endswith(
        "; next device_wait 300.0; built: _decode_scan_impl compile 2000.0 "
        "cached, _decode_scan_impl trace 500.0, _decode_scan_impl lower "
        "125.0")
    # the log: one name a program over its three events, ``?`` for an
    # event without one, ``cached`` for the compile after a cache hit only
    mine = log.since(n0)
    assert [(b.what, b.fun_name, b.cached) for b in mine] == [
        ("compile", "_step_impl", False),
        ("trace", "_decode_scan_impl", False),
        ("lower", "_decode_scan_impl", False),
        ("compile", "_decode_scan_impl", True), ("compile", "?", False)]
    assert mine[3].dur_ns == 2_000_000_000
    scan = J.builds(programs=["_decode_scan_impl"])[-3:]
    assert [b.what for b in scan] == ["trace", "lower", "compile"]
    t_step = mine[0].t_ns
    assert J.builds(programs=["_step_impl", "_decode_scan_impl"],
                    before_ns=t_step)[-1] == mine[0]
    assert J.builds(after_ns=recs[6]["t0_ns"],
                    before_ns=recs[6]["t1_ns"]) == mine[1:]


def test_one_listener_however_many_journals():
    vc = VirtualClock()
    journals = [TickJournal(clock_ns=vc.ns) for _ in range(5)]
    log = J.build_log()
    assert log is J.build_log() and all(j in log.journals for j in journals)
    journals[1].begin(0, 0)
    journals[3].begin(0, 0)
    n0 = log.emitted
    _built(LOWER, 0.5, "jit(_join_impl)")
    # one event, once: in the log, and in every record that is open
    assert log.emitted == n0 + 1
    for j in journals:
        j.end()
    assert [[r["builds"] for r in j.records()] for j in journals] == [
        [], [1], [], [1], []]
    assert journals[3].records()[0]["build_ns"] == 500_000_000
    # a journal nobody holds leaves the set
    n = len(log.journals)
    del journals, j
    assert len(log.journals) == n - 5


def test_the_build_log_is_bounded():
    assert J.BUILD_LOG_CAPACITY == 1024
    assert J.build_log().events.maxlen == J.BUILD_LOG_CAPACITY
    log = J.BuildLog(capacity=4)    # not the process's: no listener
    for i in range(6):
        log._on_duration(COMPILE, i * 1e-9, fun_name=f"jit(f{i})")
    log._on_duration("/jax/some/other_duration", 1.0, fun_name="g")
    assert (log.emitted, log.dropped, len(log.events)) == (6, 2, 4)
    assert [b.fun_name for b in log.events] == ["f2", "f3", "f4", "f5"]
    assert [b.fun_name for b in log.since(4)] == ["f4", "f5"]
    assert [b.fun_name for b in log.since(0)] == ["f2", "f3", "f4", "f5"]
    assert log.since(6) == []


@pytest.mark.parametrize("peers,caught", [(9, True), (5, False)])
def test_a_tick_of_a_rare_shape_is_judged_by_its_kind(peers, caught):
    """A stall may hit a tick whose class — (kind, decode steps, chunks) —
    has too few records for a median: it is held to its kind's records
    then, if THOSE are eight or more."""
    vc = VirtualClock()
    jr = TickJournal(clock_ns=vc.ns, chunk_width=CAP)
    for chunks, wait in [(0, 300)] * peers + [(3, 3_300)]:
        jr.begin(0, 2)
        with Span("decode_stretch", {"pc_ns": jr.clock_ns()}, jr=jr):
            if chunks:
                with Span("prefill_scan_dispatch", {"n_steps": chunks},
                          jr=jr):
                    pass
            with Span("decode_scan_dispatch", {"n_steps": 32, "rows": 2},
                      jr=jr):
                pass
            with Span("readback", jr=jr):
                vc.t += wait * 1_000_000
    jr.end()
    slow = jr.slowest()
    assert len(slow) == (1 if caught else 0)
    if caught:
        assert slow[0]["chunks"] == 3 and slow[0]["readback_ns"] > 3e9
        assert abs(slow[0]["median_ns"] - 300e6) < 1e6
