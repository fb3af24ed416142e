"""``layer_metrics/journal_ratio.py`` on a synthetic journal and clock: which
records it keeps (the window, less the profiler session), each committed
metric's arithmetic, and ``None`` where there is nothing under the ratio."""

import json
import os
import types

import pytest

from conftest import ROOT

from benchmark.run import load_module
from benchmark.serve_loop import Stamp
from flexflow_tpu.obs.journal import TickJournal
from flexflow_tpu.obs.trace import Span

METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
MS = 1_000_000
# the synthetic run, one tick a row: (kind, extent ms, launches, commits).
# Ticks 0-1 lie before the window, 12 holds the tracer's start, 13 lies in
# the session, 14 holds its stop (a stall), 15-16 come after it
DECODE = ("decode_stretch", 300,
          [("decode_scan_dispatch", dict(n_steps=32, rows=6, width=8,
                                         ctx_sum=6000))],
          dict(scan_tokens=192))
ADMIT = ("decode_stretch", 330,
         [("prefill_scan_dispatch", dict(n_steps=2, prompt_tokens=300,
                                         joiners=1, rows=5)),
          ("join_dispatch", dict(rows=6)),
          ("decode_scan_dispatch", dict(n_steps=32, rows=8, width=8,
                                        ctx_sum=4000))],
         dict(scan_tokens=250, join_tokens=1))
WAVE = ("prefill_stretch", 100,
        [("prefill_scan_dispatch", dict(n_steps=4, prompt_tokens=2000,
                                        joiners=0, rows=0))],
        dict(prefill_tokens=3))
STALL = ("decode_stretch", 3300, DECODE[2], DECODE[3])
TICKS = [DECODE, DECODE,                         # before the window
         DECODE, DECODE, ADMIT, DECODE, DECODE, WAVE, DECODE, DECODE,
         DECODE, STALL,                          # 2..11: read
         DECODE, DECODE, STALL,                  # 12..14: the session
         DECODE, DECODE]                         # 15..16: read


class Clock:
    def __init__(self):
        self.t = 1_000 * MS

    def ns(self):
        return self.t


def journal():
    """``(journal, [record start in ns])`` of the run above."""
    clock = Clock()
    jr = TickJournal(chunk_width=512, clock_ns=clock.ns)
    starts = []
    for kind, ms, launches, commits in TICKS:
        starts.append(clock.t)
        with Span("loop_arrivals", jr=jr):
            clock.t += 1 * MS
        jr.begin(pending=40, live=8)
        with Span(kind, {"pc_ns": clock.ns()}, jr=jr):
            for name, args in launches:
                with Span(name, args, jr=jr):
                    clock.t += 2 * MS
            with Span("readback", jr=jr):
                clock.t += (ms - 1 - 2 * len(launches) - 1) * MS
            with Span("commit", jr=jr) as sp:
                sp.set(**commits)
                clock.t += 1 * MS
    starts.append(clock.t)
    jr.end()
    return jr, starts


def context(jr, starts, session=True, window=(2, 17)):
    def stamp(ns):
        return Stamp(ns / 1e9, 0, 0, 0)

    clock = types.SimpleNamespace(
        opened=stamp(starts[window[0]]), closed=stamp(starts[window[1]]),
        trace_at=None, tracer=None)
    if session:
        # the tracer starts INSIDE tick 12 and stamps its stop inside 14
        clock.trace_at = (stamp(starts[12] + 200 * MS), None)
        clock.tracer = types.SimpleNamespace(
            t_stop=(starts[14] + 250 * MS) / 1e9)
    lines = []
    return dict(llm=types.SimpleNamespace(rm=types.SimpleNamespace(
        journal=jr)), clock=clock, log=lines.append), lines


def spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        s = json.load(f)
    assert s["reader"] == "journal_ratio.py" and s["what"]
    return s.get("args", {})


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(METRICS, "journal_ratio.py"))


def test_window_and_session_filtering(reader):
    jr, starts = journal()
    ctx, lines = context(jr, starts)
    _, J, rows = reader.kept_records(ctx)
    seq = rows[:, J.FIELDS.index("seq")].tolist()
    # the window's records, less 12 (the start), 13 and 14 (the stop)
    assert seq == list(range(2, 12)) + [15, 16]
    ctx, _ = context(jr, starts, session=False)
    assert len(reader.kept_records(ctx)[2]) == 15
    # a window that closes before the session opens keeps what is inside
    ctx, _ = context(jr, starts, window=(2, 8))
    assert len(reader.kept_records(ctx)[2]) == 6
    # the first reading logs what was read, once
    ctx, lines = context(jr, starts)
    reader.read(ctx, **spec("decode_rows_per_step.lat"))
    reader.read(ctx, **spec("slow_tick_share_pct.thr"))
    assert len(lines) == 1 and "in 12 records" in lines[0]
    kept_s = (9 * 300 + 330 + 100 + 3300) / 1e3
    assert f"read {kept_s:.3f}s" in lines[0]
    # depth: ctx_sum / rows of each tick's first decoding launch, averaged
    assert "over them %.1f" % ((10 * 1000 + 500) / 11) in lines[0]


def test_each_metric_s_arithmetic(reader):
    jr, starts = journal()

    def read(name):
        ctx, _ = context(jr, starts)
        return reader.read(ctx, **spec(name))

    # kept: 10 decode ticks of 300 ms and 192 tokens (one of them the stall
    # of 3300 ms), one admission tick, one wave
    assert read("window_decode_ms_per_tok.thr") == pytest.approx(
        (9 * 300 + 3300) / (10 * 192))
    assert read("window_admit_ms_per_ktok.thr") == pytest.approx(
        (330 + 100) / ((300 + 2000) / 1000))
    for name in ("prefill_chunk_fill_pct.thr", "prefill_chunk_fill_pct.lat"):
        assert read(name) == pytest.approx(100 * 2300 / (6 * 512))
    assert read("decode_rows_per_step.lat") == pytest.approx(
        (10 * 6 + 8) / 11)
    # the class (decode_stretch, 32 steps, no chunk) has 10 records, median
    # 300 ms: the one of 3300 ms is 3000 over it; the session's own stall
    # (tick 14) was not kept
    total = 9 * 300 + 3300 + 330 + 100
    assert read("slow_tick_share_pct.thr") == pytest.approx(
        100 * 3000 / total)


def test_none_where_there_is_nothing_to_read(reader):
    jr, starts = journal()
    # a window with no admission: nothing under the prompt metrics
    ctx, _ = context(jr, starts, session=False, window=(8, 12))
    assert reader.read(ctx, **spec("window_admit_ms_per_ktok.thr")) is None
    assert reader.read(ctx, **spec("prefill_chunk_fill_pct.thr")) is None
    assert reader.read(ctx, **spec("window_decode_ms_per_tok.thr")) > 0
    # fewer than 8 records of a class: no median, no slow tick
    assert reader.read(ctx, **spec("slow_tick_share_pct.thr")) == 0.0
    # no record in the window at all
    ctx, _ = context(jr, starts, window=(17, 17))
    assert reader.read(ctx, **spec("window_decode_ms_per_tok.thr")) is None
    # a program without a journal (the parent), or a window never closed
    ctx, _ = context(None, starts)
    assert reader.read(ctx, **spec("decode_rows_per_step.lat")) is None
    ctx, _ = context(jr, starts)
    ctx["clock"].closed = None
    assert reader.read(ctx, **spec("decode_rows_per_step.lat")) is None
