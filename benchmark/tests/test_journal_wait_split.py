"""The two ratios that split the window's wall time by what the host waited
for (``window_device_wait_ms_per_tok.thr`` + ``window_host_ms_per_tok.thr``,
reader ``layer_metrics/journal_wait_split.py``): their files name fields the
journal has, together they are the kept records' wall time per token, and
on a program whose journal has no ``device_wait_ns`` they read nothing."""

import json
import os
import types

import pytest

from conftest import ROOT
from test_journal_ratio import MS, Clock, context

from benchmark.run import load_module
from benchmark.serve_loop import Stamp
from flexflow_tpu.obs import journal as J
from flexflow_tpu.obs.journal import TickJournal
from flexflow_tpu.obs.trace import Span

METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
WAIT, HOST = ("window_device_wait_ms_per_tok.thr",
              "window_host_ms_per_tok.thr")
TOKENS = ["scan_tokens", "join_tokens", "step_tokens", "prefill_tokens"]
# one tick a row: (kind, launch ms, device_wait ms, copy ms, commits)
TICKS = ([("decode_stretch", 2, 290, 1, dict(scan_tokens=190, join_tokens=2))]
         * 9
         + [("prefill_stretch", 40, 55, 2, dict(prefill_tokens=3)),
            ("decode_stretch", 2, 3290, 1, dict(scan_tokens=192)),
            ("decode_stretch", 2, 290, 3001, dict(scan_tokens=192)),
            ("serve_step", 5, 20, 1, dict(step_tokens=8))])


def spec(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        s = json.load(f)
    assert s["reader"] == "journal_wait_split.py" and s["what"]
    return s["args"]


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(METRICS, "journal_wait_split.py"))


def journal():
    clock = Clock()
    jr = TickJournal(chunk_width=512, clock_ns=clock.ns)
    starts = []
    for kind, launch, wait, copy, commits in TICKS:
        starts.append(clock.t)
        with Span("loop_arrivals", jr=jr):
            clock.t += 1 * MS
        jr.begin(pending=40, live=8)
        clock.t += 1 * MS                      # under no span at all
        with Span(kind, {"pc_ns": clock.ns()}, jr=jr):
            with Span("decode_scan_dispatch", dict(n_steps=32, rows=6),
                      jr=jr):
                clock.t += launch * MS
            with Span("readback", jr=jr):
                with Span("device_wait", jr=jr):
                    clock.t += wait * MS
                clock.t += copy * MS
            with Span("commit", jr=jr) as sp:
                sp.set(**commits)
                clock.t += 2 * MS
    starts.append(clock.t)
    jr.end()
    return jr, starts


@pytest.mark.parametrize("name", [WAIT, HOST])
def test_the_files_name_fields_the_journal_has(name):
    args = spec(name)
    assert args["den"] == TOKENS and args["scale"] == 1e-06
    assert set(args) == {"num", "den", "scale"}
    for field in args["num"] + args["den"]:
        assert field in J.FIELDS
    if name == WAIT:
        assert args["num"] == ["device_wait_ns"]
    else:
        # every span of the split but the wait, and what no span covers
        assert sorted(args["num"]) == sorted(
            [f"{n}_ns" for n in J.SPLIT if n != "device_wait"]
            + ["unattributed_ns"])


def test_the_two_add_up_to_the_window_s_wall_time_per_token(reader):
    jr, starts = journal()
    ctx, lines = context(jr, starts, session=False,
                         window=(0, len(TICKS)))
    wait = reader.read(ctx, **spec(WAIT))
    host = reader.read(ctx, **spec(HOST))
    rows = jr.array()
    extent = int(J.extent_ns(rows).sum())
    tokens = int(sum(rows[:, J.FIELDS.index(f)].sum() for f in TOKENS))
    assert tokens == 9 * 192 + 3 + 192 + 192 + 8
    assert wait + host == pytest.approx(extent / tokens / 1e6, rel=1e-12)
    assert wait == pytest.approx(
        (9 * 290 + 55 + 3290 + 290 + 20) / tokens)
    # the host's share: launches, copies, commits, the loop's span and the
    # millisecond under no span, of every tick
    assert host == pytest.approx(
        (9 * (2 + 1) + (40 + 2) + (2 + 1) + (2 + 3001) + (5 + 1)
         + len(TICKS) * (1 + 1 + 2)) / tokens)
    assert len(lines) == 1 and f"in {len(TICKS)} records" in lines[0]
    # inside a window with a profiler session the same records are kept
    # as ``journal_ratio.py`` keeps
    ratio = load_module(os.path.join(METRICS, "journal_ratio.py"))
    ctx, _ = context(jr, starts, session=False, window=(2, len(TICKS)))
    ctx["clock"].trace_at = (Stamp((starts[5] + 200 * MS) / 1e9, 0, 0, 0),
                             None)
    ctx["clock"].tracer = types.SimpleNamespace(
        t_stop=(starts[7] + 250 * MS) / 1e9)
    assert reader.read(ctx, **spec(WAIT)) == ratio.read(ctx, **spec(WAIT))
    assert len(ratio.kept_records(ctx)[2]) == len(TICKS) - 2 - 3


def test_nothing_is_read_where_the_journal_keeps_no_device_wait(
        reader, monkeypatch):
    jr, starts = journal()
    ctx, lines = context(jr, starts, session=False, window=(0, len(TICKS)))
    assert reader.read(ctx, **spec(HOST)) > 0
    # the parent's journal: ``readback_ns`` holds the wait and the copies
    monkeypatch.setattr(J, "FIELDS", tuple(
        f for f in J.FIELDS if f != "device_wait_ns"))
    ctx, lines = context(jr, starts, session=False, window=(0, len(TICKS)))
    assert reader.read(ctx, **spec(WAIT)) is None
    assert reader.read(ctx, **spec(HOST)) is None
    assert not lines
