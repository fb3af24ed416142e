"""``layer_metrics/build_log_sum.py`` on events handed to jax's own
recorder: the seconds and the count of the named programs' builds before
the loop's zero, by kind; ``None`` where the program keeps no log."""

import json
import os
import types

import jax.monitoring
import pytest

from conftest import ROOT

from benchmark.run import load_module
from flexflow_tpu.obs import journal as J

METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
TRACE, LOWER, COMPILE = J.BUILD_WHATS
PROGRAMS = ["_toy_step_impl", "_toy_scan_impl"]


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(METRICS, "build_log_sum.py"))


def context(t0):
    lines = []
    return dict(clock=types.SimpleNamespace(t0=t0), log=lines.append), lines


@pytest.fixture()
def built(monkeypatch):
    """A set-up's builds on a clock of their own: two programs (one built
    for two signatures, its second compile answered by the cache), another
    process's program, and a recompile after the loop's zero at 100 s."""
    now = [0]
    log = J.build_log()
    monkeypatch.setattr(log, "clock_ns", lambda: now[0])
    n0 = log.emitted

    def emit(t_s, what, secs, name, hit=False):
        now[0] = int(t_s * 1e9)
        if hit:
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        jax.monitoring.record_event_duration_secs(what, secs, fun_name=name)

    emit(10, TRACE, 1.0, "_toy_step_impl")
    emit(11, LOWER, 0.5, "jit(_toy_step_impl)")
    emit(15, COMPILE, 4.0, "jit(_toy_step_impl)")
    for t, hit in ((20, False), (30, True)):
        emit(t, TRACE, 2.0, "_toy_scan_impl")
        emit(t + 1, LOWER, 0.25, "jit(_toy_scan_impl)")
        emit(t + 2, COMPILE, 0.125 if hit else 8.0, "jit(_toy_scan_impl)",
             hit=hit)
    emit(40, COMPILE, 64.0, "jit(_reference_forward)")
    emit(130, TRACE, 16.0, "_toy_scan_impl")
    emit(131, COMPILE, 32.0, "jit(_toy_scan_impl)")
    assert log.emitted == n0 + 12
    return log


def test_the_committed_files(reader):
    for name, count in (("program_build_s.setup", False),
                        ("program_builds.setup", True)):
        with open(os.path.join(METRICS, name + ".json")) as f:
            s = json.load(f)
        assert s["reader"] == "build_log_sum.py" and s["what"]
        assert s["args"].get("count", False) is count
        assert set(s["args"]["what"]) <= set(J.BUILD_WHATS.values())
        assert s["args"]["programs"] == [
            "_step_impl", "_decode_scan_impl", "_prefill_scan_impl",
            "_join_impl"]
    assert s["args"]["what"] == ["compile"]


def test_sum_count_and_the_cut_before_the_loop(reader, built):
    every = ["trace", "lower", "compile"]
    ctx, lines = context(100.0)
    assert reader.read(ctx, every, PROGRAMS) == pytest.approx(
        1.0 + 0.5 + 4.0 + 2 * (2.0 + 0.25) + 8.0 + 0.125)
    assert reader.read(ctx, ["compile"], PROGRAMS, count=True) == 3
    assert reader.read(ctx, ["compile"], PROGRAMS) == pytest.approx(12.125)
    assert reader.read(ctx, every, ["_toy_step_impl"], count=True) == 3
    assert reader.read(ctx, ["lower"], ["_no_such_program"]) == 0.0
    # the first reading logged the table by program, once
    assert len(lines) == 1 and lines[0].startswith("build log: ")
    assert "_toy_scan_impl compile 2 in 8.12s (1 cached), lower 2 in " \
        "0.50s, trace 2 in 4.00s" in lines[0]
    assert "_reference_forward" not in lines[0]
    # a later zero takes the recompile in; one before any build, nothing
    ctx, _ = context(200.0)
    assert reader.read(ctx, ["compile"], PROGRAMS, count=True) == 4
    assert reader.read(ctx, every, PROGRAMS) == pytest.approx(66.125)
    ctx, _ = context(5.0)
    assert reader.read(ctx, every, PROGRAMS, count=True) == 0


def test_none_without_a_log_or_a_loop(reader, built, monkeypatch):
    ctx, _ = context(None)
    assert reader.read(ctx, ["compile"], PROGRAMS) is None
    # a program that keeps no build log (the parent)
    monkeypatch.delattr(J, "builds")
    ctx, lines = context(100.0)
    assert reader.read(ctx, ["compile"], PROGRAMS) is None
    assert reader.read(ctx, ["compile"], PROGRAMS, count=True) is None
    assert not lines
