"""``xplane_spans`` and the readers built on it.

Three traces: the one PR 26 recorded (no scope, no span: what the parent
commit's program gives — every new reader must return None on it, not
raise); one recorded on the v5e after ISSUE 27 (``scoped_stretch``: a 0.2 s
span of ``opt-6.7b-d12.decode-heavy``, recorded by run.py itself), whose
numbers are written below as that run printed them; and one made by hand,
for the arithmetic of idle time under spans."""

import gzip
import importlib.util
import os
import shutil

import pytest

from conftest import DATA, ROOT

from benchmark import xplane_spans as xs
from benchmark.serve_loop import Stamp


def _unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp(name) / (name + ".xplane.pb")
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return str(path)


@pytest.fixture(scope="module")
def old_path(tmp_path_factory):
    return _unpacked(tmp_path_factory, "decode_stretch")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Clock:
    trace_at = (Stamp(0.0, 100, 0, 0), Stamp(1.5, 356, 0, 0))


def _ctx(path, logged):
    return dict(xplane=path, clock=_Clock, log=logged.append)


SCOPE_ARGS = dict(programs=["_decode_scan_impl"], stages=["kv_write"],
                  tokens=["scan_tokens"])


def test_reads_tf_op_and_the_host_plane(old_path):
    trace = xs.load(old_path)
    assert xs.load(old_path) is trace            # parsed once per path
    ops = trace.device_ops()
    # the same events, on the same time base, as trace_reduce's reader
    from benchmark import trace_reduce

    ref = sorted(trace_reduce.read_planes(old_path)["/device:TPU:0"][
        "XLA Ops"], key=lambda e: e[1])
    assert len(ops) == len(ref) == 24316
    assert all(abs(o.start_ns - e[1]) < 1 and abs(o.dur_ns - e[2]) < 1
               for o, e in zip(ops, ref))
    assert trace.programs()[-1][0] == "_decode_scan_impl"
    # XLA charges the copies to the KV scatter write, not to the scan carry
    copies = [o for o in ops if o.name == "copy"]
    under_scatter = sum(o.dur_ns for o in copies
                        if o.scope.endswith("/scatter")) / 1e9
    assert under_scatter == pytest.approx(0.355, rel=0.01)
    assert sum(o.dur_ns for o in copies) / 1e9 == pytest.approx(0.386,
                                                                rel=0.01)
    assert any(o.scope == "jit(_decode_scan_impl)/while/body/closed_call/"
               "dot_general" for o in ops)
    names = {h.name for h in trace.host_spans()}
    assert "PjitFunction(_decode_scan_impl)" in names
    assert "PJRT_LoadedExecutable_Execute" in names


def test_a_trace_without_scopes_or_spans_reads_none(old_path):
    """The parent commit's program: no node scope, no scheduler span."""
    trace = xs.load(old_path)
    assert not xs.has_node_scopes(trace)
    assert xs.program_spans(trace) == []
    assert xs.committed_tokens(trace) is None
    assert xs.idle_by_span(trace) is None
    table, named = xs.by_scope(trace)
    assert named == 0 and set(table) == {xs.NO_SCOPE}
    logged = []
    assert _reader("scope_ms_per_tok").read(
        _ctx(old_path, logged), **SCOPE_ARGS) is None
    assert any("no graph-node scope in the trace: stale executable"
               in m for m in logged)
    assert _reader("scope_ms_per_tok").read(
        _ctx(old_path, logged), programs=["_decode_scan_impl"],
        tokens=["scan_tokens"]) is None
    assert _reader("host_gap_ms_per_tok").read(_ctx(old_path, logged)) is None


def test_scope_of_and_label():
    op = xs.Op("copy", 0, 1, "jit(_decode_scan_impl)/while/body/closed_call/"
               "IncMultiHeadSelfAttention.layers_3_attn/attend/kv_write/"
               "scatter")
    assert xs.scope_of(op) == ("IncMultiHeadSelfAttention.layers_3_attn",
                               "kv_write")
    assert xs.label_of(op) == "kv_write"
    op = xs.Op("fusion", 0, 1, "jit(_step_impl)/Linear.model.layers.0.mlp."
               "up_proj/dot_general")
    assert xs.scope_of(op) == ("Linear.model.layers.0.mlp.up_proj", None)
    assert xs.label_of(op) == "Linear"
    # a stage of the step program, outside any node; a later node resets it
    assert xs.label_of(xs.Op("f", 0, 1, "jit(_step_impl)/sample/argmax")) \
        == "sample"
    assert xs.label_of(xs.Op("f", 0, 1, "jit(x)/jit(_take)/select_n")) \
        == xs.NO_SCOPE


def _hand_made(path):
    """One chip, one host thread.  Device busy 10-20 and 50-60 (two ops of
    one decode scan; the first under kv_write, the second under a Linear
    node); ticks 0-100: a decode_stretch 5-70 holding host_prepare 5-9, a
    launch 9-12, readback 12-62, commit 62-68 (scan_tokens 4); loop_bookkeep
    72-90."""
    space = xs._xspace()()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    for mid, name, scope in (
            (1, "%copy.1 = x", "jit(_decode_scan_impl)/while/body/"
             "IncMultiHeadSelfAttention.l0/attend/kv_write/scatter:"),
            (2, "%fusion.2 = y", "jit(_decode_scan_impl)/while/body/"
             "Linear.l0_fc1/dot_general:"),
            (3, "jit__decode_scan_impl(1)", None)):
        m = dev.event_metadata[mid]
        m.id, m.name = mid, name
        if scope:
            m.stats.add(metadata_id=1, str_value=scope)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=10_000, duration_ps=10_000)
    line.events.add(metadata_id=2, offset_ps=50_000, duration_ps=10_000)
    line = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    line.events.add(metadata_id=3, offset_ps=10_000, duration_ps=50_000)
    host = space.planes.add(name="/host:CPU")
    for i, n in enumerate(("scan_tokens", "join_tokens", "steps"), 1):
        host.stat_metadata[i].name = n
    line = host.lines.add(name="python", timestamp_ns=1000)
    for mid, (name, lo, hi, stats) in enumerate((
            ("decode_stretch", 5, 70, {3: 8}), ("host_prepare", 5, 9, {}),
            ("decode_scan_dispatch", 9, 12, {}), ("readback", 12, 62, {}),
            ("commit", 62, 68, {1: 4, 2: 0}),
            ("loop_bookkeep", 72, 90, {}),
            ("PjitFunction(_decode_scan_impl)", 9, 11, {})), 1):
        host.event_metadata[mid].id = mid
        host.event_metadata[mid].name = name
        e = line.events.add(metadata_id=mid, offset_ps=lo * 1000,
                            duration_ps=(hi - lo) * 1000)
        for k, v in stats.items():
            e.stats.add(metadata_id=k, int64_value=v)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    return str(path)


def test_idle_under_spans_and_readers_on_a_hand_made_trace(tmp_path):
    path = _hand_made(tmp_path / "hand.xplane.pb")
    trace = xs.load(path)
    assert [(h.name, h.args) for h in xs.program_spans(trace)][:2] == [
        ("decode_stretch", {"steps": 8}), ("host_prepare", {})]
    assert xs.committed_tokens(trace) == 4
    # spans run 5..90; busy 10-20, 50-60: idle 5-10, 20-50, 60-90
    idle = {k: round(v * 1e9, 6) for k, v in xs.idle_by_span(trace).items()}
    assert idle == {"host_prepare": 4, "decode_scan_dispatch": 1,
                    "readback": 30 + 2, "commit": 6, "decode_stretch": 2,
                    xs.NO_SPAN: 2, "loop_bookkeep": 18}
    logged = []
    gap = _reader("host_gap_ms_per_tok").read(_ctx(path, logged))
    assert gap == pytest.approx(1e3 * 63e-9 / 4)
    text = "\n".join(logged)
    assert "under a span below the tick" in text and "traced span" in text
    # (4 + 1 + 32 + 6 + 18) of 65 idle ns lie under a span below the tick
    assert "idle: 93.85% of" in text and "scopes: 100.00% of" in text
    read = _reader("scope_ms_per_tok").read
    assert read(_ctx(path, logged), **SCOPE_ARGS) \
        == pytest.approx(10e-9 * 1e3 / 4)
    assert read(_ctx(path, logged), programs=["_decode_scan_impl"],
                nodes=["Linear"], tokens=["scan_tokens"]) \
        == pytest.approx(10e-9 * 1e3 / 4)
    assert read(_ctx(path, logged), programs=["_decode_scan_impl"],
                tokens=["scan_tokens", "join_tokens"]) \
        == pytest.approx(50e-9 * 1e3 / 4)
    # operations outside the named programs, tokens of another kind: None
    assert read(_ctx(path, logged), **dict(SCOPE_ARGS,
                                           programs=["_step_impl"])) is None
    assert read(_ctx(path, logged), **dict(SCOPE_ARGS,
                                           tokens=["step_tokens"])) is None


# -- the trace recorded after ISSUE 27 ----------------------------------------
@pytest.fixture(scope="module")
def new_path(tmp_path_factory):
    return _unpacked(tmp_path_factory, "scoped_stretch")


def _metric(name, ctx):
    """A metric of BENCHMARK.json, read as run.py reads it."""
    import json

    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    return _reader(spec["reader"][:-3]).read(ctx, **spec.get("args", {}))


# what the run that recorded the trace printed (my chip run, PR 27: one
# 32-step decode stretch, 8 rows, 256 tokens, all of them the scan's)
RECORDED = {
    "kv_write_dev_ms_per_tok.thr": 1.5441616558437516,
    "dense_dev_ms_per_tok.thr": 1.933727182984371,
    "decode_scan_dev_ms_per_tok.lat": 5.768263276367188,
    "host_gap_ms_per_tok.lat": 0.025585935742186816,
    "host_gap_ms_per_tok.thr": 0.025585935742186816,
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_readers_on_the_scoped_trace(new_path, name):
    assert _metric(name, _ctx(new_path, [])) \
        == pytest.approx(RECORDED[name], rel=1e-9)


def test_tables_of_the_scoped_trace(new_path):
    trace = xs.load(new_path)
    table, named = xs.by_scope(trace)
    total = sum(table.values())
    assert named / total == pytest.approx(0.9769, abs=1e-4)
    # the kernel, then the KV write with the copies XLA makes for it
    assert table["attend"] == pytest.approx(0.54826, abs=1e-5)
    assert table["kv_write"] == pytest.approx(0.39531, abs=1e-5)
    assert table["Linear"] + table["qkv_proj"] + table["o_proj"] \
        == pytest.approx(0.49503, abs=1e-5)
    copies = [o for o in trace.device_ops() if o.name == "copy"]
    under_write = sum(o.dur_ns for o in copies
                      if xs.label_of(o) == "kv_write")
    assert under_write / sum(o.dur_ns for o in copies) > 0.9
    spans = xs.program_spans(trace)
    launch = [h for h in spans if h.name == "decode_scan_dispatch"]
    assert [h.args for h in launch] == [{
        "kind": "decode_scan", "n_steps": 32, "rows": 8,
        "prompt_tokens": 0, "ctx_sum": 1416}]
    assert xs.committed_tokens(trace, ["scan_tokens"]) == 256
    idle = xs.idle_by_span(trace)
    below = sum(v for k, v in idle.items()
                if k not in xs.TICKS and k != xs.NO_SPAN)
    assert below / sum(idle.values()) == pytest.approx(0.9557, abs=1e-4)
    # the tick's perf_counter reading: the device's work of that tick lies
    # after it on the trace's clock
    [tick] = [h for h in spans if h.name == "decode_stretch"]
    assert tick.args["pc_ns"] > 0 and tick.args["steps"] == 32
    scan = [p for p in trace.programs() if p[0] == "_decode_scan_impl"]
    assert tick.start_ns < scan[0][1] < tick.start_ns + tick.dur_ns


def test_a_trace_stripped_of_node_scopes_reads_none(new_path, tmp_path):
    """A stale executable (compiled before the scopes, served again by the
    compile cache): the spans are there, the scopes are not."""
    with open(new_path, "rb") as f:
        space = xs._xspace().FromString(f.read())
    for plane in space.planes:
        if plane.name.startswith("/device:"):
            for meta in plane.event_metadata.values():
                del meta.stats[:]
    path = str(tmp_path / "stripped.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())
    logged = []
    for name in ("kv_write_dev_ms_per_tok.thr", "dense_dev_ms_per_tok.thr"):
        assert _metric(name, _ctx(path, logged)) is None
    assert sum("no graph-node scope in the trace: stale executable from "
               "the compile cache?" in m for m in logged) == 2
    # what does not read scopes still reads
    assert _metric("decode_scan_dev_ms_per_tok.lat", _ctx(path, logged)) \
        == pytest.approx(RECORDED["decode_scan_dev_ms_per_tok.lat"])
    assert _metric("host_gap_ms_per_tok.lat", _ctx(path, logged)) \
        == pytest.approx(RECORDED["host_gap_ms_per_tok.lat"])
