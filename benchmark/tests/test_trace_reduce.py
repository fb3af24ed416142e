"""The reducer on a small recorded trace: one 32-step decode stretch of
``opt-6.7b-d12.decode-heavy`` on a TPU v5e (recorded by run.py itself with a
0.2 s span, PR 26; 8 live rows).  The same run printed
``decode_dev_ms_per_tok.thr`` 5.76865 and ``decode_attention_roofline.thr``
2.19045, which the readers must give again from the file."""

import gzip
import importlib.util
import os
import shutil

import pytest

from conftest import DATA, ROOT

from benchmark import trace_reduce
from benchmark.serve_loop import Stamp


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "decode_stretch.xplane.pb"
    with gzip.open(os.path.join(DATA, "decode_stretch.xplane.pb.gz")) as f, \
            open(path, "wb") as g:
        shutil.copyfileobj(f, g)
    return trace_reduce.reduce_trace(str(path))


def test_names():
    assert trace_reduce.program_name(
        "jit__decode_scan_impl(169448232681322745)") == "_decode_scan_impl"
    assert trace_reduce.op_name(
        "%decode_attention.36 = bf16[512,32,1,128]{3,2,1,0} custom-call("
        "s32[512]{0} %copy-done.128)") == "decode_attention"
    assert trace_reduce.op_name("%while = (s32[], bf16[9,32]) while(%t)") \
        == "while"
    assert trace_reduce.op_name("%copy-done.128 = x") == "copy-done"


def test_programs_ops_busy_gaps(reduced):
    [chip] = reduced["chips"]
    secs, runs = trace_reduce.program_seconds(chip, {"_decode_scan_impl"})
    assert runs == 1 and secs == pytest.approx(1.4768, abs=1e-3)
    # 12 layers x 32 steps, one kernel call each
    secs, calls = trace_reduce.op_seconds(chip, "decode_attention")
    assert calls == 12 * 32 and secs == pytest.approx(0.542880, abs=1e-6)
    assert chip["busy_s"] == pytest.approx(1.476772639, abs=1e-6)
    assert chip["busy_s"] <= chip["device_span_s"]
    # the cache copies PR 24 saw as 4.85 GB of temporaries: K and V of 12
    # layers, every step
    assert chip["ops"]["copy"][0] >= 24 * 32
    out = trace_reduce.breakdown(reduced)
    assert out["device_ops"][0][0] == "decode_attention"
    assert "while" not in [n for n, _ in out["device_ops"]]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert all(t >= 0 for _, t in out["idle_gaps"])


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Clock:
    # what the host counted around that span: 8 rows, 32 tokens each
    trace_at = (Stamp(0.0, 100, 0, 0), Stamp(1.5, 356, 0, 0))


class _Cfg:
    num_hidden_layers, num_attention_heads, kv_heads, hdim = 12, 32, 32, 128


class _LLM:
    config = _Cfg


def test_readers_on_the_recorded_span(reduced):
    ctx = dict(reduced=reduced, clock=_Clock, log=lambda m: None, llm=_LLM,
               dep={"compile": {"max_requests": 8}},
               peak={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    ms = _reader("program_ms_per_unit").read(
        ctx, programs=["_decode_scan_impl", "_join_impl"],
        per="generated_token")
    assert ms == pytest.approx(5.76865, abs=1e-3)
    assert _reader("program_ms_per_unit").read(
        ctx, programs=["_prefill_scan_impl"], per="prompt_ktoken") is None
    # 8 requests of (prompt, generated) lengths as that run had them give
    # the run's own 2.19 %; here: the share must sit under 100 and scale
    # with the rows' contexts
    _Clock.trace_lens = ({i: (100, 10) for i in range(8)},
                         {i: (100, 42) for i in range(8)})
    args = dict(op="decode_attention", inside=["_decode_scan_impl"],
                flat=["_step_impl"])
    share = _reader("kernel_roofline_pct").read(ctx, **args)
    rows = [100 + g for g in range(10, 42)] * 8
    nbytes = 12 * (2 * 32 * 128 * 2 * sum(rows) + 2 * len(rows) * 32 * 128 * 2)
    assert share == pytest.approx(100 * (nbytes / 819e9) / 0.542880, rel=1e-6)
    assert 0 < share < 100
    # the kernel's events elsewhere than in the named programs do not count
    assert _reader("kernel_roofline_pct").read(
        ctx, **dict(args, inside=["_step_impl"])) is None


def test_the_kernel_is_read_inside_the_scan_only():
    """A hand-made span: one decode scan with two kernel events, one flat
    step with one (a prompt's rows).  The flat step's event is left out of
    the time, and the rows it may have made (its count x the slots, the
    longest contexts first) out of the least work."""
    chip = {"programs": [("_decode_scan_impl", 1.0, 1.0),
                         ("_step_impl", 2.5, 0.5)],
            "op_events": [("decode_attention", 1.1, 0.2),
                          ("decode_attention", 1.6, 0.2),
                          ("decode_attention", 2.6, 0.4),
                          ("fusion", 1.3, 0.1)]}
    assert trace_reduce.op_seconds_inside(
        chip, "decode_attention", {"_decode_scan_impl"}) == (0.4, 2)
    assert trace_reduce.op_seconds_inside(
        chip, "decode_attention", {"_step_impl"}) == (0.4, 1)

    class Clock:
        trace_lens = ({0: (10, 1), 1: (20, 1)}, {0: (10, 4), 1: (20, 4)})

    logged = []
    ctx = dict(reduced={"chips": [chip]}, clock=Clock, log=logged.append,
               llm=_LLM, dep={"compile": {"max_requests": 2}},
               peak={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    share = _reader("kernel_roofline_pct").read(
        ctx, op="decode_attention", inside=["_decode_scan_impl"],
        flat=["_step_impl"])
    # six decode rows (contexts 11-13, 21-23); the one flat step may have
    # made two: the two longest go
    rows = [11, 12, 13, 21]
    nbytes = 12 * (2 * 32 * 128 * 2 * sum(rows) + 2 * len(rows) * 32 * 128 * 2)
    assert share == pytest.approx(100 * (nbytes / 819e9) / 0.4, rel=1e-9)
    assert "4 decode rows" in logged[0]
