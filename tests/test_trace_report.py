"""``scripts/trace_report.py`` and the schema it parses.

``validate_jsonl`` (``--check``) holds an export to the typed vocabulary
``telemetry.EVENT_SCHEMA`` — every event of it is put to the validator
below, whole, short of one argument, and under a wrong category — and
the CLI must reproduce ``summarize_jsonl``'s summary from the file alone.
"""

import json
import os
import subprocess
import sys

import pytest

from flexflow_tpu.obs.report import validate_jsonl
from flexflow_tpu.obs.telemetry import EVENT_SCHEMA

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _run_raw(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=300, cwd=REPO, env=env, **kw)


def _export(tmp_path, name, cat, args):
    """A two-line export: the meta line and ONE typed instant."""
    path = tmp_path / "one.jsonl"
    path.write_text("\n".join([
        json.dumps({"kind": "telemetry_meta", "version": 1, "ts_unit": "us",
                    "events": 1, "dropped": 0}),
        json.dumps({"kind": "event", "name": name, "cat": cat, "ph": "i",
                    "pid": 1, "tid": 1, "ts": 1.0, "s": "t", "args": args}),
    ]) + "\n")
    return str(path)


@pytest.mark.parametrize("event", sorted(EVENT_SCHEMA))
def test_each_typed_event_is_held_to_its_schema(event, tmp_path):
    """Every event of the vocabulary meets the validator: under its
    declared category with exactly its required arguments it passes; short
    of one argument, or under another typed category, it is refused by an
    error that says which."""
    cat, required = EVENT_SCHEMA[event]
    assert required, f"{event} declares no required argument"
    args = {a: 1 for a in required}
    assert validate_jsonl(_export(tmp_path, event, cat, args)) == []

    dropped = required[-1]
    short = {a: 1 for a in required if a != dropped}
    [error] = validate_jsonl(_export(tmp_path, event, cat, short))
    assert event in error and repr(dropped) in error

    other = next(c for c in sorted({c for c, _ in EVENT_SCHEMA.values()})
                 if c != cat)
    [error] = validate_jsonl(_export(tmp_path, event, other, args))
    assert event in error and repr(cat) in error and repr(other) in error


def test_check_mode_rejects_schema_violations(tmp_path):
    script = os.path.join(REPO, "scripts", "trace_report.py")
    bad = tmp_path / "bad.jsonl"
    bad.write_text("\n".join([
        json.dumps({"kind": "telemetry_meta", "version": 1, "ts_unit": "us",
                    "events": 2, "dropped": 0}),
        # unknown lifecycle event name
        json.dumps({"kind": "event", "name": "request_vanish", "cat":
                    "request", "ph": "i", "pid": 1, "tid": 1, "ts": 1.0,
                    "s": "t", "args": {"trace_id": "r0"}}),
        # missing required arg (trace_id)
        json.dumps({"kind": "event", "name": "request_finish", "cat":
                    "request", "ph": "i", "pid": 1, "tid": 1, "ts": 2.0,
                    "s": "t", "args": {"n_tokens": 3}}),
        # unknown line kind
        json.dumps({"kind": "mystery"}),
    ]) + "\n")
    proc = _run_raw([script, "--check", str(bad)])
    assert proc.returncode == 1
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert not res["ok"]
    joined = " ".join(res["errors"])
    assert "request_vanish" in joined
    assert "trace_id" in joined
    assert "mystery" in joined

    # a meta-less file is flagged too (dropped counts are load-bearing)
    nometa = tmp_path / "nometa.jsonl"
    nometa.write_text(json.dumps({"kind": "metrics", "snapshot": {}}) + "\n")
    proc = _run_raw([script, "--check", str(nometa)])
    assert proc.returncode == 1
    assert "telemetry_meta" in proc.stdout


def test_truncated_trace_warns_loudly(tmp_path):
    """Satellite: a ring that dropped events must not masquerade as a
    complete trace — meta carries emitted/dropped and the CLI warns."""
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.obs.report import summarize_jsonl

    class Clock:
        t = 0.0

        def __call__(self):
            self.t += 1e-3
            return self.t

    tel = Telemetry(capacity=8, clock=Clock())
    for i in range(30):
        tel.request_enqueued(f"r{i:05d}", prompt_len=4)
    paths = tel.export(str(tmp_path))
    s = summarize_jsonl(paths["jsonl"])
    assert s["events"] == 30 and s["dropped"] == 22
    # Perfetto metadata carries the same accounting
    with open(paths["trace_json"]) as f:
        meta = json.load(f)["metadata"]
    assert meta["trace_events_emitted"] == 30
    assert meta["trace_events_dropped"] == 22
    # the CLI prints an explicit stderr warning (stdout stays pure JSON)
    proc = _run_raw([os.path.join(REPO, "scripts", "trace_report.py"),
                     paths["jsonl"]])
    assert proc.returncode == 0
    assert "TRUNCATED" in proc.stderr and "22" in proc.stderr
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == s


def test_trace_report_on_exported_telemetry(tmp_path):
    # library-level round trip (no subprocess): a hand-driven Telemetry
    # exports and the summary reflects exactly what was recorded
    from flexflow_tpu.obs import Telemetry
    from flexflow_tpu.obs.report import summarize_jsonl

    class Clock:
        t = 0.0

        def __call__(self):
            self.t += 0.5e-3
            return self.t

    tel = Telemetry(clock=Clock())
    t0 = tel.request_enqueued("rA", prompt_len=4)
    tel.request_admitted("rA")
    tel.request_prefill_started("rA")
    tel.request_first_token("rA", ttft_s=tel.now() - t0)
    first = tel.now()
    tel.request_finished("rA", n_tokens=3, tpot_s=(tel.now() - first) / 2)
    paths = tel.export(str(tmp_path))
    s = summarize_jsonl(paths["jsonl"])
    assert s["requests"] == 1 and s["completed"] == 1
    assert s["events"] == tel.trace.emitted and s["dropped"] == 0
    # 0.5ms per clock read: the enqueue instant is read #1 (ts 0.5ms) and
    # the first-token instant read #5 (ts 2.5ms) -> event-derived TTFT 2.0ms
    assert abs(s["ttft_p50_ms"] - 2.0) < 1e-6
