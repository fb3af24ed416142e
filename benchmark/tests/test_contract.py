"""BENCHMARK.json against the letter of the contract, and the files it
names."""

import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        names.append(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["config"] in names
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert m["moves"] in [e["name"] for e in b["end_to_end"]]
    metrics = b["end_to_end"] + b["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    all_names = [m["name"] for m in metrics]
    assert len(set(all_names)) == len(all_names)
    assert "setup_s" in all_names
    assert len(json.dumps(b)) < 64 * 1024


def test_every_named_file_is_there_and_cells_report_what_they_must():
    b = bench()
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for w in cells.values():
        mix_file = os.path.join(ROOT, "benchmark", "traffic",
                                w["traffic"] + ".json")
        with open(mix_file) as f:
            mix = json.load(f)
        with open(os.path.join(ROOT, configs[w["config"]]["file"])) as f:
            conf = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "reference", conf["model_type"] + ".py"))
        assert set(conf["benchmark"]["reduced"]) == set(
            configs[w["config"]]["reduced"])
        # every metric the traffic file reports is an end-to-end metric
        # that lists this cell, and the other way round
        listed = {m["name"] for m in b["end_to_end"]
                  if w["name"] in m.get("workloads", cells)}
        assert listed == set(mix["reports"]) | {"setup_s"}
        per_layer = [m for m in b["per_layer"]
                     if w["name"] in m.get("workloads", cells)]
        assert per_layer
        for m in per_layer:
            assert m["moves"] in listed
    for m in b["per_layer"]:
        spec_file = os.path.join(ROOT, "benchmark", "layer_metrics",
                                 m["name"] + ".json")
        with open(spec_file) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "benchmark",
                                           "layer_metrics", spec["reader"]))


def test_run_py_branches_on_no_name():
    """run.py may not mention a cell, a configuration, a traffic mix or a
    per-layer metric by name."""
    b = bench()
    with open(os.path.join(ROOT, "benchmark", "run.py")) as f:
        text = f.read()
    for entry in b["configs"] + b["workloads"] + b["per_layer"]:
        assert entry["name"] not in text, entry["name"]
    for w in b["workloads"]:
        assert f'"{w["traffic"]}"' not in text
