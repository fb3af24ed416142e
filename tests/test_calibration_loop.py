"""Continuous calibration: ledger aggregation, the persisted store, and
the search auto-apply path (ISSUE 6 tentpole piece 1 + satellites).

Pins:
* geometric-mean ``suggested_scale`` + ``low_confidence`` on the ledger;
* CalibrationStore EWMA/clamp/min-sample/persistence semantics, incl.
  missing + malformed files degrading to the empty store;
* ``MachineModel.with_calibration`` fallback paths (missing file,
  malformed JSON, partial keys keep spec defaults) and its COMPOSITION
  with ``with_store`` (the auto-apply path must not clobber a measured
  constants file);
* the loop end to end: a mis-scaled machine's prediction error shrinks
  after the store is committed and auto-applied by ``search_serve_plan``.
"""

import dataclasses
import json
import os

import jax
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.obs import CalibrationLedger, CalibrationStore, StoreConfig
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.search.machine_model import TPU_SPECS, MachineModel
from flexflow_tpu.search.serve_search import price_plan, search_serve_plan
from flexflow_tpu.serve import build_model
from flexflow_tpu.serve.inference_manager import register_serve_capacities
from flexflow_tpu.serve.models.base import ServeModelConfig


# ---------------------------------------------------------------------------
# ledger aggregation (satellite: geometric mean + low_confidence)
# ---------------------------------------------------------------------------
def test_suggested_scale_is_geometric_mean():
    led = CalibrationLedger()
    # ratios 0.5 and 2.0: multiplicative errors that cancel — the
    # arithmetic mean would suggest 1.25 (over-weighting the overshoot)
    led.predict("a", tpot_ms=2.0)
    led.measure("a", tpot_ms=1.0)
    led.predict("b", tpot_ms=2.0)
    led.measure("b", tpot_ms=4.0)
    comp = led.report()["components"]["tpot_ms"]
    assert comp["suggested_scale"] == 1.0
    assert comp["mean_ratio"] == 1.0
    assert comp["n"] == 2 and not comp["low_confidence"]


def test_single_pair_flagged_low_confidence():
    led = CalibrationLedger()
    led.predict("a", x_ms=1.0)
    led.measure("a", x_ms=1.3)
    comp = led.report()["components"]["x_ms"]
    assert comp["low_confidence"] and comp["n"] == 1
    assert abs(comp["suggested_scale"] - 1.3) < 1e-9


def test_non_positive_ratio_stays_visible_but_not_aggregated():
    led = CalibrationLedger()
    led.predict("a", d_ms=2.0)
    led.measure("a", d_ms=-1.0)  # a sign bug in a recorded field
    rep = led.report()
    assert rep["plans"]["a"]["d_ms"]["ratio"] == -0.5
    assert "d_ms" not in rep["components"]


# ---------------------------------------------------------------------------
# the persisted store
# ---------------------------------------------------------------------------
def _one_run_report(ratio, n=2):
    led = CalibrationLedger()
    for i in range(n):
        led.predict(f"p{i}", tpot_ms=1.0)
        led.measure(f"p{i}", tpot_ms=ratio)
    return led.report()


def test_store_ewma_clamp_gate_and_persistence(tmp_path):
    path = str(tmp_path / "store.json")
    store = CalibrationStore(path, StoreConfig(ewma_alpha=0.5, min_samples=3,
                                               scale_max=4.0))
    # run 1: n=2 < min_samples -> recorded but NOT applied
    store.update(_one_run_report(2.0, n=2))
    assert store.scale_for("tpot_ms") == 1.0
    assert store.scales() == {}
    # run 2 clears the gate; EWMA blends toward the new suggestion
    store.update(_one_run_report(3.0, n=2))
    assert store.scale_for("tpot_ms") == pytest.approx(2.5)  # .5*2 + .5*3
    # a wild 100x outlier is clamped BEFORE blending
    store.update(_one_run_report(100.0, n=2))
    assert store.scale_for("tpot_ms") == pytest.approx(0.5 * 2.5 + 0.5 * 4.0)
    # round trip through disk preserves scales, counts, run count
    store.save()
    again = CalibrationStore.load(path, StoreConfig(min_samples=3))
    assert again.runs == 3
    assert again.scale_for("tpot_ms") == store.scale_for("tpot_ms")
    assert again.components["tpot_ms"]["n"] == 6


def test_store_missing_and_malformed_files_load_empty(tmp_path):
    missing = CalibrationStore.load(str(tmp_path / "nope.json"))
    assert not missing and missing.scale_for("anything") == 1.0
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert not CalibrationStore.load(str(bad))
    # structurally wrong but valid JSON: entries without scales are skipped
    weird = tmp_path / "weird.json"
    weird.write_text(json.dumps(
        {"runs": "x", "components": {"a": 1, "b": {"n": 5}}}))
    st = CalibrationStore.load(str(weird))
    assert not st and st.scale_for("a") == 1.0


def test_ledger_commit_into_store(tmp_path):
    led = CalibrationLedger()
    for i, m in enumerate((1.4, 1.6)):
        led.predict(f"p{i}", tpot_ms=1.0)
        led.measure(f"p{i}", tpot_ms=m)
    store = CalibrationStore(str(tmp_path / "s.json"),
                             StoreConfig(min_samples=2))
    view = led.commit(store)
    assert view["tpot_ms"]["applied"]
    # geomean of 1.4, 1.6
    assert store.scale_for("tpot_ms") == pytest.approx((1.4 * 1.6) ** 0.5,
                                                       rel=1e-3)


# ---------------------------------------------------------------------------
# MachineModel.with_calibration fallback pins (satellite 3)
# ---------------------------------------------------------------------------
def _mm():
    return MachineModel(TPU_SPECS["cpu"])


def test_with_calibration_missing_file_keeps_defaults(tmp_path):
    mm = _mm()
    out = mm.with_calibration(str(tmp_path / "absent.json"))
    assert out.spec == mm.spec  # silently unchanged — pinned behavior


def test_with_calibration_malformed_json_keeps_defaults(tmp_path):
    p = tmp_path / "broken.json"
    p.write_text("{{{{")
    out = _mm().with_calibration(str(p))
    assert out.spec == TPU_SPECS["cpu"]


def test_with_calibration_partial_keys_merge_over_defaults(tmp_path):
    p = tmp_path / "partial.json"
    p.write_text(json.dumps({"step_overhead": 7e-6, "unknown_key": 123}))
    out = _mm().with_calibration(str(p))
    assert out.spec.step_overhead == 7e-6            # measured key lands
    assert out.spec.mxu_efficiency == TPU_SPECS["cpu"].mxu_efficiency
    assert not hasattr(out.spec, "unknown_key")


def test_with_store_composes_with_measured_constants(tmp_path):
    """The auto-apply path must STACK on a measure.calibrate_machine_constants
    file, not clobber it: measured constants load first, store drift
    corrections multiply on top."""
    calib = tmp_path / "tpu_calib.json"
    calib.write_text(json.dumps({"step_overhead": 10e-6,
                                 "mxu_efficiency": 0.8}))
    store = CalibrationStore(str(tmp_path / "s.json"),
                             StoreConfig(min_samples=1))
    led = CalibrationLedger()
    led.predict("p", step_overhead=1.0)
    led.measure("p", step_overhead=2.0)   # machine 2x slower than modeled
    led.commit(store)
    mm = _mm().with_calibration(str(calib)).with_store(store)
    # measured constant survived AND the store scaled it (time-like: x2)
    assert mm.spec.step_overhead == pytest.approx(20e-6)
    # untouched constants: measured value for mxu (no store component)
    assert mm.spec.mxu_efficiency == 0.8
    # empty/None stores are no-ops
    assert _mm().with_store(None).spec == TPU_SPECS["cpu"]
    empty = CalibrationStore(str(tmp_path / "none.json"))
    assert _mm().with_store(empty).spec == TPU_SPECS["cpu"]


def test_with_store_rate_constants_divide():
    store = CalibrationStore("/dev/null/never", StoreConfig(min_samples=1))
    led = CalibrationLedger()
    led.predict("p", hbm_bandwidth=1.0)
    led.measure("p", hbm_bandwidth=2.0)  # times 2x longer -> rate halves
    led.commit(store)
    mm = _mm().with_store(store)
    assert mm.spec.hbm_bandwidth == pytest.approx(
        TPU_SPECS["cpu"].hbm_bandwidth / 2.0)


# ---------------------------------------------------------------------------
# the loop end to end through search_serve_plan
# ---------------------------------------------------------------------------
def _serve_graph():
    cfg = ServeModelConfig(
        model_type="llama", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=256)
    ff = FFModel(FFConfig(), mesh=make_mesh({"tp": 1}, jax.devices()[:1]))
    build_model(ff, cfg, max_tokens=16)
    register_serve_capacities(ff.graph, max_requests=8, max_seq_len=256)
    return ff


def calibration_scenario():
    """The shared hermetic calibration-loop scenario (tests/
    test_plan_health.py imports it too): the tiny llama-shaped serve
    graph, a "true" machine with expensive ICI (so decode-heavy vs
    prompt-heavy mixes have DIFFERENT winning plans), a "skewed" machine
    whose hardware constants over-promise 2.5x (the deliberate mis-scale
    the loop must correct), and the reference traffic features.  Graph
    building is shape inference only, nothing executes on a device."""
    true_spec = dataclasses.replace(
        TPU_SPECS["cpu"], ici_bandwidth=0.5e9, ici_latency=2e-5)
    skew = 2.5
    mm_true = MachineModel(true_spec)
    mm_skewed = MachineModel(dataclasses.replace(
        true_spec, hbm_bandwidth=true_spec.hbm_bandwidth * skew,
        mxu_efficiency=min(true_spec.mxu_efficiency * skew, 1.0),
        ici_bandwidth=true_spec.ici_bandwidth * skew))
    return {
        "ff": _serve_graph(),
        "devices": jax.devices()[:2],
        "mm_true": mm_true,
        "mm_skewed": mm_skewed,
        # decode-heavy reference mix (long outputs amortize TTFT -> the
        # pp plan's cheaper steady-state ticks win under expensive TP
        # collectives); the drifted prompt-heavy mix flips the winner
        "ref_feats": {"mean_prompt_len": 24.0, "mean_output_len": 96.0,
                      "arrival_rate_per_s": 10.0, "mean_occupancy": 0.5},
    }


def test_store_auto_apply_reduces_prediction_error(tmp_path):
    """The acceptance loop in miniature: search on a machine whose specs
    over-promise, measure reality via price_plan on the true constants,
    commit the ledger into a store — the replayed search with the store
    applied must cut the per-component error_frac."""
    scen = calibration_scenario()
    ff, devices = scen["ff"], scen["devices"]
    mm_true, mm_skewed = scen["mm_true"], scen["mm_skewed"]
    feats = scen["ref_feats"]

    def measure(plan):
        return price_plan(ff, plan["tp"], plan["pp"], plan["n_micro"],
                          machine=mm_true, devices=devices, workload=feats)

    store = CalibrationStore(str(tmp_path / "store.json"),
                             StoreConfig(min_samples=1))
    best1 = search_serve_plan(ff, n_chips=2, machine=mm_skewed,
                              devices=devices, workload=feats,
                              calibration=store)
    assert best1.get("applied_scales", {}) == {}  # round 1: nothing to apply
    meas = measure(best1)
    err_before = abs(meas["tpot_ms"] - best1["tpot_ms"]) / best1["tpot_ms"]

    led = CalibrationLedger()
    led.predict(best1["plan_key"], tpot_ms=best1["tpot_ms"],
                ttft_ms=best1["ttft_ms"])
    led.measure(best1["plan_key"], tpot_ms=meas["tpot_ms"],
                ttft_ms=meas["ttft_ms"])
    led.commit(store)
    store.save()

    best2 = search_serve_plan(ff, n_chips=2, machine=mm_skewed,
                              devices=devices, workload=feats,
                              calibration=str(store.path))
    assert best2["applied_scales"]["tpot_ms"] > 1.2  # skew detected
    meas2 = measure(best2)
    err_after = abs(meas2["tpot_ms"] - best2["tpot_ms"]) / best2["tpot_ms"]
    assert err_after < err_before * 0.5, (err_before, err_after)


def test_calibration_auto_env_override_and_test_isolation(tmp_path,
                                                          monkeypatch):
    """The "auto" consult is env-steerable and test-hermetic: conftest
    sets FLEXFLOW_TPU_CALIBRATION_STORE="" so a store an operator
    persisted to the repo artifact can never silently steer test
    searches; a path redirects auto-consult to that store."""
    from flexflow_tpu.obs.calibration import default_store_path

    # conftest's hermetic setting: auto resolves to nothing
    assert os.environ["FLEXFLOW_TPU_CALIBRATION_STORE"] == ""
    assert default_store_path() is None
    ff = _serve_graph()
    devices = jax.devices()[:2]
    a = search_serve_plan(ff, n_chips=2, devices=devices, spec_name="cpu",
                          calibration="auto")
    b = search_serve_plan(ff, n_chips=2, devices=devices, spec_name="cpu",
                          calibration=None)
    assert a["plan_key"] == b["plan_key"]
    assert a["tpot_ms"] == b["tpot_ms"]
    assert "applied_scales" not in a

    # a path in the env redirects "auto" to THAT store
    spath = str(tmp_path / "redirected.json")
    store = CalibrationStore(spath, StoreConfig(min_samples=1))
    store.update(_one_run_report(2.0, n=1))
    store.save()
    monkeypatch.setenv("FLEXFLOW_TPU_CALIBRATION_STORE", spath)
    assert default_store_path() == spath
    c = search_serve_plan(ff, n_chips=2, devices=devices, spec_name="cpu",
                          calibration="auto")
    assert c["applied_scales"] == {"tpot_ms": 2.0}
    # (rel tolerance: the scale applies before the 4-decimal rounding)
    assert c["tpot_ms"] == pytest.approx(b["tpot_ms"] * 2.0, rel=1e-3)
    # unset env: auto falls back to the (absent) repo artifact
    monkeypatch.delenv("FLEXFLOW_TPU_CALIBRATION_STORE")
    from flexflow_tpu.obs.calibration import DEFAULT_STORE_PATH

    assert default_store_path() == DEFAULT_STORE_PATH


def test_workload_features_flip_the_plan():
    """The drift->replan premise: the SAME graph+machine prefer different
    factorizations for different traffic mixes — a decode-heavy mix keeps
    the pp plan (cheaper steady-state ticks under expensive TP
    collectives), a prompt-heavy mix flips to tp (which parallelizes a
    single prefill; pp crosses stages serially and buys TTFT nothing)."""
    scen = calibration_scenario()
    ff, devices, mm = scen["ff"], scen["devices"], scen["mm_true"]
    decode_heavy = scen["ref_feats"]
    prompt_heavy = {"mean_prompt_len": 512.0, "mean_output_len": 8.0,
                    "arrival_rate_per_s": 40.0, "mean_occupancy": 0.9}
    a = search_serve_plan(ff, n_chips=2, machine=mm, devices=devices,
                          workload=decode_heavy, calibration=None)
    b = search_serve_plan(ff, n_chips=2, machine=mm, devices=devices,
                          workload=prompt_heavy, calibration=None)
    assert a["plan_key"] == "tp1_pp2_m2"
    assert b["plan_key"] == "tp2_pp1_m1"
    # the asymmetry is TTFT: under the SAME prompt-heavy mix, the tp
    # winner's first token beats the pp runner-up's
    assert b["ttft_ms"] < b["candidates"]["tp1_pp2"]["by_micro"]["2"][
        "ttft_ms"]
    # prefill interference is priced (prompt-heavy mix eats compute)
    assert b["prefill_util"] > a["prefill_util"]
