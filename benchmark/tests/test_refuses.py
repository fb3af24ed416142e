"""run.py refuses where it must: no TPU, a device without peaks, a
directory without the system under test."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", "opt-6.7b-d12.decode-heavy", "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"correct"' not in out.stdout


def test_refuses_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_refuses_a_device_without_peaks(monkeypatch):
    import jax

    from benchmark import run

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(SystemExit) as e:
        run.require_device(1)
    assert e.value.code != 0
    Dev.device_kind = "TPU v5 lite"
    with pytest.raises(SystemExit):
        run.require_device(4)
