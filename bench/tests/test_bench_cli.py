"""The command refuses, with no result line, off a TPU, on a device kind it
has no peaks for, with too few chips, and without the program."""
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as R

ARGS = ["-m", "bench.run", "--workload", "qwen2-1.5b-lcd4.chat",
        "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_exits_nonzero_off_a_tpu():
    p = _run(R.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(R.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(R.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".runs", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def _fake(monkeypatch, kind, n):
    import jax
    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_refuses_unknown_device_kind(monkeypatch):
    _fake(monkeypatch, "TPU v99", 1)
    with pytest.raises(R.Refused, match="no peaks"):
        R.device_or_refuse(1)


def test_refuses_too_few_chips(monkeypatch):
    _fake(monkeypatch, "TPU v5 lite", 1)
    with pytest.raises(R.Refused, match="asks for 4 chips"):
        R.device_or_refuse(4)
    _fake(monkeypatch, "TPU v5 lite", 4)
    devs, peaks = R.device_or_refuse(4)
    assert len(devs) == 4 and peaks["bf16_flops_per_s"] == 197e12
