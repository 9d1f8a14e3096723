"""A run refuses a machine without the cell's chips, and a checkout
without the program: it exits non-zero and prints no result."""

import shutil
import subprocess
import sys
import types

import jax
import pytest

from chipbench import harness


def test_refuses_a_cpu():
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.require_devices(1)


def _fake(kind, n=1):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind) for _ in range(n)]


def test_refuses_an_unknown_device_kind(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: _fake("TPU v9 imaginary"))
    with pytest.raises(harness.NoChip, match="peaks.json"):
        harness.require_devices(1)


def test_refuses_too_few_chips(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: _fake("TPU v5 lite", 1))
    with pytest.raises(harness.NoChip, match="4 chips"):
        harness.require_devices(4)


def test_accepts_the_v5e_by_its_reported_kind(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: _fake("TPU v5 lite", 4))
    peak, dev = harness.require_devices(4)
    assert peak["bf16_flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def _run(cwd, workload):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def test_run_on_a_cpu_prints_no_result():
    cell = harness._read(harness.ROOT / "BENCHMARK.json")["workloads"][0]["name"]
    p = _run(harness.ROOT, cell)
    assert p.returncode == 3, p.stderr
    assert p.stdout == "" and "no TPU" in p.stderr


def test_run_without_the_program_fails(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = harness._read(harness.ROOT / "BENCHMARK.json")["workloads"][0]["name"]
    p = _run(tmp_path, cell)
    assert p.returncode != 0 and p.stdout == ""
