"""A run refuses what is not the chip it is for, and prints no result."""
from __future__ import annotations

import os
import subprocess
import sys
import types

import pytest

from bench import run
from bench.registry import ROOT, Registry


def test_cpu_is_refused_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig9-sphere",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
        check=False)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "needs a TPU" in proc.stderr
    assert not proc.stdout.strip()


def _fake_devices(monkeypatch, platform, kind, count):
    import jax

    devs = [types.SimpleNamespace(platform=platform, device_kind=kind)
            for _ in range(count)]
    monkeypatch.setattr(jax, "devices", lambda: devs)
    return devs


def test_unknown_device_kind_is_refused(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v99 imaginary", 4)
    with pytest.raises(SystemExit) as e:
        run.chip_devices(Registry(), 1)
    assert e.value.code == 3


def test_too_few_chips_are_refused(monkeypatch):
    _fake_devices(monkeypatch, "tpu", "TPU v5 lite", 1)
    with pytest.raises(SystemExit) as e:
        run.chip_devices(Registry(), 4)
    assert e.value.code == 3


def test_known_chip_passes(monkeypatch):
    devs = _fake_devices(monkeypatch, "tpu", "TPU v5 lite", 4)
    assert run.chip_devices(Registry(), 4) == devs
