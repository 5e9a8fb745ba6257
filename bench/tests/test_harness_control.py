"""The control: the program traced one precision below the
configuration's (``highest`` → ``high``, for the line-DFT GEMMs and every
other f32 matmul) must come out not correct.

On a TPU this runs the cell at its own size and asserts exactly that.  A
CPU computes f32 matmuls in f32 whatever the precision asks, so there the
test checks what the control changes — that the program is traced at the
lower precision, and restored after — and that the program's own run is
correct.
"""
from __future__ import annotations

import jax
import pytest

from bench import common
from bench.control import readings
from bench.registry import Registry
from bench.tests.harness_testkit import tiny_registry


def test_precision_names_step_down():
    assert common.LOWER == {"highest": "high", "high": "default"}


def test_control_traces_the_program_one_precision_lower(tmp_path,
                                                        monkeypatch):
    from repro.core import local_fft

    seen = []
    real = local_fft._matmul_backend

    def spy(*a, **kw):
        seen.append(local_fft.DFT_PRECISION)
        return real(*a, **kw)
    monkeypatch.setattr(local_fft, "_matmul_backend", spy)
    reg = tiny_registry(tmp_path)
    out = readings("fig9-sphere", [3], [4], 0.2, reg, jax.devices()[:1])
    assert out["program"][0]["correct"]
    assert jax.lax.Precision.HIGHEST in seen
    assert jax.lax.Precision.HIGH in seen
    assert local_fft.DFT_PRECISION == jax.lax.Precision.HIGHEST


@pytest.mark.parametrize("workload", ["fig9-sphere", "fig9-cube",
                                      "fig9-scf"])
def test_control_is_not_correct_on_the_chip(workload):
    if jax.default_backend() != "tpu":
        pytest.skip("the MXU's precision is what the control lowers")
    out = readings(workload, [5], [6], 1.0, Registry(),
                   jax.devices()[:1])
    assert out["program"][0]["correct"]
    assert not out["control"][0]["correct"]
