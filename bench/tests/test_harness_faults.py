"""The check catches a broken timed path: each fault a cell can have is
planted underneath a whole run (the look for a chip skipped, the rest as
``run.py`` drives it) and ``correct`` has to come out false.

Faults: an answer altered where it is produced; half of the batch left
out (the rest doubled, as a mean over it would be); an SCF step that
returns its state unchanged; the exchange between chips left out (the
four-chip cell, on four host devices in a child process).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.tests.harness_testkit import run_child, tiny_registry


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("bench"))


def _run(reg, workload, seed=2 ** 32 + 3):
    return run.run_cell(workload, seed=seed, seconds=0.2, trace=False,
                        registry=reg, devices=jax.devices()[:1])


@pytest.mark.parametrize("workload", ["fig9-sphere", "fig9-cube",
                                      "fig9-scf"])
def test_sound_run_is_correct(reg, workload):
    assert _run(reg, workload)["correct"]


def test_altered_answer_in_the_sphere_inverse(reg, monkeypatch):
    from repro.core.planewave import StackedPlaneWaveFFT

    real = StackedPlaneWaveFFT.unpack_transform

    def altered(self, packed, **kw):
        psi = real(self, packed, **kw)
        return psi.at[1, 0, 0, 0].add(1e-3 * jnp.abs(psi).max())
    monkeypatch.setattr(StackedPlaneWaveFFT, "unpack_transform", altered)
    res = _run(reg, "fig9-sphere")
    assert not res["correct"] and res["failed"] >= 1


def test_half_the_sphere_batch_left_out(reg, monkeypatch):
    from repro.core.planewave import StackedPlaneWaveFFT

    real = StackedPlaneWaveFFT.transform_pack

    def half(self, cube, **kw):
        out = real(self, cube, **kw)
        b = out.shape[0] // 2
        return jnp.concatenate([2 * out[:b], jnp.zeros_like(out[b:])])
    monkeypatch.setattr(StackedPlaneWaveFFT, "transform_pack", half)
    assert not _run(reg, "fig9-sphere")["correct"]


def test_altered_answer_in_the_cube_forward(reg, monkeypatch):
    from repro.core.plan import FftPlan

    real = FftPlan._execute

    def altered(self, x, pol):
        y = real(self, x, pol)
        return y if self.is_inverse else y.at[0, 1, 2, 3].add(1.0)
    monkeypatch.setattr(FftPlan, "_execute", altered)
    assert not _run(reg, "fig9-cube")["correct"]


def _patch_step(monkeypatch, edit):
    import repro.dft.scf as scf

    real = scf.make_scf_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(rho, c_segs, mix_state, *rest):
            return edit(step(rho, c_segs, mix_state, *rest),
                        (rho, c_segs, mix_state))
        return broken
    monkeypatch.setattr(scf, "make_scf_step", make)


def test_scf_step_that_returns_its_state_unchanged(reg, monkeypatch):
    _patch_step(monkeypatch, lambda out, state: state + out[3:])
    res = _run(reg, "fig9-scf")
    assert not res["correct"]


def test_scf_density_from_half_the_bands(reg, monkeypatch):
    import repro.dft.scf as scf

    real = scf.density_from_stacked

    def half(basis, c_pad, occ, seg=0):
        nb = c_pad.shape[1]
        kept = c_pad.at[:, nb // 2:].set(0)
        return 2 * real(basis, kept, occ, seg=seg)
    monkeypatch.setattr(scf, "density_from_stacked", half)
    assert not _run(reg, "fig9-scf")["correct"]


def test_scf_energy_altered_where_produced(reg, monkeypatch):
    _patch_step(monkeypatch, lambda out, state:
                out[:5] + (out[5] * 1.001,) + out[6:])
    res = _run(reg, "fig9-scf")
    assert not res["correct"]
    assert res["checks"]["energy_err"]["value"] > \
        res["checks"]["energy_err"]["limit"]


NO_EXCHANGE = r"""
import pathlib, sys, tempfile
import jax, jax.numpy as jnp
from bench import run
from bench.tests.harness_testkit import tiny_registry

def keep_local(x, axis_name, split_axis, concat_axis, tiled=True):
    # the all_to_all's shapes, with no data from the other devices
    p = jax.lax.axis_size(axis_name)
    return jnp.concatenate(jnp.split(x, p, axis=split_axis),
                           axis=concat_axis)

reg = tiny_registry(pathlib.Path(tempfile.mkdtemp()))
kw = dict(seed=5, seconds=0.2, trace=False, registry=reg,
          devices=jax.devices()[:4])
sound = run.run_cell("fig9-sphere-fft4", **kw)
jax.lax.all_to_all = keep_local
jax.clear_caches()
from repro.core import global_plan_cache
global_plan_cache().clear()
broken = run.run_cell("fig9-sphere-fft4", **kw)
print("RESULT", sound["correct"], broken["correct"])
"""


def test_exchange_between_chips_left_out():
    out = run_child(NO_EXCHANGE, n_devices=4)
    assert "RESULT True False" in out, out[-3000:]
