"""The program's layer names reach its compiled programs.

Each layer wraps its work in a ``jax.named_scope`` (``fftb.unpack``,
``fftb.line_dft/<dim>``, ``scf.hartree``, …); the compiler keeps the
names as the ``op_name`` of each instruction's metadata, which is what a
device trace is attributed by.  These tests lower the stacked sphere pair
(on a 2-device fft grid, so the plan moves data with all-to-alls) and the
jitted SCF step at n=8 on the CPU, and look for every scope among the
compiled HLO's ``op_name`` components.
"""
import json
import re

import pytest

SPHERE_SCOPES = ("fftb.unpack", "fftb.pack", "fftb.line_dft", "fftb.a2a")
SCF_SCOPES = ("scf.hartree", "scf.xc", "scf.hamiltonian", "scf.subspace",
              "scf.density", "scf.energy", "scf.mixer")

SCRIPT = r"""
import json, re
import numpy as np
import jax, jax.numpy as jnp
from repro.core import ProcGrid, kpoint_sphere, make_stacked_planewave_pair
from repro.dft import PlaneWaveBasis, SCFConfig
from repro.dft.density import density_from_stacked
from repro.dft.hartree import HartreeSolver
from repro.dft.scf import jit_mixer_init, make_scf_step

def op_names(compiled):
    return sorted(set(re.findall(r'op_name="([^"]*)"', compiled.as_text())))

KPTS = ((0.0, 0.0, 0.0), (0.5, 0.5, 0.5))
out = {}
grid = ProcGrid.create([2], ["dft_f"])
inv, fwd = make_stacked_planewave_pair(
    grid, 8, [kpoint_sphere(4, k) for k in KPTS], 2, fft_axes=(0,))
c = jax.ShapeDtypeStruct((4, inv.npacked_max), jnp.complex64)
pi = jax.jit(inv.unpack_transform).lower(c).compile()
psi = jax.ShapeDtypeStruct((4, 8, 8, 8), jnp.complex64,
                           sharding=pi.output_shardings)
pf = jax.jit(fwd.transform_pack).lower(psi).compile()
out["stages"] = [getattr(s, "dim", None) for s in inv.stages]
out["sphere"] = op_names(pi) + op_names(pf)

cfg = SCFConfig(n=8, nbands=2, kpts=KPTS, stack_k=True, jit_step=True,
                mix_history=3)
one = ProcGrid.create([1])
basis = PlaneWaveBasis(8, kpts=KPTS, nbands=2, grid=one)
hartree = HartreeSolver(basis)
occ = np.ones((basis.nk, basis.nbands))
basis.stacked_hamiltonian_plans(0)
basis.cube_plans()
tables = (basis.stacked_band_tables(0),)
step = make_scf_step(cfg, basis, hartree, occ, float(basis.nk * 2))
c0 = jnp.zeros((basis.nk, 2, basis.npacked_max), jnp.complex64)
rho = density_from_stacked(basis, c0, occ)
mix = jit_mixer_init(8 ** 3, cfg.mix_history)
v_ext = jnp.zeros((8, 8, 8), jnp.float32)
ps = jax.jit(step).lower(rho, (c0,), mix, v_ext, hartree.kernel,
                         tables).compile()
out["scf"] = op_names(ps)
print("NAMES " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def names(dist):
    text = dist(SCRIPT, n_devices=2)
    line = next(ln for ln in text.splitlines() if ln.startswith("NAMES "))
    return json.loads(line[len("NAMES "):])


def _components(paths):
    return {c for p in paths for c in p.split("/")}


@pytest.mark.parametrize("scope", SPHERE_SCOPES)
def test_sphere_pair_scope_reaches_the_compiled_program(names, scope):
    assert scope in _components(names["sphere"])


def test_each_line_dft_stage_names_its_dim(names):
    dims = [d for d in names["stages"] if d is not None]
    stage_paths = {m.group(1) for p in names["sphere"]
                   for m in [re.search(r"fftb\.line_dft/([^/]+)", p)] if m}
    assert dims and set(dims) <= stage_paths


@pytest.mark.parametrize("scope", SCF_SCOPES + ("fftb.unpack", "fftb.pack",
                                                "fftb.line_dft"))
def test_scf_step_scope_reaches_the_compiled_program(names, scope):
    assert scope in _components(names["scf"])


def test_scopes_nest_inside_the_scf_step(names):
    paths = names["scf"]
    assert any(re.search(r"scf\.hamiltonian/.*fftb\.unpack", p)
               for p in paths)
    assert any(re.search(r"scf\.energy/.*scf\.hartree", p) for p in paths)
    assert any(re.search(r"scf\.density/.*fftb\.line_dft", p)
               for p in paths)
