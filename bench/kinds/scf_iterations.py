"""Full SCF iterations of the program's jitted, k-stacked step.

Set-up builds one object: the program's basis (``PlaneWaveBasis``), its
Hartree solver, and its fused SCF step (``dft.scf.make_scf_step``) compiled
ahead with donated density, band and mixer buffers, as ``run_scf`` does
for ``jit_step=True``; the start state comes from orbitals made on the
device from the seed.  ``advance`` runs one iteration through that
compiled step and reads the energy and residual back, as ``run_scf``'s
loop does.  Set-up drives the first ``checked_iterations`` iterations
through ``advance``; the window then goes on calling it (closed loop).

Traffic parameters: ``grid``, ``checked_iterations``.

Correctness: the plain reference (``reference/scf.py``) runs the same
first iterations from the same orbitals and external potential.  Compared,
each as a max-norm relative gap: the energy and the output density of
each checked iteration, and the mixed density the last one hands on (the
Anderson step).
"""
from __future__ import annotations

import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import common
from bench.reference import scf as ref
from bench.reference import sphere

PROGRAMS = ("bench_scf_step",)
CHECKS = ("energy_err", "density_err", "mixed_density_err")


def window_metrics(units: int, steps: int, seconds: float) -> dict:
    """The window's end-to-end numbers: seconds an SCF iteration."""
    return {"scf_iter_s": seconds / steps}


def required_work(config: dict, params: dict) -> tuple[float, float]:
    """(flops, bytes) of the transforms one iteration requires: per
    band-update step two H applies (a sphere round trip of every orbital
    each), the density's inverse of every orbital, and two cube round
    trips (the Hartree solves of ρ_in and of ρ_out).  5·N·log2 N flops a
    transform (N = n³); bytes as for the sphere and cube round trips."""
    n, d = config["n"], config["diameter"]
    cells = n ** 3
    orbitals = len(config["kpts"]) * config["nbands"]
    npk = np.mean([sphere.packed_points(d, k).size for k in config["kpts"]])
    fft = 5 * cells * math.log2(cells)
    rt_sphere = (2 * fft, 2 * (npk + cells) * 8)
    inv_sphere = (fft, (npk + cells) * 8)
    rt_cube = (2 * fft, 4 * cells * 8)
    sweeps = 2 * config["inner_steps"] * orbitals
    parts = [(sweeps, rt_sphere), (orbitals, inv_sphere), (2, rt_cube)]
    return (float(sum(m * w[0] for m, w in parts)),
            float(sum(m * w[1] for m, w in parts)))


def scope_work(config: dict, params: dict) -> dict:
    """(flops, bytes) one iteration requires of each named transform
    layer, counted from the configuration's shapes, whatever implements
    the layer:

    * ``fftb.line_dft``: every transform of the iteration,
      ``required_work``: the transforms need no less than the packed
      coefficients on one side of a sphere transform and the cube on
      the other;
    * ``fftb.unpack``: each orbital's packed coefficients read once and
      written once, no flops, for each H apply (2 a band-update step)
      and for the density;
    * ``fftb.pack``: the same for each H apply.
    """
    packed = config["nbands"] * sum(
        sphere.packed_points(config["diameter"], k).size
        for k in config["kpts"])
    applies = 2 * config["inner_steps"]
    return {"fftb.line_dft": required_work(config, params),
            "fftb.unpack": (0.0, float((applies + 1) * 2 * packed * 8)),
            "fftb.pack": (0.0, float(applies * 2 * packed * 8))}


def initial_orbitals(seed: int, config: dict, npm: int):
    """Orthonormal random orbitals per k-point, (nk, nb, npm) complex64,
    zero on each k-point's padded lanes — made on the device."""
    nk, nb, d = len(config["kpts"]), config["nbands"], config["diameter"]
    valid = np.zeros((nk, 1, npm), bool)
    for i, k in enumerate(config["kpts"]):
        valid[i, :, :sphere.packed_points(d, k).size] = True
    c = common.c64_normal(common.key(seed, 0), (nk, nb, npm))

    @jax.jit
    def orth(c, valid):
        c = jnp.where(valid, c, 0)
        q, r = jnp.linalg.qr(jnp.swapaxes(c, -1, -2))
        ph = jnp.sign(jnp.real(jnp.diagonal(r, axis1=-2, axis2=-1)) + 1e-30)
        return jnp.swapaxes(q * ph[:, None, :], -1, -2)

    with jax.default_matmul_precision("highest"):
        return orth(c, jnp.asarray(valid))


class Cell:
    def __init__(self, ctx):
        from repro.dft import PlaneWaveBasis, SCFConfig
        from repro.dft.density import density_from_stacked
        from repro.dft.hartree import HartreeSolver
        from repro.dft.scf import jit_mixer_init, make_scf_step

        cfg, p = ctx.config, ctx.params
        self.ctx = ctx
        n = cfg["n"]
        grid = common.make_grid(ctx.devices, p["grid"])
        scf_cfg = SCFConfig(
            n=n, diameter=cfg["diameter"], nbands=cfg["nbands"],
            kpts=tuple(tuple(k) for k in cfg["kpts"]), L=cfg.get("L"),
            depth=cfg["depth"], xc=cfg["xc"],
            inner_steps=cfg["inner_steps"], mix_alpha=cfg["mix_alpha"],
            mix_history=cfg["mix_history"], mix_warmup=cfg["mix_warmup"],
            stack_k=True, jit_step=True, backend=cfg["backend"])
        basis = PlaneWaveBasis(
            n, diameter=cfg["diameter"], kpts=scf_cfg.kpts,
            nbands=cfg["nbands"], L=cfg.get("L"), grid=grid,
            backend=cfg["backend"])
        if basis.nsegments != 1:
            raise ValueError("one stacking segment expected")
        hartree = HartreeSolver(basis)
        occ = np.ones((basis.nk, basis.nbands))
        nelec = float(basis.weights.sum() * basis.nbands)
        self.npm = basis.npacked_max
        self.v_ext = jax.jit(ref.gaussian_wells, static_argnums=(0, 1))(
            n, float(cfg["depth"]))
        self.c0 = initial_orbitals(ctx.seed, cfg, self.npm)
        self.c0_host = np.asarray(self.c0)
        # plans and tables are built eagerly, before any trace, as
        # run_scf does: arrays first made inside a trace are hoisted into
        # the compiled step as extra arguments its call does not pass
        basis.stacked_hamiltonian_plans(0)
        basis.cube_plans()
        tables = (basis.stacked_band_tables(0),)
        step = make_scf_step(scf_cfg, basis, hartree, occ, nelec)

        def bench_scf_step(rho, c_segs, mix_state, v_ext, coulomb, tables):
            return step(rho, c_segs, mix_state, v_ext, coulomb, tables)

        with common.matmul_precision(ctx.precision):
            rho = density_from_stacked(basis, self.c0, occ)
            mix = jit_mixer_init(n ** 3, cfg["mix_history"])
            self.step_fn = common.compile_program(
                "bench_scf_step", bench_scf_step, rho, (self.c0,), mix,
                self.v_ext, hartree.kernel, tables, donate_argnums=(0, 1, 2))
        self.programs = {"bench_scf_step": self.step_fn}
        self.units_per_step = 1
        # the step takes exactly the placements it was compiled from; its
        # outputs may come back placed otherwise on a multi-device grid
        self.state = (rho, (self.c0,), mix)
        self.in_sh = jax.tree.map(lambda a: a.sharding, self.state)
        self.consts = (self.v_ext, hartree.kernel, tables)
        self.c0 = None
        self.history = []
        self.checked = int(p["checked_iterations"])
        self.done = 0

    def warm(self) -> float:
        """The checked iterations, through the window's own call; returns
        the last one's seconds."""
        t = 0.0
        for i in range(self.checked):
            t = time.perf_counter()
            self.step(i)
            t = time.perf_counter() - t
        return t

    def step(self, i: int) -> None:
        """One SCF iteration, as ``run_scf``'s jitted loop runs it; the
        first ``checked`` of the run are recorded for the check."""
        with jax.profiler.TraceAnnotation("bench.step"):
            state = jax.device_put(self.state, self.in_sh)
            rho, c_segs, mix, rho_out, eps, energy, resid = self.step_fn(
                *state, *self.consts)
        with jax.profiler.TraceAnnotation("bench.sync"):
            energy, resid = float(energy), float(resid)
        self.state = (rho, c_segs, mix)
        it, self.done = self.done, self.done + 1
        if it < self.checked:
            rec = {"energy": energy, "residual": resid,
                   "rho_out": np.asarray(rho_out), "eps": np.asarray(eps[0])}
            if it == self.checked - 1:
                rec["rho_next"] = np.asarray(rho)
            self.history.append(rec)

    def release(self) -> dict:
        held = {"history": self.history, "c0": self.c0_host,
                "v_ext": np.asarray(self.v_ext)}
        self.state = self.consts = self.v_ext = None
        self.step_fn = None
        self.programs = {}
        return held

    def readings(self, held: dict) -> dict:
        r = ref.ReferenceSCF(self.ctx.config, self.npm)
        want = ref.run(r, held["c0"], held["v_ext"], len(held["history"]))
        got = held["history"]
        e_err = max(abs(g["energy"] - w) / abs(w)
                    for g, w in zip(got, want["energy"]))
        rho_err = max(common.rel_err(g["rho_out"], w)
                      for g, w in zip(got, want["rho_out"]))
        mixed = common.rel_err(got[-1]["rho_next"], want["rho_next"])
        return {"energy_err": e_err, "density_err": rho_err,
                "mixed_density_err": mixed}


def build(ctx) -> Cell:
    return Cell(ctx)
