"""Sphere round trips: every k-point's bands, inverse then forward.

One step takes ``nbands`` bands of every k-point of the configuration
(its value for the cell's chip count; ``nk·nbands`` orbitals, stacked as the program's
``StackedPlaneWaveFFT`` wants them) sphere → real space with the
program's ``unpack_transform`` and back with ``transform_pack``, as two
jitted programs: ``bench_inverse`` returns the whole real-space ``psi``
and ``bench_forward`` reads it, as a caller that applies ``V_eff`` in
between does.  Steps run back to back (closed loop, one client) on one
band batch made on the device from the seed: the work does not depend on
the values, and every step owes the same answer.

Traffic parameters: ``grid`` (process-grid shape), ``batch_axes``,
``fft_axes``.

Correctness: the round trip of every orbital of the window's first and
last steps against the input; and one band of each k-point in the last
step's ``psi``, drawn from the seed, against the float64 NumPy inverse
(``reference/sphere.py``).
"""
from __future__ import annotations

import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import common
from bench.reference import sphere as ref

PROGRAMS = ("bench_inverse", "bench_forward")
CHECKS = ("inverse_err", "roundtrip_err")


def window_metrics(units: int, steps: int, seconds: float) -> dict:
    """The window's end-to-end numbers: orbital round trips a second."""
    return {"roundtrip_rate": units / seconds}


def required_work(config: dict, params: dict) -> tuple[float, float]:
    """(flops, bytes) that one orbital's round trip requires, whatever
    implements it: 5·N·log2 N flops per direction (N = n³), and per
    direction the packed coefficients read or written once (8 B each,
    complex64, the k-points' mean count) and the n³ cube written or read
    once."""
    n, d = config["n"], config["diameter"]
    npk = np.mean([ref.packed_points(d, k).size for k in config["kpts"]])
    cells = n ** 3
    flops = 2 * 5 * cells * math.log2(cells)
    nbytes = 2 * (npk * 8 + cells * 8)
    return float(flops), float(nbytes)


def scope_work(config: dict, params: dict) -> dict:
    """(flops, bytes) that one orbital's round trip requires of each named
    layer, counted from the configuration's shapes, whatever implements
    the layer:

    * ``fftb.line_dft``: the whole round trip's ``required_work``: the
      transforms need no less than the packed coefficients on one side
      and the cube on the other, and a route that pads the sphere into a
      cube first moves more, never less;
    * ``fftb.unpack`` and ``fftb.pack``: the orbital's packed
      coefficients read once and written once (to or from their places
      in the cube), and no flops: the layers only move data.
    """
    npk = np.mean([ref.packed_points(config["diameter"], k).size
                   for k in config["kpts"]])
    move = (0.0, float(2 * npk * 8))
    return {"fftb.line_dft": required_work(config, params),
            "fftb.unpack": move, "fftb.pack": move}


class Cell:
    def __init__(self, ctx):
        from repro.core import kpoint_sphere, make_stacked_planewave_pair

        cfg, p = ctx.config, ctx.params
        self.ctx = ctx
        self.n, self.d = cfg["n"], cfg["diameter"]
        self.kpts = [tuple(k) for k in cfg["kpts"]]
        self.nb = int(cfg["nbands"][str(len(ctx.devices))])
        grid = common.make_grid(ctx.devices, p["grid"])
        spheres = [kpoint_sphere(self.d, k) for k in self.kpts]
        self.npk = [ref.packed_points(self.d, k).size for k in self.kpts]
        if self.npk != [s.npacked for s in spheres]:
            raise AssertionError(f"sphere sizes differ from the reference's "
                                 f"{self.npk}: {[s.npacked for s in spheres]}")
        inv, fwd = make_stacked_planewave_pair(
            grid, self.n, spheres, self.nb, backend=cfg["backend"],
            batch_axes=tuple(p["batch_axes"]), fft_axes=tuple(p["fft_axes"]))
        self.rows = len(self.kpts) * self.nb
        self.npm = inv.npacked_max

        def bench_inverse(c):
            return inv.unpack_transform(c)

        def bench_forward(psi):
            return fwd.transform_pack(psi)

        with common.matmul_precision(ctx.precision):
            self.pi = common.compile_program(
                "bench_inverse", bench_inverse,
                jax.ShapeDtypeStruct((self.rows, self.npm), jnp.complex64))
            psi = jax.ShapeDtypeStruct((self.rows,) + (self.n,) * 3,
                                       jnp.complex64,
                                       sharding=self.pi.output_shardings)
            self.pf = common.compile_program("bench_forward", bench_forward,
                                             psi)
        self.programs = {"bench_inverse": self.pi, "bench_forward": self.pf}
        self.units_per_step = self.rows
        row_npk = np.repeat(self.npk, self.nb)

        def in_sphere(shape):
            return jnp.arange(shape[1])[None, :] < row_npk[:, None]

        # one program and nothing else left on the device, so the window's
        # buffers are placed alike in every run
        self.c = common.c64_normal(common.key(ctx.seed, 0),
                                   (self.rows, self.npm),
                                   self.pi.input_shardings[0][0],
                                   keep=in_sphere)
        self.c.block_until_ready()
        self.first = None
        self.last = None

    def step(self, i: int) -> None:
        self.last = None                 # frees the previous step's psi
        with jax.profiler.TraceAnnotation("bench.step"):
            psi = self.pi(self.c)
            back = self.pf(psi)
        with jax.profiler.TraceAnnotation("bench.sync"):
            back.block_until_ready()
        if i == 0:
            self.first = back
        self.last = (psi, back)

    def warm(self) -> float:
        """Run every program once; returns the step's seconds."""
        t = time.perf_counter()
        self.step(-1)
        return time.perf_counter() - t

    def release(self) -> dict:
        """Copy what the check reads to the host; drop device state."""
        psi, back = self.last
        rng = common.host_rng(self.ctx.seed, 2)
        orbitals = [k * self.nb + int(rng.integers(self.nb))
                    for k in range(len(self.kpts))]
        out = {"psi": {r: np.asarray(psi[r]) for r in orbitals},
               "input": np.asarray(self.c),
               # the round trip's reference is its input: compared where
               # both already are, before the state is dropped
               "roundtrip_err": max(common.device_rel_err(b, self.c)
                                    for b in (self.first, back))}
        self.first = self.last = self.c = None
        del psi, back
        return out

    def readings(self, held: dict) -> dict:
        """The numbers compared, from what ``release`` kept."""
        c = held["input"]
        inv = 0.0
        for r, psi in held["psi"].items():
            k = r // self.nb
            want = ref.inverse(c[r, :self.npk[k]], self.d, self.kpts[k],
                               self.n)
            inv = max(inv, common.rel_err(psi, want))
        return {"inverse_err": inv, "roundtrip_err": held["roundtrip_err"]}


def build(ctx) -> Cell:
    return Cell(ctx)
