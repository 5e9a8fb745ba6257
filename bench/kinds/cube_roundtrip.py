"""Batched regular-grid round trips: ``b x y z -> b X Y Z`` and back.

One step takes ``batch`` full ``n³`` complex cubes through the program's
cube plan (``fftb.plan_for`` on ``"b x{0} y z -> b X Y Z{0}"``, inverse)
and its derived mirror, as two jitted programs: ``bench_inverse`` returns
the whole real-space batch and ``bench_forward`` reads it.  No sphere:
the line-DFT GEMMs and their transposes do the work.  Steps run back to
back (closed loop, one client) on one input batch made on the device from
the seed.

Traffic parameters: ``batch``, ``grid``.

Correctness: the round trip of every cube of the last step against the
input, and one cube of that step's inverse, drawn from the seed, against
the float64 NumPy inverse (``reference/cube.py``).  Every step takes the
same input, so every step owes the same answer; keeping a second step's
2 GiB output through the window would crowd the chip's memory for
nothing the check needs.
"""
from __future__ import annotations

import math
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import common
from bench.reference import cube as ref

PROGRAMS = ("bench_inverse", "bench_forward")
CHECKS = ("inverse_err", "roundtrip_err")
SPEC = "b x{0} y z -> b X Y Z{0}"


def window_metrics(units: int, steps: int, seconds: float) -> dict:
    """The window's end-to-end numbers: cube round trips a second."""
    return {"roundtrip_rate": units / seconds}


def required_work(config: dict, params: dict) -> tuple[float, float]:
    """(flops, bytes) one cube's round trip requires: 5·N·log2 N flops per
    direction (N = n³), and per direction the cube read once and written
    once (complex64, 8 B a point)."""
    cells = config["n"] ** 3
    return (float(2 * 5 * cells * math.log2(cells)),
            float(2 * 2 * cells * 8))


def scope_work(config: dict, params: dict) -> dict:
    """(flops, bytes) that one cube's round trip requires of the line-DFT
    layer, its only named layer: ``required_work``'s count, two
    transforms of 5·N·log2 N flops, each reading and writing the n³
    complex64 cube once."""
    return {"fftb.line_dft": required_work(config, params)}


class Cell:
    def __init__(self, ctx):
        from repro.core import Domain, fftb

        cfg, p = ctx.config, ctx.params
        self.ctx = ctx
        self.n = cfg["n"]
        self.batch = int(p["batch"])
        grid = common.make_grid(ctx.devices, p["grid"])
        doms = (Domain((0,), (self.batch - 1,)),
                Domain((0, 0, 0), (self.n - 1,) * 3))
        inv = fftb.plan_for(SPEC, domains=doms, grid=grid, inverse=True,
                            backend=cfg["backend"])
        fwd = inv.inverse()
        shape = (self.batch,) + (self.n,) * 3

        def bench_inverse(x):
            return inv(x)

        def bench_forward(y):
            return fwd(y)

        with common.matmul_precision(ctx.precision):
            self.pi = common.compile_program(
                "bench_inverse", bench_inverse,
                jax.ShapeDtypeStruct(shape, jnp.complex64))
            self.pf = common.compile_program(
                "bench_forward", bench_forward,
                jax.ShapeDtypeStruct(shape, jnp.complex64,
                                     sharding=self.pi.output_shardings))
        self.programs = {"bench_inverse": self.pi, "bench_forward": self.pf}
        self.units_per_step = self.batch
        self.x = common.c64_normal(common.key(ctx.seed, 0), shape,
                                   self.pi.input_shardings[0][0])
        self.last = None

    def step(self, i: int) -> None:
        self.last = None
        with jax.profiler.TraceAnnotation("bench.step"):
            y = self.pi(self.x)
            back = self.pf(y)
        with jax.profiler.TraceAnnotation("bench.sync"):
            back.block_until_ready()
        self.last = (y, back)

    def warm(self) -> float:
        """Run every program once; returns the step's seconds."""
        t = time.perf_counter()
        self.step(-1)
        return time.perf_counter() - t

    def release(self) -> dict:
        y, back = self.last
        rng = common.host_rng(self.ctx.seed, 2)
        j = int(rng.integers(self.batch))
        out = {"y": (j, np.asarray(y[j]), np.asarray(self.x[j])),
               "roundtrip_err": common.device_rel_err(back, self.x)}
        self.last = None
        self.x = None
        del y, back
        return out

    def readings(self, held: dict) -> dict:
        _, y, x = held["y"]
        return {"inverse_err": common.rel_err(y, ref.inverse(x)),
                "roundtrip_err": held["roundtrip_err"]}


def build(ctx) -> Cell:
    return Cell(ctx)
