"""Helpers the traffic kinds share: seeded keys, grids, named programs,
the precision a configuration states, and program memory."""
from __future__ import annotations

import contextlib
import math
import os

import numpy as np

import jax
import jax.numpy as jnp

#: names of matmul precisions, and the next one down (the control's)
PRECISIONS = ("highest", "high", "default")
LOWER = {"highest": "high", "high": "default"}


def key(seed: int, stream: int):
    """A PRNG key from a seed of up to 64 bits and a stream number."""
    s = int(seed)
    if s < 0:
        raise ValueError(f"seed must be a whole number, got {seed}")
    k = jax.random.key(s & 0xFFFFFFFF)
    k = jax.random.fold_in(k, (s >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(k, stream)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """The host generator for choices drawn from the seed (samples)."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def make_grid(devices, shape):
    """A ``ProcGrid`` over the first ``prod(shape)`` devices, with the
    DFT axis names the program's chooser gives (``sharding/grids.py``)."""
    from repro.core import ProcGrid
    from repro.core.compat import mesh_from_devices
    from repro.sharding.grids import DFT_AXES_1D, DFT_AXES_2D

    shape = tuple(int(s) for s in shape)
    names = {1: DFT_AXES_1D, 2: DFT_AXES_2D}[len(shape)]
    devs = np.array(list(devices)[:math.prod(shape)]).reshape(shape)
    return ProcGrid(mesh_from_devices(devs, names), names)


def compile_program(name: str, fn, *args, **jit_kwargs):
    """``jax.jit(fn).lower(*args).compile()`` with ``fn`` renamed, so the
    trace shows the program as ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def program_bytes(compiled) -> int:
    """argument + output + temp − alias bytes of a compiled program, per
    device (``memory_analysis()`` of the chip's own compile)."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


@contextlib.contextmanager
def matmul_precision(name: str):
    """Trace the program at the configuration's precision: the line-DFT
    GEMMs (``local_fft.DFT_PRECISION``, read at trace time) and every
    other f32 matmul (``jax.default_matmul_precision``).  Restores the
    program's own default on exit."""
    from repro.core import local_fft

    if name not in PRECISIONS:
        raise ValueError(f"unknown precision {name!r}; one of {PRECISIONS}")
    old = local_fft.DFT_PRECISION
    local_fft.DFT_PRECISION = getattr(jax.lax.Precision, name.upper())
    try:
        with jax.default_matmul_precision(name):
            yield
    finally:
        local_fft.DFT_PRECISION = old


def c64_normal(k, shape, sharding=None, keep=None):
    """Complex64 normal samples made on the device in one program.

    ``keep(shape)``, traced into the same program, gives the entries to
    keep; the rest are 0."""
    def gen(k):
        kr, ki = jax.random.split(k)
        x = jax.lax.complex(jax.random.normal(kr, shape, jnp.float32),
                            jax.random.normal(ki, shape, jnp.float32))
        return x if keep is None else jnp.where(keep(shape), x, 0)
    fn = jax.jit(gen, out_shardings=sharding) if sharding else jax.jit(gen)
    return fn(k)


@jax.jit
def _device_rel_err(got, ref):
    return jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref))


def device_rel_err(got, ref) -> float:
    """max |got − ref| / max |ref| on the device (device arrays)."""
    return float(_device_rel_err(got, ref))


def rel_err(got, ref) -> float:
    """max |got − ref| / max |ref| (host arrays)."""
    got = np.asarray(got)
    ref = np.asarray(ref)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def reference_threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))
