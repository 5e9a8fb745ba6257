"""Local 1D DFT backends — the red "local computation" block of the paper.

The paper calls FFTW/cuFFT here.  Neither exists on TPU; the TPU-native
adaptation (DESIGN.md §2) expresses line DFTs as dense matmuls on the MXU,
with *rectangular* DFT matrices fusing the plane-wave zero-pad / truncation
directly into the GEMM shape:

    ifft_n(pad_{m→n}(x))   ==  iDFT_n[:, :m] @ x
    fft_n(x)[:k]           ==  DFT_n[:k, :]  @ x

Backends:
  "jnp"     jnp.fft (oracle / CPU validation; explicit pad + slice)
  "matmul"  split re/im real matmuls (MXU-shaped; what the TPU runs via XLA)
  "pallas"  the Pallas kernel in repro.kernels (interpret=True on CPU)

Normalization follows jnp.fft: forward unnormalized, inverse scaled by 1/n.
For rectangular inverse transforms the scale is 1/n_out (the padded length),
identical to `ifft(pad(x, n))`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_BACKENDS = ("jnp", "matmul", "pallas")
# crossover above which a single dense-DFT matmul stops being the right tool
# and the four-step factorization takes over (kernels/ops.py).
MATMUL_MAX_N = 2048
#: precision of the line-DFT GEMMs.  A TPU's default f32 ``dot`` is one
#: bf16 pass (~3e-3 relative error at n=256); HIGHEST keeps the transform
#: within f32 rounding of an f64 FFT.  The CPU computes f32 dots in f32
#: either way, so CPU results do not depend on it.
DFT_PRECISION = jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=128)
def _dft_matrix_np(n: int, inverse: bool) -> np.ndarray:
    k = np.arange(n)
    sign = 2j if inverse else -2j
    w = np.exp(sign * np.pi * np.outer(k, k) / n)
    if inverse:
        w = w / n
    return w.astype(np.complex64)


def dft_matrix(n_out: int, n_in: int, inverse: bool) -> np.ndarray:
    """Rectangular DFT operator (n_out × n_in) fusing pad or truncation.

    n_in <  n_out : inverse/forward of zero-padded input (cols sliced)
    n_in >  n_out : spectrum truncation (rows sliced of the n_in transform)
    """
    if n_in <= n_out:
        return _dft_matrix_np(n_out, inverse)[:, :n_in]
    return _dft_matrix_np(n_in, inverse)[:n_out, :]


@functools.lru_cache(maxsize=128)
def dft_matrix_device(n_out: int, n_in: int, inverse: bool):
    """Device-resident f32 (real, imag, real+imag) planes of ``dft_matrix``.

    The matmul executors split W into real planes; building them with
    ``jnp.asarray`` per call re-uploads the matrix host→device on every
    stage execution (and re-embeds it on every trace).  Caching the device
    arrays per (n_out, n_in, inverse) makes repeated stage execution — the
    SCF loop's thousands of identical line-DFT stages — transfer-free.
    The sum plane feeds the lazy executor's Gauss 3-mult product.

    ``ensure_compile_time_eval`` keeps the construction eager even when the
    first request happens inside a jit/shard_map trace — otherwise the
    cache would capture (and leak) tracers instead of device arrays.
    """
    w = dft_matrix(n_out, n_in, inverse)
    with jax.ensure_compile_time_eval():
        return (jnp.asarray(w.real), jnp.asarray(w.imag),
                jnp.asarray(w.real + w.imag))


def dft_operands(n_out: int, n_in: int, inverse: bool):
    """``dft_matrix_device`` planes as GEMM operands, behind an
    optimization barrier.

    Under jit the planes are constants.  Where the other operand is known
    too — the zero imaginary plane of a real field entering the Hartree
    transform — XLA would constant-fold the whole GEMM in its evaluator at
    compile time: ~40 s per 256³ line stage in a v5e compile.  The barrier
    keeps the planes opaque, so the GEMM runs on the device instead.
    """
    return jax.lax.optimization_barrier(
        dft_matrix_device(n_out, n_in, inverse))


def _move_last(x, axis):
    return jnp.moveaxis(x, axis, -1)


def _jnp_backend(x, axis, n_in, n_out, inverse):
    fn = jnp.fft.ifft if inverse else jnp.fft.fft
    if n_in <= n_out:
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, n_out - n_in)
        xp = jnp.pad(x, pad)
        y = fn(xp, axis=axis)
        if inverse:
            # jnp.ifft normalizes by padded length already — matches matmul
            pass
        return y
    y = fn(x, axis=axis)
    return jnp.take(y, jnp.arange(n_out), axis=axis)


def _matmul_backend(x, axis, n_in, n_out, inverse):
    wr, wi, _ = dft_operands(n_out, n_in, inverse)
    xm = _move_last(x, axis)
    xr, xi = jnp.real(xm), jnp.imag(xm)
    # y = x @ W^T with complex split into real MXU GEMMs
    def mm(a, w):
        return jnp.matmul(a, w.T, precision=DFT_PRECISION)
    yr = mm(xr, wr) - mm(xi, wi)
    yi = mm(xr, wi) + mm(xi, wr)
    y = jax.lax.complex(yr, yi)
    return jnp.moveaxis(y, -1, axis)


def _pallas_backend(x, axis, n_in, n_out, inverse):
    from repro.kernels import ops as kops
    xm = _move_last(x, axis)
    shp = xm.shape
    xf = xm.reshape(-1, n_in)
    yf = kops.dft_apply(xf, n_out=n_out, inverse=inverse)
    return jnp.moveaxis(yf.reshape(*shp[:-1], n_out), -1, axis)


def realized_backend(n_in: int, n_out: int, backend: str) -> str:
    """The backend ``local_dft`` will actually run for this line shape.

    A dense-matrix backend ("matmul" — and "pallas", whose kernel is the
    same single GEMM) requested above the ``MATMUL_MAX_N`` crossover
    *realizes* as "jnp" (the four-step factorization lives in
    ``kernels/ops.py`` and is not a line-stage backend).  Everything that
    accounts or reports per-stage work — ``dft_flops``, stage spans,
    ``describe()`` — must go through this so the books match what executed
    rather than what was requested.
    """
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    if backend in ("matmul", "pallas") and max(n_in, n_out) > MATMUL_MAX_N:
        return "jnp"
    return backend


def local_dft(x, axis: int, n_out: int | None = None, *,
              inverse: bool = False, backend: str = "matmul"):
    """Apply a (possibly rectangular) DFT along ``axis`` of complex ``x``."""
    n_in = x.shape[axis]
    n_out = n_in if n_out is None else n_out
    backend = realized_backend(n_in, n_out, backend)
    x = x.astype(jnp.complex64)
    if backend == "jnp":
        return _jnp_backend(x, axis, n_in, n_out, inverse)
    if backend == "matmul":
        return _matmul_backend(x, axis, n_in, n_out, inverse)
    return _pallas_backend(x, axis, n_in, n_out, inverse)


def dft_flops(n_out: int, n_in: int, batch: int, backend: str) -> int:
    """FLOP estimate for one batched line-DFT stage (roofline/fig9 model).

    Priced at the *realized* backend: a matmul/pallas stage above the
    ``MATMUL_MAX_N`` crossover silently runs "jnp", and reporting dense
    GEMM FLOPs for it would overstate the stage ~n/log n-fold.
    """
    backend = realized_backend(n_in, n_out, backend)
    if backend == "matmul" or backend == "pallas":
        # 4 real GEMMs, 2·m·n MACs each → 8·m·n real FLOPs per line... use
        # 8 flops per complex MAC: y(n_out) = W(n_out×n_in) x
        return 8 * n_out * n_in * batch
    # split-radix style estimate
    n = max(n_out, n_in)
    return int(5 * n * np.log2(max(n, 2))) * batch
