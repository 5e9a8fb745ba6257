"""Pallas TPU kernel: batched (rectangular) complex DFT as MXU matmuls.

The paper's local-compute stage calls cuFFT; on TPU the right primitive for
line lengths in the plane-wave regime (n ≤ ~2k) is a dense DFT *matmul* on
the 128×128 MXU — O(n²) FLOPs at 197 TFLOP/s beat O(n log n) VPU shuffles,
and the rectangular slice of the DFT matrix fuses the sphere zero-pad /
truncation for free (DESIGN.md §2).

Complex arithmetic is split re/im (the MXU has no complex type): one kernel
invocation performs the four real GEMMs

    yr = xr·Wrᵀ − xi·Wiᵀ          yi = xr·Wiᵀ + xi·Wrᵀ

with an optional fused twiddle epilogue (used by the four-step large-n
factorization): y ← y ⊙ (tr + i·ti), where t broadcasts over rows.

Tiling: grid (B/bm, N/bn); x blocks (bm, K) stream down the batch, W blocks
(bn, K) stream across output frequencies, K (= n_in ≤ 2048) is kept whole in
VMEM — worst case VMEM footprint ≈ 2·bm·K + 2·bn·K + 2·bm·bn floats ≈ 6.5 MB
at (bm, bn, K) = (256, 128, 2048), comfortably inside the ~16 MB budget,
with MXU-aligned (multiple-of-128) contraction and output dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import local_fft


def resolve_interpret(interpret: bool | None) -> bool:
    """Pallas interpret mode for one kernel call.

    An explicit choice wins.  Otherwise the default backend decides, at
    call time: Mosaic on a TPU, the Pallas interpreter on the CPU (tests
    and rehearsals).  Any other platform raises — these kernels are TPU
    kernels, and interpreting them on an accelerator would hide that.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"no Pallas route for platform {platform!r}: the "
                       "kernels compile for TPU and interpret on CPU only")


def dot_t(a, b):
    """a · bᵀ on the MXU, accumulated in f32 at the line-DFT precision
    (``local_fft.DFT_PRECISION``)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=local_fft.DFT_PRECISION,
                               preferred_element_type=jnp.float32)


def _kernel(xr_ref, xi_ref, wr_ref, wi_ref, yr_ref, yi_ref):
    xr = xr_ref[...]
    xi = xi_ref[...]
    wr = wr_ref[...]
    wi = wi_ref[...]
    # 4 real GEMMs on the MXU; accumulate in f32 regardless of input dtype
    yr_ref[...] = (dot_t(xr, wr) - dot_t(xi, wi)).astype(yr_ref.dtype)
    yi_ref[...] = (dot_t(xr, wi) + dot_t(xi, wr)).astype(yi_ref.dtype)


def _kernel_twiddle(xr_ref, xi_ref, wr_ref, wi_ref, tr_ref, ti_ref,
                    yr_ref, yi_ref):
    xr = xr_ref[...]
    xi = xi_ref[...]
    wr = wr_ref[...]
    wi = wi_ref[...]
    yr = dot_t(xr, wr) - dot_t(xi, wi)
    yi = dot_t(xr, wi) + dot_t(xi, wr)
    tr = tr_ref[...]            # (bm, bn): per-row twiddles, pre-broadcast
    ti = ti_ref[...]
    yr_ref[...] = (yr * tr - yi * ti).astype(yr_ref.dtype)
    yi_ref[...] = (yr * ti + yi * tr).astype(yi_ref.dtype)


def dft_matmul(xr, xi, wr, wi, tr=None, ti=None, *, bm: int = 256,
               bn: int = 128, interpret: bool | None = None):
    """y = (xr + i·xi) @ (wr + i·wi)ᵀ [⊙ twiddle], shapes (B,K)·(N,K)→(B,N).

    B must be divisible by bm and N by bn (ops.py pads).  ``tr``/``ti`` are
    optional (B, N) twiddle factors fused into the epilogue.
    ``interpret=None`` decides from the platform at call time
    (:func:`resolve_interpret`).
    """
    return _dft_matmul(xr, xi, wr, wi, tr, ti, bm=bm, bn=bn,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def _dft_matmul(xr, xi, wr, wi, tr, ti, *, bm: int, bn: int,
                interpret: bool):
    B, K = xr.shape
    N = wr.shape[0]
    assert B % bm == 0 and N % bn == 0, (B, N, bm, bn)
    grid = (B // bm, N // bn)
    x_spec = pl.BlockSpec((bm, K), lambda i, j: (i, 0))
    w_spec = pl.BlockSpec((bn, K), lambda i, j: (j, 0))
    y_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out_shape = [jax.ShapeDtypeStruct((B, N), xr.dtype)] * 2
    if tr is None:
        return pl.pallas_call(
            _kernel,
            grid=grid,
            in_specs=[x_spec, x_spec, w_spec, w_spec],
            out_specs=[y_spec, y_spec],
            out_shape=out_shape,
            interpret=interpret,
        )(xr, xi, wr, wi)
    t_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel_twiddle,
        grid=grid,
        in_specs=[x_spec, x_spec, w_spec, w_spec, t_spec, t_spec],
        out_specs=[y_spec, y_spec],
        out_shape=out_shape,
        interpret=interpret,
    )(xr, xi, wr, wi, tr, ti)
