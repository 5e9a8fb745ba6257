"""jit'd wrappers around the Pallas DFT kernel + the four-step composition.

On CPU the kernels run with ``interpret=True``; on TPU the same code emits
real Mosaic kernels (decided per call by ``dft_matmul.resolve_interpret``).
``dft_apply`` handles padding of the batch/frequency dims to the kernel tile
sizes; ``four_step_dft`` factors large n into two MXU-sized stages with the
twiddle fused into the first stage's epilogue.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.local_fft import dft_matrix
from . import ref as _ref
from .dft_matmul import dft_matmul


def _pad_to(x, m: int, axis: int):
    n = x.shape[axis]
    r = (-n) % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, r)
    return jnp.pad(x, pad)


def _pick_block(n: int, pref: int) -> int:
    """Largest MXU-friendly block ≤ pref that keeps padding mild."""
    if n >= pref:
        return pref
    # small problems: round up to the 8-lane sublane granule
    return max(8, 1 << (n - 1).bit_length())


def dft_apply(x, n_out: int | None = None, *, inverse: bool = False,
              bm: int = 256, bn: int = 128,
              interpret: bool | None = None):
    """Batched line DFT via the Pallas kernel: (B, n_in) → (B, n_out).

    Rectangular n_in≠n_out fuses zero-padding (n_in < n_out) or spectrum
    truncation (n_in > n_out) into the GEMM shape.
    """
    B, n_in = x.shape
    n_out = n_in if n_out is None else n_out
    w = dft_matrix(n_out, n_in, inverse)
    wr = jnp.asarray(w.real)
    wi = jnp.asarray(w.imag)
    xr = jnp.real(x).astype(jnp.float32)
    xi = jnp.imag(x).astype(jnp.float32)

    bm = _pick_block(B, bm)
    bn = _pick_block(n_out, bn)
    xr = _pad_to(xr, bm, 0)
    xi = _pad_to(xi, bm, 0)
    wr = _pad_to(wr, bn, 0)
    wi = _pad_to(wi, bn, 0)
    yr, yi = dft_matmul(xr, xi, wr, wi, bm=bm, bn=bn, interpret=interpret)
    return jax.lax.complex(yr[:B, :n_out], yi[:B, :n_out])


@functools.lru_cache(maxsize=64)
def _factor(n: int) -> tuple[int, int]:
    """n = n1·n2 with n1 ≈ n2 (n1 the outer/output-major factor)."""
    best = (1, n)
    for n1 in range(2, int(math.isqrt(n)) + 1):
        if n % n1 == 0:
            best = (n1, n // n1)
    n1, n2 = best
    if n1 == 1:
        raise ValueError(f"four-step needs composite n, got prime {n}")
    return n1, n2


def four_step_dft(x, *, inverse: bool = False, interpret: bool | None = None):
    """Large-n line DFT: two MXU-sized stages + fused twiddle (Bailey).

    x: (B, n) with composite n = n1·n2.  Stage 1: DFT_n2 over columns with
    the W_N^{j1·k2} twiddle fused into the kernel epilogue; stage 2: DFT_n1
    over rows; output re-rolled to natural order.
    """
    B, n = x.shape
    n1, n2 = _factor(n)
    # (B, n) -> (B, n2, n1): j = j1 + n1·j2, j1 fast
    xm = x.reshape(B, n2, n1)

    # --- stage 1: DFT_n2 along axis 1, twiddle fused -------------------
    # lines are the n1 columns: bring them to rows: (B, n1, n2)
    s1 = jnp.swapaxes(xm, 1, 2).reshape(B * n1, n2)
    tw = _ref.twiddle_matrix(n1, n2, inverse)            # (n2, n1)
    w = dft_matrix(n2, n2, inverse)
    wr, wi = jnp.asarray(w.real), jnp.asarray(w.imag)
    # twiddle for row (b, j1): t[k2] = tw[k2, j1] — build (B·n1, n2)
    twt = jnp.asarray(np.ascontiguousarray(tw.T))        # (n1, n2)
    tr = jnp.tile(jnp.real(twt), (B, 1))
    ti = jnp.tile(jnp.imag(twt), (B, 1))
    xr = jnp.real(s1).astype(jnp.float32)
    xi = jnp.imag(s1).astype(jnp.float32)
    bm = _pick_block(B * n1, 256)
    bn = _pick_block(n2, 128)
    xr = _pad_to(xr, bm, 0)
    xi = _pad_to(xi, bm, 0)
    wrp = _pad_to(wr, bn, 0)
    wip = _pad_to(wi, bn, 0)
    trp = _pad_to(_pad_to(tr, bm, 0), bn, 1)
    tip = _pad_to(_pad_to(ti, bm, 0), bn, 1)
    yr, yi = dft_matmul(xr, xi, wrp, wip, trp, tip, bm=bm, bn=bn,
                        interpret=interpret)
    t = jax.lax.complex(yr[:B * n1, :n2], yi[:B * n1, :n2])  # (B·n1, n2)

    # --- stage 2: DFT_n1 along j1 ---------------------------------------
    z = t.reshape(B, n1, n2)
    z = jnp.swapaxes(z, 1, 2).reshape(B * n2, n1)            # rows: k2
    z = dft_apply(z, inverse=inverse, interpret=interpret)   # (B·n2, n1)
    # output order k = k2 + n2·k1 → (B, k1, k2) ravel
    y = z.reshape(B, n2, n1)
    y = jnp.swapaxes(y, 1, 2).reshape(B, n)
    if inverse:
        # both stages applied 1/n2 and 1/n1 → already 1/n total
        pass
    return y
