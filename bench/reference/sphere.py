"""The plane-wave transform in float64 NumPy, from the sphere's definition.

A k-point's cut-off sphere has diameter ``d`` grid points, its centre at
``(d − 1)/2 + k`` in each axis of the ``d³`` bounding box ``[0, d − 1]³``.
Its packed coefficients are the box points within radius ``d/2`` of the
centre, in C order of (x, y, z).  The inverse places the box in the corner
``[0, d)³`` of the ``n³`` cube and takes the normalised inverse FFT; the
forward is the unnormalised FFT, read back at the packed points.
"""
from __future__ import annotations

import numpy as np
import scipy.fft

from ..common import reference_threads


def packed_points(d: int, kpt) -> np.ndarray:
    """Flat C-order indices of the packed points in the ``d³`` box."""
    c = (d - 1) / 2.0 + np.asarray(kpt, np.float64)
    ax = np.arange(d, dtype=np.float64)
    r2 = ((ax - c[0]) ** 2)[:, None, None] \
        + ((ax - c[1]) ** 2)[None, :, None] \
        + ((ax - c[2]) ** 2)[None, None, :]
    return np.flatnonzero(r2 <= (d / 2.0) ** 2)


def inverse(coeffs, d: int, kpt, n: int) -> np.ndarray:
    """One orbital's real-space cube, float64."""
    pts = packed_points(d, kpt)
    box = np.zeros(d ** 3, np.complex128)
    box[pts] = np.asarray(coeffs, np.complex128)[:pts.size]
    full = np.zeros((n, n, n), np.complex128)
    full[:d, :d, :d] = box.reshape(d, d, d)
    return scipy.fft.ifftn(full, workers=reference_threads())
