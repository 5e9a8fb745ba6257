"""Batched regular-grid transforms in float64 NumPy (``numpy.fft``
conventions: the inverse normalised by 1/n³, the forward not)."""
from __future__ import annotations

import numpy as np
import scipy.fft

from ..common import reference_threads


def inverse(x) -> np.ndarray:
    return scipy.fft.ifftn(np.asarray(x, np.complex128),
                           workers=reference_threads())


def forward(x) -> np.ndarray:
    return scipy.fft.fftn(np.asarray(x, np.complex128),
                          workers=reference_threads())
