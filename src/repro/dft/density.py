"""Electron density from sphere-packed orbitals.

    ρ(r) = (n³/ΔV) Σ_k w_k Σ_b f_kb |ψ_kb(r)|²

with ψ = ifft(c) the *unnormalized* inverse transform of unit-norm packed
coefficients (Σ_G |c_G|² = 1 ⇒ Σ_r |ψ_r|² = 1/n³), so the prefactor makes
each occupied orbital integrate to one electron: Σ_r ρ ΔV = Σ w·f.

The per-k inverse plans come from the plan cache (one batched transform per
k-point, bands batched); the accumulation runs on the real-space cubes as
they come out of the plans — z-sharded on a multi-device grid — so the sum
over bands and k-points never gathers the mesh.

On a (batch × fft) 2D grid where ``nk`` divides the batch-axis size
(``basis.stacks_k``), all k-points' padded coefficients are stacked into
one ragged batch of nk·nbands and pushed through a *single* staged-padding
transform (``basis.stacked_hamiltonian_plans()`` — the same pair the
stacked H apply uses): the batch axes then shard k-points and bands
jointly, and nk per-k dispatches collapse into one.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def density_from_stacked(basis, c_pad, occ, seg: int = 0) -> jnp.ndarray:
    """Segment ``seg``'s density contribution from its padded
    (nk_seg, nbands, pad_width) coefficient stack.

    One nk_seg·nbands-batched transform; k and bands shard the batch
    axes.  Rides the same ragged ``StackedPlaneWaveFFT`` pair as the
    stacked Hamiltonian apply (padded per-k pack tables, shared d³→n³
    plan), so the stacked SCF path never needs the per-k sphere plans at
    all.  ``occ`` is the *full* (nk, nbands) table — the segment's rows
    are selected here, weights included, so summing the per-segment
    contributions (each carries the n³/ΔV prefactor, the sum is linear)
    gives exactly ρ.  With the default single segment this is the whole
    density.  Padded lanes never reach the cube (the unpack keeps only
    each line's z range), so they contribute nothing to ρ.
    Traceable — the jitted SCF step runs it under ``jax.jit``; ``occ``
    must be a trace-time constant (numpy).
    """
    inv, _ = basis.stacked_hamiltonian_plans(seg)
    nks, nb, npm = c_pad.shape
    idx = list(basis.segments[seg])
    w = (basis.weights[idx, None] * np.asarray(occ, np.float64)[idx]
         ).reshape(-1).astype(np.float32)
    with jax.named_scope("scf.density"):
        psi = inv(inv.unpack(c_pad.reshape(nks * nb, npm)))
        rho = jnp.tensordot(jnp.asarray(w), jnp.abs(psi) ** 2,
                            axes=(0, 0))
        return rho * jnp.float32(basis.n ** 3 / basis.dv)


def _density_stacked(basis, coeffs, occ) -> jnp.ndarray:
    """Per-k blocks → stacked-batch density, one batch per segment."""
    rho = None
    for s, seg in enumerate(basis.segments):
        inv, _ = basis.stacked_hamiltonian_plans(s)
        c_pad = inv.stack([coeffs[ik] for ik in seg]).reshape(
            len(seg), basis.nbands, inv.npacked_max)
        part = density_from_stacked(basis, c_pad, occ, seg=s)
        rho = part if rho is None else rho + part
    return rho


def density_from_orbitals(basis, coeffs, occ) -> jnp.ndarray:
    """ρ(r) on the n³ cube (f32) from per-k packed coefficient blocks.

    coeffs: list of (nbands, npacked_k) complex blocks, one per k-point
    occ:    (nk, nbands) occupation numbers f_kb
    """
    occ = np.asarray(occ, np.float64)
    if occ.shape != (basis.nk, basis.nbands):
        raise ValueError(
            f"occ shape {occ.shape} != (nk, nbands) = "
            f"({basis.nk}, {basis.nbands})")
    if getattr(basis, "stacks_k", False):
        return _density_stacked(basis, coeffs, occ)   # prefactor included
    rho = jnp.zeros((basis.n,) * 3, jnp.float32)
    for ik, c in enumerate(coeffs):
        inv, _ = basis.plans_for_k(ik)
        psi = inv(inv.unpack(c))              # (nb, n, n, n) sharded
        f = jnp.asarray((basis.weights[ik] * occ[ik]).astype(np.float32))
        rho = rho + jnp.tensordot(f, jnp.abs(psi) ** 2, axes=(0, 0))
    return rho * jnp.float32(basis.n ** 3 / basis.dv)


def electron_count(basis, rho) -> float:
    """∫ ρ dr — sanity invariant (should equal Σ_k w_k Σ_b f_kb)."""
    return float(jnp.sum(rho) * basis.dv)
