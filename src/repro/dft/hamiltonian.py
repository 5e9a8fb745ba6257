"""Kohn-Sham Hamiltonian apply + band updates — per k-point or k-stacked.

H is applied in the packed sphere basis:

    (H c)_G = ½|G+k|² c_G  +  pack( fft( v_eff(r) · ifft(unpack(c)) ) )

— kinetic is diagonal on packed coefficients, the local potential is a
batched sphere→cube→sphere round-trip (inverse plan, pointwise multiply,
derived forward plan).  Bands ride the plans' batch dimension, so one H
apply per k-point is two batched distributed transforms regardless of the
band count — the matrix-matrix form the paper's batching argument is about.
When the basis stacks k-points (``basis.stacks_k``) the argument extends
across k: :func:`apply_hamiltonian_stacked` pushes *all* nk·nbands
orbitals through one ragged padded batch, so the whole sweep is two
distributed transforms regardless of nk as well.

The band update is preconditioned all-band descent in its locally-optimal
form (LOBPCG without the history block): each step does a Rayleigh-Ritz
solve in the 2·nb-dimensional span of the current bands and their
preconditioned residuals, which picks the optimal step length per band
automatically.  The preconditioner is the Teter-style kinetic damping
1/(1 + ½|G+k|²).

Two band-update engines share that math:

  * the **per-k** path (``update_bands`` / the pipelined loop inside
    ``update_bands_all_k``) runs the Gram builds, Rayleigh-Ritz solves
    and orthonormalizations k-point by k-point in eager Python — the
    fallback and equivalence oracle;
  * the **stacked** engine (:func:`update_bands_stacked`) runs them as
    batched einsums / batched ``eigh``/``qr`` over one padded
    ``(nk, nbands, npacked_max)`` coefficient array, with the kinetic
    and preconditioner served as dense per-k tables
    (``basis.stacked_band_tables()``).  Padded lanes hold exact zeros in
    coefficients, H·c blocks and tables alike, so they contribute exact
    zeros to every reduction and the two engines agree bitwise on CPU
    (asserted to 1e-10 in tests).  One sweep is **two** distributed
    transforms and **zero** per-k Python linalg calls, whatever nk is —
    ``PERK_LINALG_CALLS`` and ``FftPlan.executions`` instrument exactly
    that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs.metrics import global_metrics
from repro.obs.trace import get_tracer

#: process-wide count of per-k eager linalg calls (descent-direction
#: builds and Rayleigh-Ritz solves dispatched for a single k-point) —
#: lets tests assert the stacked engine performs zero of them.
PERK_LINALG_CALLS = 0

global_metrics().register_probe(
    "dft", lambda: {"per_k_linalg_calls": PERK_LINALG_CALLS})

#: the band update's dense linalg (descent directions, Gram, ``eigh``,
#: QR, Rayleigh-Ritz) as a compiled program names it
SUBSPACE_SCOPE = "scf.subspace"


def _replicated(basis, x):
    """Pin an eager coefficient block onto the basis mesh, replicated.

    The band update mixes shard_map outputs (packed H·c blocks, sharded
    over the batch axes) with replicated/single-device blocks (QR and
    Rayleigh-Ritz outputs) in eager concatenates and matmuls — exactly
    the mixed-placement situation ``ProcGrid.replicate`` exists for
    (reported eigenvalues came out doubled on a 2×2 grid before every
    block was pinned; the eigenvectors survived only because a uniform
    scaling has the same eigenbasis).  No-op on a 1-device grid, so
    results there are bitwise unchanged.
    """
    return basis.grid.replicate(x)


def apply_hamiltonian(basis, ik: int, c, v_eff):
    """H·c for one k-point block c of shape (nbands, npacked_k).

    ``v_eff`` is the real (n, n, n) effective local potential.  Plans are
    fetched through the plan cache on every call — after the first SCF
    iteration these are all hits.
    """
    inv, fwd = basis.plans_for_k(ik)
    kin = basis.kinetic(ik)
    psi = inv(inv.unpack(c))                  # sphere → real space, batched
    vpsi = fwd(psi * v_eff)                   # apply V, truncate back
    return kin[None, :] * c + inv.pack(vpsi)


def apply_hamiltonian_pipelined(basis, blocks, v_eff):
    """H·c for *all* k-points, double-buffering the sphere→cube transforms.

    The serial loop alternates "all_to_all-heavy inverse transform" and
    "compute-heavy cube-space potential apply" per k-point, leaving the
    interconnect idle during the apply.  Here k-point ``ik+1``'s inverse
    transform (its comm) is dispatched *before* ``ik``'s potential apply,
    so on an asynchronous backend the next k's all_to_alls are in flight
    while the current k's cube multiply runs — the ROADMAP "pipeline
    k-point transforms" item.  Per-k operations and their order are
    identical to :func:`apply_hamiltonian`, so results match the serial
    path bit-for-bit; only the dispatch interleaving differs.

    ``blocks``: list of (nbands, npacked_k) coefficient blocks, one per k.
    Returns the list of H·c blocks in k order.
    """
    nk = len(blocks)
    if nk == 0:
        return []
    plans = [basis.plans_for_k(ik) for ik in range(nk)]
    inv0 = plans[0][0]
    psi = inv0(inv0.unpack(blocks[0]))        # prologue: k=0 in flight
    out = []
    for ik in range(nk):
        psi_next = None
        if ik + 1 < nk:                       # issue k+1's comm first …
            inv_n = plans[ik + 1][0]
            psi_next = inv_n(inv_n.unpack(blocks[ik + 1]))
        inv, fwd = plans[ik]                  # … then apply V for k
        vpsi = fwd(psi * v_eff)
        out.append(basis.kinetic(ik)[None, :] * blocks[ik]
                   + inv.pack(vpsi))
        psi = psi_next
    return out


def apply_hamiltonian_padded(basis, c_pad, v_eff, kin_pad=None,
                             seg: int = 0):
    """H·c on one segment's padded ``(nk_seg, nbands, pad_width)`` stack.

    The array-native core of the stacked route: one batched inverse
    transform, one cube-space ``v_eff`` multiply, one batched forward —
    two distributed transforms for every k-point and band at once — plus
    the dense padded kinetic diagonal (``basis.stacked_band_tables(seg)``)
    applied as a broadcast multiply.  Padded lanes stay exact zeros: the
    pack gather reads them from the zero slot and the kinetic table is
    zero there, so H·c is as inert on padding as c itself.  Traceable
    (the jitted SCF step runs it under ``jax.jit``).

    The sphere↔cube legs go through the plans' fused entry points
    (``unpack_transform`` / ``transform_pack``): with ``backend="pallas"``
    these route the unpack + first iDFT stage and the last DFT stage +
    pack gather through the fused sphere-pack kernels (no d³ cube ever
    materialized); on every other backend they fall back to the composed
    ``unpack``/plan/``pack`` calls, which are bitwise-identical — so this
    one code path serves both the oracle and the optimized route.
    """
    if kin_pad is None:
        kin_pad = basis.stacked_band_tables(seg).kinetic
    inv, fwd = basis.stacked_hamiltonian_plans(seg)
    nk, nb, npm = c_pad.shape
    with jax.named_scope("scf.hamiltonian"):
        psi = inv.unpack_transform(c_pad.reshape(nk * nb, npm))
        vc = fwd.transform_pack(psi * v_eff).reshape(nk, nb, npm)
        return kin_pad[:, None, :] * c_pad + vc


def apply_hamiltonian_stacked(basis, blocks, v_eff):
    """H·c for *all* k-points in ragged stacked batches, one per segment.

    The pipelined path still dispatches one sphere→cube→sphere round trip
    per k-point; here each segment's bands ride a single
    ``(nk_seg·nbands, pad_width)`` padded batch through the basis's
    ``StackedPlaneWaveFFT`` pair (:func:`apply_hamiltonian_padded`):
    two distributed transforms per H sweep per segment regardless of nk
    and nbands (one pair total with the default single segment).
    Raggedness (distinct ``npacked_k``) is absorbed by the padded pack
    tables, whose dump/zero slots keep padded lanes inert; the kinetic
    diagonal rides the dense padded table, which matches the per-k
    ladders bitwise on valid lanes.  Per-orbital math is identical to
    :func:`apply_hamiltonian` — same rectangular DFT stages, same
    pack/unpack values — so stacked ≡ pipelined ≡ serial per k.

    ``blocks``: list of (nbands, npacked_k) coefficient blocks, one per k.
    Returns the list of H·c blocks in k order.
    """
    if len(blocks) == 0:
        return []
    out = [None] * len(blocks)
    for s, seg in enumerate(basis.segments):
        inv, _ = basis.stacked_hamiltonian_plans(s)
        c_pad = inv.stack([blocks[ik] for ik in seg]).reshape(
            len(seg), inv.nbands, inv.npacked_max)
        hc = apply_hamiltonian_padded(basis, c_pad, v_eff, seg=s)
        hcs = inv.split(hc.reshape(len(seg) * inv.nbands, inv.npacked_max))
        for j, ik in enumerate(seg):
            out[ik] = hcs[j]
    return out


def orthonormalize(c):
    """QR re-orthonormalization; bands are rows of c."""
    q, r = jnp.linalg.qr(c.T)
    # fix the phase so the update is continuous across iterations
    ph = jnp.sign(jnp.real(jnp.diagonal(r)) + 1e-30)
    return (q * ph[None, :]).T


def _pad_lanes(x, npm: int):
    """Zero-pad the packed-coefficient axis of ``x`` to ``npm`` lanes.

    Both band-update engines contract their Gram/descent linalg over
    exactly ``npacked_max`` lanes — f32 GEMM reductions are *not*
    invariant under zero-padding the contraction length (the kernel's
    blocking changes with it), so running the per-k oracle over npk
    lanes and the stacked engine over npacked_max would leave an ~1e-5
    reduction-noise gap between mathematically identical results.
    Padding both to the same length makes the two engines execute
    identical kernels on identical operands: bitwise agreement, not
    approximate.
    """
    return jnp.pad(x, ((0, 0), (0, npm - x.shape[-1])))


def _padded_precond(basis, ik: int):
    """Per-k Teter damping row, zero-padded to the k's segment lane width.

    Valid lanes carry the same f32 ``1/(1 + kinetic)`` arithmetic as the
    stacked ``precond`` table row (bitwise), built locally so the per-k
    fallback never touches the band-tables cache entry — its plan-cache
    ledger stays purely per-k traffic.  Padding to ``pad_width(ik)``
    (``npacked_max`` with the default single segment) keeps the per-k
    oracle's contraction lengths equal to the stacked engine's.
    """
    pre = 1.0 / (1.0 + basis.kinetic(ik))
    return jnp.pad(pre, (0, basis.pad_width(ik) - pre.shape[0]))


def update_bands(basis, ik: int, c, v_eff, *, steps: int = 3):
    """Locally-optimal preconditioned band update for k-point ``ik``.

    Per step: residuals r_b = (H − λ_b)c_b, preconditioned and
    orthonormalized against the bands, then a Rayleigh-Ritz solve in
    span{c, P r} keeps the lowest ``nbands`` vectors.  Two batched H
    applies per step, riding the per-k sphere plans; the linalg runs as
    singleton-batch dispatches of the stacked kernels over lanes padded
    to the k's segment width (:func:`_pad_lanes`), so this serial oracle
    and the batched engine agree bit for bit.

    Returns (rotated coefficients, eigenvalues ascending, n_h_applies).
    """
    npm = basis.pad_width(ik)
    pre = _padded_precond(basis, ik)
    napply = 0
    eps = None
    c = _replicated(basis, c)
    for _ in range(steps):
        hc = _replicated(basis, apply_hamiltonian(basis, ik, c, v_eff))
        napply += 1
        d = _replicated(basis, _descent_direction(c, hc, pre, npm))
        hd = _replicated(basis, apply_hamiltonian(basis, ik, d, v_eff))
        napply += 1
        c, eps = _rayleigh_ritz(c, d, hc, hd, npm)
    return c, eps, napply


def _descent_direction(c, hc, pre, npm: int):
    """Per-k preconditioned residual block, orthogonal to the bands.

    A singleton-batch dispatch of :func:`_descent_direction_stacked`
    over npacked_max-padded operands — one per-k eager linalg call,
    counted by ``PERK_LINALG_CALLS``.  ``pre`` is the padded per-k
    damping row.  Returns the unpadded (nbands, npk) block.
    """
    global PERK_LINALG_CALLS
    PERK_LINALG_CALLS += 1
    npk = c.shape[-1]
    d = _descent_direction_stacked(_pad_lanes(c, npm)[None],
                                   _pad_lanes(hc, npm)[None], pre[None])
    return d[0, :, :npk]


def _rayleigh_ritz(c, d, hc, hd, npm: int):
    """Per-k lowest-nb Ritz vectors of span{c, d}; (c', eps ascending).

    Singleton-batch dispatch of :func:`_rayleigh_ritz_stacked` over
    npacked_max-padded blocks — one per-k eager linalg call, counted by
    ``PERK_LINALG_CALLS``.
    """
    global PERK_LINALG_CALLS
    PERK_LINALG_CALLS += 1
    npk = c.shape[-1]
    cp, eps = _rayleigh_ritz_stacked(
        _pad_lanes(c, npm)[None], _pad_lanes(d, npm)[None],
        _pad_lanes(hc, npm)[None], _pad_lanes(hd, npm)[None])
    return cp[0, :, :npk], eps[0]


# ------------------------------------------------- stacked (batched) engine
def _orthonormalize_stacked(c):
    """Batched QR re-orthonormalization over (nk, nbands, npacked_max).

    Each k's matrix is the per-k one with zero rows appended for the
    padded lanes; Householder QR keeps those rows exactly zero (the
    reflectors never mix them in), so padding survives the batched solve
    untouched and the valid lanes match :func:`orthonormalize` bitwise.
    """
    q, r = jnp.linalg.qr(jnp.swapaxes(c, -1, -2))       # (nk, np, nb)
    ph = jnp.sign(jnp.real(
        jnp.diagonal(r, axis1=-2, axis2=-1)) + 1e-30)   # (nk, nb)
    return jnp.swapaxes(q * ph[:, None, :], -1, -2)


def _descent_direction_stacked(c, hc, pre):
    """Batched preconditioned residuals, orthogonal to the current bands.

    The per-k ``_descent_direction`` as three einsums over the stacked
    axis: Rayleigh quotients, the projected gradient, and the
    projection of span{c} out of the preconditioned block.  ``pre`` is
    the masked table, so padded lanes come out exact zeros.
    """
    lam = jnp.real(jnp.sum(jnp.conj(c) * hc, axis=-1))  # (nk, nb)
    grad = hc - lam[..., None] * c
    d = pre[:, None, :] * grad
    ovl = jnp.einsum("kip,kjp->kij", jnp.conj(c), d)    # ⟨c_i|d_j⟩ per k
    return _orthonormalize_stacked(
        d - jnp.einsum("kij,kip->kjp", ovl, c))


def _rayleigh_ritz_stacked(c, d, hc, hd):
    """Batched lowest-nb Ritz vectors of span{c, d} for every k at once.

    One (nk, 2nb, 2nb) blocked Gram build (padded lanes add exact zeros),
    one nk-batched dense ``eigh``, one batched back-rotation — no per-k
    Python dispatch anywhere.  Returns (c', eps) with eps ascending per k.
    """
    nb = c.shape[1]
    bb = jnp.concatenate([c, d], axis=1)                # (nk, 2nb, np)
    hb = jnp.concatenate([hc, hd], axis=1)
    hmat = jnp.einsum("kip,kjp->kij", jnp.conj(bb), hb)
    hmat = 0.5 * (hmat + jnp.conj(jnp.swapaxes(hmat, -1, -2)))
    eps, vecs = jnp.linalg.eigh(hmat)                   # nk-batched solve
    new = jnp.einsum("kin,kip->knp", vecs[:, :, :nb], bb)
    return _orthonormalize_stacked(new), eps[:, :nb]


def update_bands_stacked(basis, c_pad, v_eff, *, steps: int = 3,
                         tables=None, seg: int = 0):
    """Locally-optimal band update on one segment's padded
    (nk_seg, nbands, pad_width) coefficient stack — every stage batched
    over the segment's k-points.

    The per-k math of :func:`update_bands` with the orchestration layer
    removed: each step is two stacked H sweeps (two distributed
    transforms each, via :func:`apply_hamiltonian_padded`), one batched
    descent-direction build, and one nk-batched blocked Rayleigh-Ritz
    solve — a handful of XLA calls total, none of them per-k.  Padded
    lanes carry exact zeros end to end (zero coefficients, zero table
    entries, zero Gram contributions), so results on valid lanes equal
    the per-k path bitwise on CPU.  Fully traceable — the jitted SCF
    step runs it under ``jax.jit`` with donated buffers.

    Returns (updated stack, eigenvalues (nk, nbands) ascending per k,
    H sweeps executed).
    """
    if tables is None:
        tables = basis.stacked_band_tables(seg)
    kin, pre = tables.kinetic, tables.precond
    c = _replicated(basis, c_pad)
    eps = None
    nsweep = 0
    for _ in range(steps):
        hc = _replicated(basis, apply_hamiltonian_padded(basis, c, v_eff,
                                                         kin, seg=seg))
        nsweep += 1
        with jax.named_scope(SUBSPACE_SCOPE):
            d = _replicated(basis, _descent_direction_stacked(c, hc, pre))
        hd = _replicated(basis, apply_hamiltonian_padded(basis, d, v_eff,
                                                         kin, seg=seg))
        nsweep += 1
        with jax.named_scope(SUBSPACE_SCOPE):
            c, eps = _rayleigh_ritz_stacked(c, d, hc, hd)
    return c, eps, nsweep


def update_bands_all_k(basis, coeffs, v_eff, *, steps: int = 3,
                       stacked: bool | None = None):
    """All-k locally-optimal band update — stacked engine or pipelined per-k.

    The per-k math is :func:`update_bands` exactly — same preconditioner,
    same Rayleigh-Ritz step, same op order within each k.
    ``stacked=None`` (the default) routes through
    :func:`update_bands_stacked` when ``basis.stacks_k`` — the whole
    update runs on one padded (nk, nbands, npacked_max) stack, two
    distributed transforms per sweep and zero per-k Python linalg — and
    falls back to the pipelined per-k loop (k+1's sphere→cube
    all_to_alls dispatched before k's potential apply, Gram/Rayleigh-Ritz
    per k) otherwise; pass True/False to force a path, e.g. to use the
    pipelined loop as the equivalence oracle.  Because no arithmetic
    crosses k-points, both routes match running ``update_bands`` serially
    per k.

    Returns (new coefficient blocks, eigenvalues list [(nbands,)] per k,
    H sweeps executed — each sweep is one H apply per k-point).
    """
    nk = len(coeffs)
    if stacked is None:
        stacked = bool(getattr(basis, "stacks_k", False))
    tr = get_tracer()
    if stacked:
        cs = [None] * nk
        eps_out = [None] * nk
        nsweep = 0
        with tr.span("band_update", route="stacked", nk=nk, steps=steps,
                     segments=len(basis.segments)):
            for s, seg in enumerate(basis.segments):
                inv, _ = basis.stacked_hamiltonian_plans(s)
                c_pad = inv.stack([coeffs[ik] for ik in seg]).reshape(
                    len(seg), inv.nbands, inv.npacked_max)
                c_pad, eps, nsweep = update_bands_stacked(
                    basis, c_pad, v_eff, steps=steps, seg=s)
                outs = inv.split(c_pad.reshape(len(seg) * inv.nbands,
                                               inv.npacked_max))
                for j, ik in enumerate(seg):
                    cs[ik] = outs[j]
                    eps_out[ik] = eps[j]
        return cs, eps_out, nsweep
    cs = [_replicated(basis, c) for c in coeffs]
    npms = [basis.pad_width(ik) for ik in range(nk)]
    pres = [_padded_precond(basis, ik) for ik in range(nk)]
    eps_out = [None] * nk
    nsweep = 0
    for _ in range(steps):
        hcs = [_replicated(basis, hc)
               for hc in apply_hamiltonian_pipelined(basis, cs, v_eff)]
        nsweep += 1
        ds = [_replicated(basis,
                          _descent_direction(cs[ik], hcs[ik], pres[ik],
                                             npms[ik]))
              for ik in range(nk)]
        hds = [_replicated(basis, hd)
               for hd in apply_hamiltonian_pipelined(basis, ds, v_eff)]
        nsweep += 1
        for ik in range(nk):
            cs[ik], eps_out[ik] = _rayleigh_ritz(cs[ik], ds[ik], hcs[ik],
                                                 hds[ik], npms[ik])
    return cs, eps_out, nsweep
