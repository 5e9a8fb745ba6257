"""A plain plane-wave SCF in ``jax.numpy`` float32, at ``highest`` precision.

The same equations as the program's fused SCF step, written from their
definitions with dense DFT matrices (no FFT library, no plans, no pack
tables of the program):

* orbitals ``c`` on each k-point's cut-off sphere (``reference/sphere.py``
  gives the points), real space ``ψ = ifftn(box padded into the n³
  corner)``;
* ``ρ = (n³/ΔV) Σ_k w_k Σ_b |ψ_kb|²``, all bands occupied;
* ``v_eff = v_ext + v_H[ρ] + v_x[ρ]``, ``v_H = ifftn(4π/|G|² fftn ρ)``
  with ``G = 0`` dropped, Slater exchange ``v_x = −(4/3) C_x ρ^{1/3}``;
* ``H c = ½|G+k|² c + pack(fftn(v_eff ψ))``, ``G+k`` measured from the
  sphere's centre in units of ``2π/L``;
* per band-update step: preconditioned residuals ``d = (H c − λ c)/(1 +
  ½|G+k|²)``, made orthogonal to ``c`` and orthonormal (QR), then a
  Rayleigh-Ritz solve in ``span{c, d}`` keeps the lowest ``nb`` (QR again);
* ``E = Σ w ⟨c|T|c⟩ + ∫ρ v_ext + ½∫ρ v_H + ∫e_x`` on the new density;
* residual ``‖ρ_out − ρ_in‖ √ΔV / N_e``; Anderson (DIIS) mixing over the
  last ``history`` pairs after ``warmup`` linear iterations.

Everything runs on the device (``jnp``), f32 with every matmul at
``Precision.HIGHEST``.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import sphere

#: Slater exchange constant C_x = (3/4)(3/π)^{1/3}
CX = 0.75 * (3.0 / np.pi) ** (1.0 / 3.0)


def gaussian_wells(n: int, depth: float):
    """The external potential the configuration states: two Gaussian
    wells on the cube diagonal at 0.3·n and 0.7·n, width n/16 (f32)."""
    ax = jnp.arange(n, dtype=jnp.float32)
    width = n / 16.0
    v = jnp.zeros((n, n, n), jnp.float32)
    for c in (0.3 * n, 0.7 * n):
        g = jnp.exp(-((ax - c) ** 2) / (2 * width ** 2))
        v = v - depth * g[:, None, None] * g[None, :, None] * g[None, None, :]
    return v


class ReferenceSCF:
    def __init__(self, config: dict, npacked_max: int):
        self.n = n = int(config["n"])
        self.d = d = int(config["diameter"])
        self.kpts = [tuple(k) for k in config["kpts"]]
        self.nk = len(self.kpts)
        self.nb = int(config["nbands"])
        self.L = float(config.get("L") or n)
        self.dv = (self.L / n) ** 3
        self.weights = np.full(self.nk, 1.0 / self.nk)
        self.nelec = float(self.weights.sum() * self.nb)
        self.steps = int(config["inner_steps"])
        self.alpha = float(config["mix_alpha"])
        self.history = int(config["mix_history"])
        self.warmup = int(config["mix_warmup"])
        self.xc = bool(config["xc"])
        self.npm = int(npacked_max)
        step = 2 * np.pi / self.L
        # per k: box cell → packed lane (a zero lane where outside),
        # packed lane → box cell, and the kinetic row; padded to npm
        box_idx, lane_cell, kin = [], [], []
        valid = np.zeros((self.nk, self.npm), bool)
        for i, k in enumerate(self.kpts):
            pts = sphere.packed_points(d, k)
            npk = pts.size
            valid[i, :npk] = True
            inside = np.full(d ** 3, self.npm, np.int32)
            inside[pts] = np.arange(npk, dtype=np.int32)
            box_idx.append(inside)
            lane_cell.append(np.concatenate(
                [pts, np.zeros(self.npm - npk, np.int64)]).astype(np.int32))
            xyz = np.stack(np.unravel_index(pts, (d, d, d)), 1)
            off = xyz - ((d - 1) / 2.0 + np.asarray(k))
            row = np.zeros(self.npm, np.float64)
            row[:npk] = 0.5 * ((off ** 2).sum(1) * step ** 2)
            kin.append(row)
        kin = np.stack(kin)
        xs = np.arange(n)
        fr = np.fft.fftfreq(n, 1.0 / n)
        g2 = (fr[:, None, None] ** 2 + fr[None, :, None] ** 2
              + fr[None, None, :] ** 2) * step ** 2
        ph = 2j * np.pi / n
        self.tabs = {
            "box_idx": jnp.asarray(np.stack(box_idx)),
            "lane_cell": jnp.asarray(np.stack(lane_cell)),
            "valid": jnp.asarray(valid),
            "kin": jnp.asarray(kin.astype(np.float32)),
            "pre": jnp.asarray(np.where(valid, 1.0 / (1.0 + kin), 0.0)
                               .astype(np.float32)),
            "coulomb": jnp.asarray(np.where(
                g2 > 0, 4 * np.pi / np.where(g2 > 0, g2, 1.0), 0.0)
                .astype(np.float32)),
            # inverse pads d → n (1/n per axis), forward cuts n → d
            "w_pad": jnp.asarray((np.exp(ph * np.outer(xs, xs[:d])) / n)
                                 .astype(np.complex64)),
            "w_cut": jnp.asarray(np.exp(-ph * np.outer(xs[:d], xs))
                                 .astype(np.complex64)),
            "w_fwd": jnp.asarray(np.exp(-ph * np.outer(xs, xs))
                                 .astype(np.complex64)),
            "w_inv": jnp.asarray((np.exp(ph * np.outer(xs, xs)) / n)
                                 .astype(np.complex64)),
        }
        self._iterate = jax.jit(functools.partial(_iterate, self))
        self._density = jax.jit(functools.partial(_density, self))

    # ------------------------------------------------------ transforms
    def ein(self, spec, a, b):
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)

    def axes3(self, w, x):
        """Apply ``w`` along each of the last three axes of ``x``."""
        x = self.ein("Zz,...xyz->...xyZ", w, x)
        x = self.ein("Yy,...xyz->...xYz", w, x)
        return self.ein("Xx,...xyz->...Xyz", w, x)

    def to_real(self, t, c):
        """(nk, nb, npm) packed → (nk, nb, n, n, n) ψ."""
        d = self.d
        ext = jnp.concatenate(
            [c, jnp.zeros(c.shape[:2] + (1,), c.dtype)], axis=-1)
        box = jnp.take_along_axis(ext, t["box_idx"][:, None, :], axis=-1)
        return self.axes3(t["w_pad"], box.reshape(c.shape[:2] + (d, d, d)))

    def to_sphere(self, t, psi):
        """(nk, nb, n, n, n) → (nk, nb, npm), padded lanes zero."""
        box = self.axes3(t["w_cut"], psi).reshape(psi.shape[:2] + (-1,))
        c = jnp.take_along_axis(box, t["lane_cell"][:, None, :], axis=-1)
        return jnp.where(t["valid"][:, None, :], c, 0)

    def hartree(self, t, rho):
        g = self.axes3(t["w_fwd"], rho.astype(jnp.complex64))
        return jnp.real(self.axes3(t["w_inv"], g * t["coulomb"]))

    def apply_h(self, t, c, v_eff):
        return (t["kin"][:, None, :] * c
                + self.to_sphere(t, v_eff * self.to_real(t, c)))

    # ------------------------------------------------------ band update
    def orth(self, c):
        q, r = jnp.linalg.qr(jnp.swapaxes(c, -1, -2))
        ph = jnp.sign(jnp.real(jnp.diagonal(r, axis1=-2, axis2=-1)) + 1e-30)
        return jnp.swapaxes(q * ph[:, None, :], -1, -2)

    def band_step(self, t, c, v_eff):
        hc = self.apply_h(t, c, v_eff)
        lam = jnp.real(jnp.sum(jnp.conj(c) * hc, axis=-1))
        d = t["pre"][:, None, :] * (hc - lam[..., None] * c)
        ovl = self.ein("kip,kjp->kij", jnp.conj(c), d)
        d = self.orth(d - self.ein("kij,kip->kjp", ovl, c))
        hd = self.apply_h(t, d, v_eff)
        bb = jnp.concatenate([c, d], axis=1)
        hb = jnp.concatenate([hc, hd], axis=1)
        hm = self.ein("kip,kjp->kij", jnp.conj(bb), hb)
        hm = 0.5 * (hm + jnp.conj(jnp.swapaxes(hm, -1, -2)))
        eps, vecs = jnp.linalg.eigh(hm)
        new = self.ein("kin,kip->knp", vecs[:, :, :self.nb], bb)
        return self.orth(new), eps[:, :self.nb]


def _density(ref: ReferenceSCF, t, c):
    with jax.default_matmul_precision("highest"):
        psi = ref.to_real(t, c)
        w = jnp.asarray(ref.weights, jnp.float32)[:, None, None, None, None]
        return jnp.sum(w * jnp.abs(psi) ** 2, axis=(0, 1)) \
            * jnp.float32(ref.n ** 3 / ref.dv)


def _exchange(rho):
    r = jnp.maximum(rho, 0.0)
    r13 = jnp.cbrt(r)
    return -CX * r13 * r, -(4.0 / 3.0) * CX * r13


def _iterate(ref: ReferenceSCF, t, rho, c, hist, seen, v_ext):
    """One SCF iteration; returns (ρ_next, c, hist, seen, ρ_out, eps, E,
    residual).  ``hist`` = (ρ_in rows, residual rows), oldest first."""
    with jax.default_matmul_precision("highest"):
        v_eff = v_ext + ref.hartree(t, rho)
        if ref.xc:
            v_eff = v_eff + _exchange(rho)[1]
        eps = None
        for _ in range(ref.steps):
            c, eps = ref.band_step(t, c, v_eff)
        rho_out = _density(ref, t, c)
        dv = jnp.float32(ref.dv)
        w = jnp.asarray(ref.weights, jnp.float32)[:, None]
        e_kin = jnp.sum(w * jnp.sum(t["kin"][:, None, :] * jnp.abs(c) ** 2,
                                    axis=-1))
        e = (e_kin + jnp.sum(rho_out * v_ext) * dv
             + 0.5 * jnp.sum(rho_out * ref.hartree(t, rho_out)) * dv)
        if ref.xc:
            e = e + jnp.sum(_exchange(rho_out)[0]) * dv
        resid = (jnp.linalg.norm(rho_out - rho)
                 * jnp.float32(ref.dv ** 0.5 / ref.nelec))
        # Anderson / DIIS over the newest min(seen, history) pairs
        rin = rho.reshape(-1)
        res = rho_out.reshape(-1) - rin
        seen = seen + 1
        linear = rin + ref.alpha * res
        h = ref.history
        rho_h = jnp.concatenate([hist[0][1:], rin[None]], axis=0)
        res_h = jnp.concatenate([hist[1][1:], res[None]], axis=0)
        m = jnp.minimum(seen, h)
        live = (jnp.arange(h) >= h - m).astype(jnp.float32)
        r = res_h * live[:, None]
        a = ref.ein("ip,jp->ij", r, r) * (live[:, None] * live[None, :])
        a = a + jnp.diag(1.0 - live)
        top = jnp.concatenate([a, live[:, None]], axis=1)
        bot = jnp.concatenate([live, jnp.zeros((1,), jnp.float32)])[None]
        rhs = jnp.zeros((h + 1,), jnp.float32).at[h].set(1.0)
        beta = jnp.linalg.solve(jnp.concatenate([top, bot], axis=0),
                                rhs)[:h] * live
        mixed = ref.ein("i,ip->p", beta, rho_h + ref.alpha * res_h)
        use_linear = (seen <= ref.warmup) | (m < 2) \
            | ~jnp.all(jnp.isfinite(beta))
        rho_next = jnp.where(use_linear, linear, mixed).reshape(rho.shape)
    return rho_next, c, (rho_h, res_h), seen, rho_out, eps, e, resid


def run(ref: ReferenceSCF, c0, v_ext, iterations: int) -> dict:
    """``iterations`` SCF iterations from the orbitals ``c0``; per
    iteration the energy, residual, ρ_out and eigenvalues (host), and the
    mixed density after the last."""
    t = ref.tabs
    c = jnp.asarray(c0)
    v_ext = jnp.asarray(v_ext)
    rho = ref._density(t, c)
    nvol = ref.n ** 3
    hist = (jnp.zeros((ref.history, nvol), jnp.float32),
            jnp.zeros((ref.history, nvol), jnp.float32))
    seen = jnp.zeros((), jnp.int32)
    out = {"energy": [], "residual": [], "rho_out": [], "eps": []}
    for _ in range(iterations):
        rho, c, hist, seen, rho_out, eps, e, resid = ref._iterate(
            t, rho, c, hist, seen, v_ext)
        out["energy"].append(float(e))
        out["residual"].append(float(resid))
        out["rho_out"].append(np.asarray(rho_out))
        out["eps"].append(np.asarray(eps))
    out["rho_next"] = np.asarray(rho)
    return out
