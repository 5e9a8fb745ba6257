"""Mixing-driven SCF loop over the plane-wave basis.

Each outer iteration: build v_eff = v_ext + v_H[ρ] + v_xc[ρ], update all
bands at every k-point (batched H applies through cached plans), rebuild
the density from the new orbitals, evaluate the total energy

    E = Σ_k w_k Σ_b f ⟨c|T|c⟩ + ∫ρ v_ext + E_H[ρ] + E_xc[ρ]

and mix ρ_in/ρ_out — plain linear mixing for the warm-up iterations, then
Anderson/Pulay acceleration on the stored residual history.  Convergence is
declared when |ΔE| stays below ``e_tol`` (and the density residual below
``r_tol``) after the warm-up.

The orchestration is eager Python by default: every transform goes
through a plan fetched from the process-global ``PlanCache`` (the per-plan
executors are jitted ``shard_map``s), so the cache's hit counter is the
subsystem's plan-reuse ledger and ``SCFResult.transforms`` counts real
batched 3D transforms.

``SCFConfig(jit_step=True)`` (requires the stacked band-update route)
fuses one whole outer iteration — v_eff build, the stacked band update,
density rebuild, total energy, residual, **and the density mixing** —
into a single jit-compiled step with donated density/band/mixer buffers:
after the first trace, an SCF iteration is one XLA dispatch with zero
per-k Python work.  Plans and band tables are fetched from the PlanCache
eagerly at trace time, so cache traffic stays honestly accounted (it is
counted once per trace, not once per iteration — the whole point);
``SCFResult.transforms`` keeps the same analytic per-iteration count as
the eager path.  The mixer runs in f32 inside the step (the eager
AndersonMixer accumulates its DIIS history in f64), so jitted and eager
runs agree to mixing precision, not bitwise; with plain linear mixing
(``mix_history<=1``) the two paths perform identical f32 arithmetic.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ProcGrid, global_plan_cache
from repro.core.policy import ExecPolicy
from repro.obs.metrics import program_memory
from repro.obs.trace import get_tracer

from .basis import PlaneWaveBasis
from .density import (density_from_orbitals, density_from_stacked,
                      electron_count)
from .hamiltonian import (orthonormalize, update_bands, update_bands_all_k,
                          update_bands_stacked)
from .hartree import HartreeSolver
from .potentials import gaussian_wells, lda_exchange


# -------------------------------------------------------------------- mixing
class LinearMixer:
    """ρ ← ρ_in + α (ρ_out − ρ_in)."""

    def __init__(self, alpha: float = 0.5):
        self.alpha = float(alpha)

    def mix(self, rho_in, rho_out):
        return rho_in + self.alpha * (rho_out - rho_in)


class AndersonMixer:
    """Anderson/Pulay (DIIS) density mixing on the residual history.

    Minimizes |Σ_i β_i r_i|² over Σ β_i = 1 (r_i = ρ_out,i − ρ_in,i), then
    takes ρ ← Σ β_i (ρ_in,i + α r_i).  Falls back to linear mixing for the
    first ``warmup`` iterations and whenever the DIIS system is singular.
    """

    def __init__(self, alpha: float = 0.5, history: int = 4,
                 warmup: int = 2):
        self.alpha = float(alpha)
        self.history = int(history)
        self.warmup = int(warmup)
        self._rho_in: list[np.ndarray] = []
        self._res: list[np.ndarray] = []
        self._seen = 0

    def mix(self, rho_in, rho_out):
        rin = np.asarray(rho_in, np.float64).ravel()
        res = np.asarray(rho_out, np.float64).ravel() - rin
        self._rho_in.append(rin)
        self._res.append(res)
        if len(self._res) > self.history:
            self._rho_in.pop(0)
            self._res.pop(0)
        self._seen += 1
        m = len(self._res)
        if self._seen <= self.warmup or m < 2:
            mixed = rin + self.alpha * res
        else:
            r = np.stack(self._res)                       # (m, N)
            a = np.empty((m + 1, m + 1))
            a[:m, :m] = r @ r.T
            a[m, :m] = a[:m, m] = 1.0
            a[m, m] = 0.0
            rhs = np.zeros(m + 1)
            rhs[m] = 1.0
            try:
                beta = np.linalg.solve(a, rhs)[:m]
            except np.linalg.LinAlgError:
                beta = None
            if beta is None or not np.all(np.isfinite(beta)):
                mixed = rin + self.alpha * res
            else:
                mixed = beta @ (np.stack(self._rho_in)
                                + self.alpha * r)
        return jnp.asarray(mixed.astype(np.float32).reshape(rho_in.shape))


# ------------------------------------------------------------- jitted mixing
def jit_mixer_init(nvol: int, history: int):
    """Mixer state for the fused (jit-compiled) SCF step.

    Linear mixing (``history <= 1``) needs only the iteration counter;
    Anderson/Pulay keeps fixed-size ρ_in/residual history buffers (rows
    ordered oldest→newest, zero-filled until ``seen`` fills them) so the
    state is a fixed-shape pytree the step can donate and return.
    """
    state = {"seen": jnp.zeros((), jnp.int32)}
    if history > 1:
        state["rho_in"] = jnp.zeros((history, nvol), jnp.float32)
        state["res"] = jnp.zeros((history, nvol), jnp.float32)
    return state


def jit_mix(state, rho_in, rho_out, *, alpha: float, warmup: int):
    """One mixing step inside the fused sweep; returns (state', ρ_mixed).

    The traceable twin of ``AndersonMixer.mix``/``LinearMixer.mix``: the
    same bordered DIIS system with rows that are not yet (or no longer)
    in the history pinned to identity rows, the same linear-mixing
    fallback for the warm-up iterations and whenever the solve goes
    non-finite.  Runs in f32 (the eager mixer accumulates in f64), and
    with ``history <= 1`` it is exactly the eager linear mixer's f32
    arithmetic.
    """
    rin = rho_in.reshape(-1)
    res = rho_out.reshape(-1) - rin
    seen = state["seen"] + 1
    linear = rin + jnp.float32(alpha) * res
    if "rho_in" not in state:                     # plain linear mixing
        return {"seen": seen}, linear.reshape(rho_in.shape)
    h = state["rho_in"].shape[0]
    rho_hist = jnp.concatenate([state["rho_in"][1:], rin[None]], axis=0)
    res_hist = jnp.concatenate([state["res"][1:], res[None]], axis=0)
    m = jnp.minimum(seen, h)
    valid = jnp.arange(h) >= h - m                # newest rows are valid
    r = res_hist * valid[:, None].astype(res_hist.dtype)
    a = r @ r.T
    vf = valid.astype(a.dtype)
    a = a * (vf[:, None] * vf[None, :])           # invalid rows/cols → 0
    a = a + jnp.diag(1.0 - vf)                    # … pinned to identity
    top = jnp.concatenate([a, vf[:, None]], axis=1)
    bot = jnp.concatenate([vf, jnp.zeros((1,), a.dtype)])[None, :]
    rhs = jnp.zeros((h + 1,), a.dtype).at[h].set(1.0)
    beta = jnp.linalg.solve(jnp.concatenate([top, bot], axis=0), rhs)[:h]
    beta = beta * vf
    mixed = beta @ (rho_hist + jnp.float32(alpha) * res_hist)
    use_linear = ((seen <= warmup) | (m < 2)
                  | ~jnp.all(jnp.isfinite(beta)))
    out = jnp.where(use_linear, linear, mixed)
    state = {"seen": seen, "rho_in": rho_hist, "res": res_hist}
    return state, out.reshape(rho_in.shape)


# -------------------------------------------------------------------- config
@dataclasses.dataclass
class SCFConfig:
    n: int = 16                       # FFT cube width
    diameter: int | None = None       # sphere diameter (default n // 2)
    nbands: int = 4
    nocc: int | None = None           # occupied bands (default: all)
    kpts: tuple = ((0.0, 0.0, 0.0),)  # reduced coords, units 2π/L
    weights: tuple | None = None
    L: float | None = None            # cell side (default n, spacing 1)
    depth: float = 4.0                # Gaussian-well depth
    xc: bool = True                   # include the LDA exchange term
    max_iter: int = 50
    e_tol: float = 1e-5               # |ΔE| convergence threshold
    r_tol: float = 1e-4               # density-residual threshold (per elec)
    inner_steps: int = 4              # band-update steps per k per outer it
    mix_alpha: float = 0.7
    mix_history: int = 5
    mix_warmup: int = 2               # linear iterations before Anderson
    seed: int = 0
    pipeline: bool = True             # double-buffer the per-k transforms
    stack_k: bool | None = None       # ragged-stack the H apply across k
                                      # (None: auto via basis.stacks_k;
                                      # True requires pipeline=True)
    jit_step: bool = False            # fuse mixing + band update + density
                                      # into one jitted step with donated
                                      # buffers (requires the stacked
                                      # band-update route)
    batch_axes: tuple | None = None   # grid axes carrying the band batch
    fft_axes: tuple | None = None     # grid axes carrying the transforms
    segment_padding: float | None = None
                                      # per-segment padding budget for the
                                      # ragged k-stacking (None: one global
                                      # npacked_max segment, the pre-
                                      # segmentation behaviour)
    policy: ExecPolicy | None = None
    backend: str | None = None        # line-DFT backend preference; None
                                      # resolves explicit > policy.backend
                                      # > "matmul" (see PlaneWaveBasis)


@dataclasses.dataclass
class SCFResult:
    converged: bool
    iterations: int
    energy: float
    energies: list[float]             # total energy per outer iteration
    residuals: list[float]            # |ρ_out − ρ_in| per electron
    eigenvalues: np.ndarray           # (nk, nbands), ascending per k
    rho: jnp.ndarray
    transforms: int                   # per-band 3D transforms executed
                                      # (plan calls batch nbands of them)
    seconds: float
    cache_stats: dict                 # global PlanCache counters (delta)
    grid_shape: tuple = ()            # processing-grid shape the run used
    stacked: bool = False             # H sweeps rode the k-stacked batch
    padding_fraction: float = 0.0     # padded lanes / total stacked lanes
    band_update: str = "per-k"        # band-update route: "stacked" (the
                                      # batched engine) or "per-k"
    backend: str = "matmul"           # resolved line-DFT backend the basis
                                      # ran (what plans were built with —
                                      # bench records persist this so a
                                      # silent downgrade is visible)
    segments: int = 1                 # ragged-stacking segment count
    segment_padding_fractions: tuple = ()
                                      # realized per-segment padding, each
                                      # ≤ the configured segment_padding
    jitted: bool = False              # iterations ran as the fused jit step
    compile_seconds: float = 0.0      # the fused step's trace + compile
                                      # (0 when eager); ``seconds`` and the
                                      # first iteration record include it
    step_memory: dict = dataclasses.field(default_factory=dict)
                                      # the compiled step's
                                      # ``memory_analysis()`` in bytes:
                                      # argument, output, temp, code
    #: per-iteration telemetry: one dict per outer iteration with
    #: {iteration, energy, residual, seconds, transforms} — the record
    #: the observability layer attaches so a slow run can be broken
    #: down without re-running under a profiler
    iteration_records: list = dataclasses.field(default_factory=list)

    @property
    def transforms_per_s(self) -> float:
        return self.transforms / max(self.seconds, 1e-9)

    @property
    def seconds_per_iteration(self) -> float:
        """Mean wall time of one outer SCF iteration."""
        return self.seconds / max(self.iterations, 1)


# -------------------------------------------------------------------- energy
def total_energy(basis, coeffs, rho, v_ext, hartree: HartreeSolver, occ,
                 *, xc: bool = True) -> tuple[float, dict]:
    """E[{ψ}, ρ] and its components; ρ should be the orbitals' density."""
    occ = np.asarray(occ, np.float64)
    e_kin = 0.0
    for ik, c in enumerate(coeffs):
        kin = basis.kinetic(ik)
        per_band = jnp.sum(kin[None, :] * jnp.abs(c) ** 2, axis=1)
        e_kin += float(basis.weights[ik]
                       * (occ[ik] @ np.asarray(per_band, np.float64)))
    dv = basis.dv
    e_ext = float(jnp.sum(rho * v_ext) * dv)
    vh = hartree(rho)
    e_h = hartree.energy(rho, vh)
    if xc:
        e_x, _ = lda_exchange(rho)
        e_xc = float(jnp.sum(e_x) * dv)
    else:
        e_xc = 0.0
    total = e_kin + e_ext + e_h + e_xc
    return total, {"kinetic": e_kin, "external": e_ext, "hartree": e_h,
                   "xc": e_xc, "total": total}


def total_energy_stacked(basis, c_pad, rho, v_ext, hartree: HartreeSolver,
                         occ, *, xc: bool = True, tables=None):
    """Traceable E[{ψ}, ρ] on the padded per-segment coefficient stacks.

    ``c_pad`` is either one (nk_seg, nbands, pad_width) stack (the
    single-segment case) or a tuple/list of them, one per basis segment
    in segment order.  The kinetic term is one masked einsum per segment
    against the dense padded kinetic table (padded lanes contribute
    exact zeros), everything else is cube arithmetic — no per-k Python,
    no host transfers, so the fused jit step can inline it.  Accumulates
    in f32 where the eager :func:`total_energy` reduces per-band terms
    in host f64; the two agree to f32 reduction precision (~1e-6 on the
    demo problems).
    """
    if not isinstance(c_pad, (tuple, list)):
        c_pad = (c_pad,)
    if tables is None:
        # eager callers only — the jitted step always passes tables,
        # fetched at trace time, so this branch never runs under tracing
        tables = [basis.stacked_band_tables(s)  # noqa: FFTB202
                  for s in range(len(c_pad))]
    elif not isinstance(tables, (tuple, list)):
        tables = (tables,)
    occ64 = np.asarray(occ, np.float64)  # noqa: FFTB201 — host array
    e_kin = jnp.float32(0.0)
    for s, (cs, tab) in enumerate(zip(c_pad, tables)):
        idx = list(basis.segments[s])
        w = jnp.asarray((basis.weights[idx, None] * occ64[idx]
                         ).astype(np.float32))              # (nk_seg, nb)
        per_band = jnp.sum(tab.kinetic[:, None, :] * jnp.abs(cs) ** 2,
                           axis=-1)
        e_kin = e_kin + jnp.sum(w * per_band)
    dv = jnp.float32(basis.dv)
    e_ext = jnp.sum(rho * v_ext) * dv
    vh = hartree(rho)
    e_h = jnp.sum(rho * vh) * (0.5 * dv)
    e_xc = jnp.sum(lda_exchange(rho)[0]) * dv if xc else 0.0
    return e_kin + e_ext + e_h + e_xc


# -------------------------------------------------------------------- driver
def make_scf_step(cfg: SCFConfig, basis, hartree, occ, nelec: float):
    """One fused outer SCF iteration as a pure, jittable function.

    ``step(rho, c_segs, mix_state, v_ext, coulomb, tables)`` → (ρ_next,
    c_segs', mix_state', ρ_out, eps per segment, energy, residual).  The
    cube-sized fields (``v_ext``, the Coulomb kernel) and the per-segment
    band tables are arguments, not closed-over arrays: the compiled
    program does not carry them as constants (closed over, they add
    ~37 MB to the serialized executable at n=128, ~8× that at n=256, and
    to every compile-cache entry), and the step compiles from abstract
    arguments for a chip that is only described
    (``tests/test_tpu_compile.py``).  Plans come from the process-global
    PlanCache at trace time.
    """
    segs = basis.segments
    inelec = 1.0 / max(nelec, 1e-9)

    def step(rho, c_segs, mix_state, v_ext, coulomb, tables):
        vh = hartree(rho, coulomb)
        v_eff = v_ext + vh
        if cfg.xc:
            v_eff = v_eff + lda_exchange(rho)[1]
        c_new = []
        eps_segs = []
        for s in range(len(segs)):
            c_s, eps_s, _ = update_bands_stacked(
                basis, c_segs[s], v_eff, steps=cfg.inner_steps,
                tables=tables[s], seg=s)
            c_new.append(c_s)
            eps_segs.append(eps_s)
        c_new = tuple(c_new)
        rho_out = sum(density_from_stacked(basis, c_new[s], occ, seg=s)
                      for s in range(len(segs)))
        energy = total_energy_stacked(
            basis, c_new, rho_out, v_ext,
            lambda r: hartree(r, coulomb), occ, xc=cfg.xc, tables=tables)
        resid = (jnp.linalg.norm(rho_out - rho)
                 * jnp.float32(basis.dv ** 0.5 * inelec))
        mix_state, rho_next = jit_mix(mix_state, rho, rho_out,
                                      alpha=cfg.mix_alpha,
                                      warmup=cfg.mix_warmup)
        return (rho_next, c_new, mix_state, rho_out,
                tuple(eps_segs), energy, resid)

    return step


def _jit_scf_loop(cfg: SCFConfig, basis, v_ext, hartree, occ,
                  nelec: float, coeffs, callback):
    """The fused SCF loop: one jit-compiled step per outer iteration.

    Everything the eager loop does per iteration — v_eff build, the
    stacked band update, density rebuild, total energy, residual, density
    mixing — is traced into a single XLA computation
    (:func:`make_scf_step`) with the density, band-coefficient and mixer
    buffers donated, so iterations after the first dispatch no per-k
    Python work at all.  Plans and band tables come from the
    process-global PlanCache *eagerly at trace time*, which keeps cache
    traffic honestly accounted: one fetch per traced transform, zero per
    steady-state iteration.  The step is compiled ahead of the loop; its
    compile counts in ``seconds`` and in the first iteration, as a first
    jitted call would, and is also returned apart.

    Returns (energies, residuals, records, eigs, ρ_out, transforms,
    converged, seconds, compile_seconds, step_memory) with the same
    accounting semantics as the eager loop.
    """
    segs = basis.segments
    invs = [basis.stacked_hamiltonian_plans(s)[0] for s in range(len(segs))]
    tables = tuple(basis.stacked_band_tables(s) for s in range(len(segs)))
    c_segs = tuple(
        invs[s].stack([coeffs[ik] for ik in seg]).reshape(
            len(seg), basis.nbands, invs[s].npacked_max)
        for s, seg in enumerate(segs))
    rho = sum(density_from_stacked(basis, c_segs[s], occ, seg=s)
              for s in range(len(segs)))
    mix_state = jit_mixer_init(basis.n ** 3, cfg.mix_history)
    consts = (v_ext, hartree.kernel, tables)
    t0 = time.perf_counter()
    step = jax.jit(make_scf_step(cfg, basis, hartree, occ, nelec),
                   donate_argnums=(0, 1, 2)
                   ).lower(rho, c_segs, mix_state, *consts).compile()
    compile_seconds = time.perf_counter() - t0
    step_memory = program_memory(step)
    # a compiled step takes exactly the placements it was compiled for;
    # on a multi-device grid its outputs may come back placed otherwise
    in_sh = step.input_shardings[0]
    consts = jax.device_put(consts, in_sh[3:])

    energies: list[float] = []
    residuals: list[float] = []
    records: list[dict] = []
    eigs = np.zeros((basis.nk, basis.nbands))
    transforms = 0
    converged = False
    rho_out = rho
    # per-iteration analytic transform count, matching the eager loop:
    # Hartree pair + band-update sweeps + density + the energy's Hartree
    per_iter = (2 + 2 * cfg.inner_steps * basis.nk * 2 * basis.nbands
                + basis.nk * basis.nbands + 2)
    tr = get_tracer()
    for it in range(cfg.max_iter):
        it_t0 = time.perf_counter() if it else t0
        with tr.span("scf_iteration", iteration=it, route="jit"):
            state = jax.device_put((rho, c_segs, mix_state), in_sh[:3])
            rho, c_segs, mix_state, rho_out, eps_segs, energy, resid = \
                step(*state, *consts)
            # the float() conversions sync on the step's outputs, so
            # the span and the per-iteration seconds cover real work
            energy = float(energy)
            resid = float(resid)
        transforms += per_iter
        energies.append(energy)
        residuals.append(resid)
        records.append({"iteration": it, "energy": energy,
                        "residual": resid,
                        "seconds": time.perf_counter() - it_t0,
                        "transforms": per_iter})
        for s, seg in enumerate(segs):
            eigs[list(seg)] = np.asarray(eps_segs[s])
        if callback is not None:
            callback(it, energy, resid)
        if (it > cfg.mix_warmup
                and abs(energies[-1] - energies[-2]) < cfg.e_tol
                and resid < cfg.r_tol):
            converged = True
            break
    # drain the donated buffers before stopping the clock: the scalar
    # syncs above cover the energy/residual path but not necessarily the
    # mixed density still in flight
    jax.block_until_ready((rho, rho_out))
    seconds = time.perf_counter() - t0
    return energies, residuals, records, eigs, rho_out, transforms, \
        converged, seconds, compile_seconds, step_memory


def _init_coefficients(basis, seed: int):
    rng = np.random.default_rng(seed)
    coeffs = []
    for ik in range(basis.nk):
        npk = basis.npacked(ik)
        c = (rng.standard_normal((basis.nbands, npk))
             + 1j * rng.standard_normal((basis.nbands, npk))
             ).astype(np.complex64)
        coeffs.append(orthonormalize(jnp.asarray(c)))
    return coeffs


def run_scf(cfg: SCFConfig, *, grid: ProcGrid | None = None,
            v_ext=None, callback=None) -> SCFResult:
    """Run the SCF loop; see module docstring for the iteration structure.

    ``callback(it, energy, residual)`` is invoked after every outer
    iteration (the example CLI uses it for progress lines).
    """
    basis = PlaneWaveBasis(
        cfg.n, diameter=cfg.diameter, kpts=cfg.kpts, weights=cfg.weights,
        nbands=cfg.nbands, L=cfg.L, grid=grid,
        batch_axes=cfg.batch_axes, fft_axes=cfg.fft_axes,
        segment_padding=cfg.segment_padding,
        policy=cfg.policy, backend=cfg.backend)
    cache0 = dict(global_plan_cache().stats)
    if v_ext is None:
        v_ext = jnp.asarray(gaussian_wells(cfg.n, depth=cfg.depth))
    hartree = HartreeSolver(basis)

    if cfg.inner_steps < 1:
        raise ValueError(f"inner_steps must be >= 1, got {cfg.inner_steps}")
    nocc = cfg.nbands if cfg.nocc is None else int(cfg.nocc)
    if not 0 < nocc <= cfg.nbands:
        raise ValueError(f"nocc {nocc} not in (0, nbands={cfg.nbands}]")
    occ = np.zeros((basis.nk, basis.nbands))
    occ[:, :nocc] = 1.0
    nelec = float(basis.weights.sum() * nocc)

    # route the H sweeps through the ragged k-stacked batch when the grid
    # supports it (or the caller forces it); pipelined per-k is the fallback
    stack_k = basis.stacks_k if cfg.stack_k is None else bool(cfg.stack_k)
    if cfg.stack_k and not cfg.pipeline:
        # stacking IS an all-k sweep — the serial per-k branch cannot
        # honor it, and silently dropping a forced route would lie
        raise ValueError("stack_k=True requires pipeline=True (the "
                         "stacked route sweeps all k-points per step; "
                         "pipeline=False runs the serial per-k loop)")
    stacked = bool(stack_k and cfg.pipeline)
    if cfg.jit_step and not stacked:
        # the fused step is built on the padded stacked engine — running
        # it per-k would re-introduce the dispatch overhead it removes
        raise ValueError("jit_step=True requires the stacked band-update "
                         "route (stack_k=True, or a grid satisfying "
                         "basis.stacks_k with stack_k left on auto)")

    coeffs = _init_coefficients(basis, cfg.seed)

    compile_seconds, step_memory = 0.0, {}
    if cfg.jit_step:
        (energies, residuals, iteration_records, eigs, rho, transforms,
         converged, seconds, compile_seconds, step_memory) = _jit_scf_loop(
             cfg, basis, v_ext, hartree, occ, nelec, coeffs, callback)
    else:
        rho = density_from_orbitals(basis, coeffs, occ)
        mixer = AndersonMixer(cfg.mix_alpha, cfg.mix_history,
                              cfg.mix_warmup) \
            if cfg.mix_history > 1 else LinearMixer(cfg.mix_alpha)

        energies = []
        residuals = []
        iteration_records = []
        eigs = np.zeros((basis.nk, basis.nbands))
        # counter and timer both cover the SCF loop only: the warm-up
        # density build above (plan construction + first traces) is
        # excluded from both
        transforms = 0
        converged = False
        tr = get_tracer()
        t0 = time.perf_counter()

        for it in range(cfg.max_iter):
            it_t0 = time.perf_counter()
            it_transforms0 = transforms
            with tr.span("scf_iteration", iteration=it,
                         route="stacked" if stacked else "per-k"):
                vh = hartree(rho)
                transforms += 2                    # cube fwd + derived inv
                v_eff = v_ext + vh
                if cfg.xc:
                    _, v_x = lda_exchange(rho)
                    v_eff = v_eff + v_x
                if cfg.pipeline:
                    # all-k loop: the batched stacked engine (one ragged
                    # nk·nbands stack, einsum Gram/Rayleigh-Ritz) when
                    # the basis stacks k-points, pipelined per-k dispatch
                    # otherwise — per-k math identical to the serial
                    # branch
                    coeffs, eps_list, nsweep = update_bands_all_k(
                        basis, coeffs, v_eff, steps=cfg.inner_steps,
                        stacked=stack_k)
                    for ik in range(basis.nk):
                        eigs[ik] = np.asarray(eps_list[ik])
                    transforms += nsweep * basis.nk * 2 * basis.nbands
                else:
                    for ik in range(basis.nk):
                        coeffs[ik], eps, napply = update_bands(
                            basis, ik, coeffs[ik], v_eff,
                            steps=cfg.inner_steps)
                        eigs[ik] = np.asarray(eps)
                        transforms += napply * 2 * basis.nbands
                rho_out = density_from_orbitals(basis, coeffs, occ)
                transforms += basis.nk * basis.nbands
                energy, _ = total_energy(basis, coeffs, rho_out, v_ext,
                                         hartree, occ, xc=cfg.xc)
                transforms += 2                    # energy's Hartree solve
                # float() syncs on rho_out, closing the span honestly
                resid = float(jnp.linalg.norm(rho_out - rho)
                              * basis.dv ** 0.5) / max(nelec, 1e-9)
            energies.append(energy)
            residuals.append(resid)
            iteration_records.append({
                "iteration": it, "energy": energy, "residual": resid,
                "seconds": time.perf_counter() - it_t0,
                "transforms": transforms - it_transforms0})
            if callback is not None:
                callback(it, energy, resid)
            if (it > cfg.mix_warmup
                    and abs(energies[-1] - energies[-2]) < cfg.e_tol
                    and resid < cfg.r_tol):
                converged = True
                break
            rho = mixer.mix(rho, rho_out)

        jax.block_until_ready(rho)   # drain the last mix before the clock
        seconds = time.perf_counter() - t0
        # return the density the orbitals actually produced (not the mixed
        # guess) — coeffs are unchanged since the loop's last rho_out
        rho = rho_out if energies \
            else density_from_orbitals(basis, coeffs, occ)

    cache1 = global_plan_cache().stats
    delta = {k: cache1[k] - cache0.get(k, 0)
             for k in ("hits", "misses", "evictions")}
    delta["size"] = cache1["size"]
    assert abs(electron_count(basis, rho) - nelec) < 1e-3 * max(nelec, 1.0)
    padding = basis.padding_fraction if stacked else 0.0
    return SCFResult(
        converged=converged, iterations=len(energies),
        energy=energies[-1] if energies else float("nan"),
        energies=energies, residuals=residuals, eigenvalues=eigs, rho=rho,
        transforms=transforms, seconds=seconds, cache_stats=delta,
        grid_shape=tuple(basis.grid.shape), stacked=stacked,
        padding_fraction=padding,
        band_update="stacked" if stacked else "per-k",
        backend=basis.backend,
        jitted=bool(cfg.jit_step),
        compile_seconds=compile_seconds,
        step_memory=step_memory,
        segments=basis.nsegments,
        segment_padding_fractions=basis.segment_padding_fractions,
        iteration_records=iteration_records)
