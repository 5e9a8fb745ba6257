"""Per-k-point plane-wave bases — a batch of *different* spheres.

Every k-point carries its own cut-off sphere: the Bloch factor e^{ik·r}
shifts the kinetic-energy paraboloid, so the set of plane waves with
½|G+k|² ≤ E_cut is a sphere whose *center* moves with k (paper §2.2 — "one
sphere per k-point, bands batched within each").  All spheres share one
d³ bounding box and one n³ FFT cube, so every k-point's transform has the
same data layout but a *different* static pack/unpack table — exactly the
multi-plan traffic the process-global ``PlanCache`` exists for: distinct
spheres build distinct plans, repeated spheres (and every later SCF
iteration) hit the cache.

Processing grids (paper §3.3): the basis runs on 1D fft-only grids *or* 2D
(batch × fft) grids.  On a 2D grid the band batch is sharded over the batch
axes and only the fft axes carry the transforms' all_to_alls — the paper's
headline configuration, which keeps scaling after the fft axes saturate the
sphere diameter.  When ``nk`` divides the batch-axis size, the density
build additionally stacks k-points into the batch dimension (one transform
of batch nk·nbands), so k-points are genuinely distributed too.

Units: cubic cell of side ``L`` (default: ``n`` grid spacings of 1), so a
reciprocal-lattice step is 2π/L.  k-points are given in reduced coordinates
(units of 2π/L).  The sphere is centered at c_k = c0 + k, and the kinetic
energy of packed coefficient at cube index ``idx`` is
½(2π/L)²|idx − c_k|² — the cut-off rule and the kinetic ladder agree by
construction.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import (Domain, ProcGrid, cube_spec, fftb,
                        global_plan_cache, kpoint_sphere,
                        make_stacked_planewave_pair, padded_kinetic_table,
                        planewave_spec, segment_padding_fraction,
                        segment_spheres, sphere_gvectors, sphere_kinetic_row)
from repro.check.diagnostics import raise_if_errors
from repro.check.preflight import preflight_basis
from repro.core.cache import domains_key, grid_key
from repro.core.policy import ExecPolicy

#: sphere bounding-cube (bands, x, y, z) → real-space cube, x/Z sharded
#: (the 1D fft-only layout; 2D grids derive their spec via planewave_spec)
PW_SPEC = planewave_spec()
#: full density/potential cube, real space (z-sharded) → G space (Z-sharded)
CUBE_SPEC = cube_spec()


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StackedBandTables:
    """Dense per-k tables for the batched band-update engine.

    All three are ``(nk, npacked_max)`` float32 arrays, pinned replicated
    on the basis's mesh, with **exact zeros** on padded lanes — so they
    can ride batched einsums over the full ``(nk, nbands, npacked_max)``
    coefficient stack, and padded lanes contribute exact zeros to every
    Gram matrix, energy and preconditioned residual without any runtime
    masking:

      * ``kinetic``  — ½|G+k|² diagonal (bitwise-equal to the per-k
        :meth:`PlaneWaveBasis.kinetic` ladders on valid lanes),
      * ``mask``     — lane validity as {0.0, 1.0},
      * ``precond``  — the masked Teter-style damping mask/(1 + ½|G+k|²).

    Cached in the process-global ``PlanCache`` next to the stacked plan
    pair (same key ingredients), so every SCF iteration after the first
    is a cache hit; the cache bills the three tables as private bytes.
    A pytree, so the jitted SCF step takes the tables as an argument.
    """

    kinetic: jnp.ndarray
    mask: jnp.ndarray
    precond: jnp.ndarray

    # ------------------------------------------- PlanCache accounting
    def private_bytes(self) -> int:
        return sum(int(a.nbytes)
                   for a in (self.kinetic, self.mask, self.precond))

    def shared_table_bytes(self) -> dict:
        return {}

    def estimated_bytes(self) -> int:
        return self.private_bytes()


class PlaneWaveBasis:
    """Shared FFT cube + per-k-point spheres, plans served from the cache.

    Plans are *not* stored on the instance: ``plans_for_k``/``cube_plans``
    go through ``fftb.plan_for`` (the process-global ``PlanCache``) on every
    call, so plan reuse across SCF iterations — and across bases that happen
    to request the same sphere — is the cache's hit counter, not a private
    dict.  Derived mirrors are memoized on the plan itself (``inverse()``),
    so a pair costs one schedule search process-wide.

    ``grid`` may be 1D (fft-only, the former pinned layout) or multi-axis.
    On a multi-axis grid ``batch_axes``/``fft_axes`` split the grid axes
    between the band batch and the transform dims; by default the first
    axis is batch and the rest are fft — a ``(batch, fft)`` mesh, or the
    pencil ``(batch, fft, fft)`` mesh on 3-axis grids (both transform
    dims sharded, every all_to_all over one small axis).

    ``segment_padding`` switches the ragged k-stacking from one global
    ``npacked_max`` pad target to **segmented** stacking: k-points are
    grouped into similar-``npacked`` segments (``core.segment_spheres``)
    whose realized padding fraction never exceeds the budget, and every
    stacked plan/table method takes the segment index.  ``None`` (the
    default) keeps the single full-batch segment — all existing
    single-segment behaviour, cache keys included, is unchanged.
    """

    def __init__(self, n: int, *, diameter: int | None = None,
                 kpts=((0.0, 0.0, 0.0),), weights=None, nbands: int = 4,
                 L: float | None = None, grid: ProcGrid | None = None,
                 batch_axes: tuple[int, ...] | None = None,
                 fft_axes: tuple[int, ...] | None = None,
                 segment_padding: float | None = None,
                 policy: ExecPolicy | None = None,
                 backend: str | None = None):
        self.n = int(n)
        self.d = int(diameter) if diameter is not None else self.n // 2
        self.L = float(L) if L is not None else float(n)
        self.grid = grid if grid is not None else \
            ProcGrid.create([jax.device_count()])
        self.nbands = int(nbands)
        self.policy = policy
        # backend resolution ladder: explicit argument > policy preference
        # > the "matmul" default.  The resolved value is what every plan
        # request below carries — callers read ``basis.backend`` to learn
        # what the run actually asked for (bench records persist it).
        if backend is None:
            backend = policy.backend if policy is not None and \
                policy.backend is not None else "matmul"
        self.backend = backend

        if batch_axes is None:
            # (batch, fft, …) convention: the first axis carries the band
            # batch, every remaining axis transforms — (batch, fft) on 2D
            # grids, the pencil (batch, fft, fft) on 3D; 1D stays fft-only
            batch_axes = () if self.grid.ndim == 1 else (0,)
        self.batch_axes = tuple(batch_axes)
        if fft_axes is None:
            fft_axes = tuple(a for a in range(self.grid.ndim)
                             if a not in self.batch_axes)
        self.fft_axes = tuple(fft_axes)
        # coded preflight diagnostics (FFTB110–117) replace the former
        # ad-hoc ValueErrors; DiagnosticError is a ValueError carrying
        # the same message substrings, so existing handlers keep working
        raise_if_errors(preflight_basis(
            self.n, diameter=self.d, kpts=kpts, nbands=self.nbands,
            grid=self.grid, batch_axes=self.batch_axes,
            fft_axes=self.fft_axes, segment_padding=segment_padding,
            backend=self.backend))
        self.batch_procs = math.prod(
            self.grid.axis_size(a) for a in self.batch_axes)
        self.fft_procs = math.prod(
            self.grid.axis_size(a) for a in self.fft_axes)
        self._pw_spec = planewave_spec(self.batch_axes, self.fft_axes)
        self._cube_spec = cube_spec(self.fft_axes)

        self.kpts = np.atleast_2d(np.asarray(kpts, np.float64))
        nk = self.kpts.shape[0]
        if weights is None:
            self.weights = np.full(nk, 1.0 / nk)
        else:
            self.weights = np.asarray(weights, np.float64)
            if self.weights.shape != (nk,):
                raise ValueError("one weight per k-point")
            self.weights = self.weights / self.weights.sum()

        # one construction rule shared with the transform service: same
        # cutoff ⇒ same bounding box ⇒ batch-compatible pack tables
        self.spheres = [kpoint_sphere(self.d, kp) for kp in self.kpts]
        self.bdom = Domain((0,), (self.nbands - 1,))
        self.cube = Domain((0, 0, 0), (self.n - 1,) * 3)
        self._kin = [None] * nk
        self._gvec = [None] * nk

        # segmented ragged stacking: partition k-points into similar-
        # npacked segments under the padding budget; segment sizes are
        # constrained to divide the batch-axis size so every segment's
        # stacked nk_seg·nbands batch keeps the stacks_k sharding
        # contract.  The default (None) is the single full-batch segment
        # in k order — bitwise and cache-key identical to the
        # pre-segmentation behaviour.
        self.segment_padding = (float(segment_padding)
                                if segment_padding is not None else None)
        if self.segment_padding is None:
            self.segments: tuple[tuple[int, ...], ...] = (tuple(range(nk)),)
        else:
            div = self.batch_procs if self.batch_procs > 1 else None
            self.segments = segment_spheres(
                self.spheres, self.segment_padding, size_divisor=div)
        self._seg_of = [0] * nk
        for s, seg in enumerate(self.segments):
            for i in seg:
                self._seg_of[i] = s

    # ----------------------------------------------------------------- size
    @property
    def nk(self) -> int:
        return self.kpts.shape[0]

    @property
    def cell_volume(self) -> float:
        return self.L ** 3

    @property
    def dv(self) -> float:
        """Real-space integration element ΔV = Ω / n³."""
        return (self.L / self.n) ** 3

    def npacked(self, ik: int) -> int:
        return self.spheres[ik].npacked

    @property
    def npacked_max(self) -> int:
        """max_k npacked(k) — the padded lane count of the stacked batch.

        Both band-update engines run their Gram/Rayleigh-Ritz contractions
        over exactly this many lanes (padded with exact zeros) *within a
        segment* (``pad_width``), so the per-k and stacked paths share
        one rounding behaviour; see ``dft.hamiltonian``.
        """
        return max(s.npacked for s in self.spheres)

    # ------------------------------------------------------------ segments
    @property
    def nsegments(self) -> int:
        return len(self.segments)

    def seg_of(self, ik: int) -> int:
        """Index of the segment k-point ``ik`` stacks into."""
        return self._seg_of[ik]

    def pad_width(self, ik: int) -> int:
        """Padded lane count of k-point ``ik``'s segment.

        Both band-update engines contract their linalg over exactly this
        many lanes for ``ik`` — the per-k oracle pads to it so stacked
        and per-k execute identical GEMM shapes (bitwise agreement).
        With the default single segment this is ``npacked_max``.
        """
        seg = self.segments[self._seg_of[ik]]
        return max(self.spheres[i].npacked for i in seg)

    @property
    def padding_fraction(self) -> float:
        """Padded lanes / total lanes over all segments.

        With one segment this equals the stacked plan pair's
        ``padding_fraction``; segmentation can only lower it.
        """
        used = sum(s.npacked for s in self.spheres)
        lanes = sum(len(seg) * max(self.spheres[i].npacked for i in seg)
                    for seg in self.segments)
        return 1.0 - used / float(lanes)

    @property
    def segment_padding_fractions(self) -> tuple[float, ...]:
        """Realized per-segment padding — each ≤ ``segment_padding``."""
        return tuple(segment_padding_fraction(self.spheres, seg)
                     for seg in self.segments)

    @property
    def stacks_k(self) -> bool:
        """True when k-points stack into the transforms' batch dimension.

        On a (batch × fft) grid, every segment's nk_seg·nbands stacked
        batch must split evenly over the batch axes — segment length
        divides the batch-axis size and nk_seg·nbands is divisible by it
        — so k-points (not just bands) are sharded.  Both the density
        build and the Hamiltonian apply route through the stacked plans
        then — one batched transform per direction per segment instead
        of nk per-k dispatches (the pipelined per-k path remains as the
        fallback and oracle).  With the default single segment this is
        the original ``nk | batch_procs`` condition; segmentation can
        *restore* stacking for k-counts that do not divide the batch
        axis (the segmenter caps segment sizes at divisors of it).
        """
        return (bool(self.batch_axes) and self.nk > 1
                and self.batch_procs > 1
                and all(self.batch_procs % len(seg) == 0
                        and (len(seg) * self.nbands) % self.batch_procs == 0
                        for seg in self.segments))

    # ------------------------------------------------------- G bookkeeping
    def gvectors(self, ik: int) -> np.ndarray:
        """(npacked, 3) G+k offsets from the sphere center, in units 2π/L.

        CSR (pack) order — aligned with the packed coefficient vector.
        Delegates to ``core.planewave.sphere_gvectors``, the same decode
        the padded dense tables use."""
        if self._gvec[ik] is None:
            self._gvec[ik] = sphere_gvectors(self.spheres[ik])
        return self._gvec[ik]

    def kinetic(self, ik: int):
        """½|G+k|² diagonal over packed coefficients (f32, on device).

        The same ``sphere_kinetic_row`` pipeline that fills the padded
        table in :meth:`stacked_band_tables`, so the two agree bitwise
        by construction."""
        if self._kin[ik] is None:
            self._kin[ik] = jnp.asarray(
                sphere_kinetic_row(self.spheres[ik], self.L))
        return self._kin[ik]

    # ----------------------------------------------------------------- plans
    def plans_for_k(self, ik: int):
        """(inverse, forward) sphere↔cube pair for k-point ``ik``.

        Served from the process-global PlanCache — the first request per
        distinct sphere builds (one schedule search), every later request
        (same k re-visited, next SCF iteration, a symmetry-equivalent
        k-point) is a cache hit.  On a 2D grid the band batch rides the
        batch axes, the staged transposes ride the fft axes.
        """
        inv = fftb.plan_for(
            self._pw_spec, domains=(self.bdom, self.spheres[ik]),
            grid=self.grid, sizes=(self.n,) * 3, inverse=True,
            backend=self.backend, policy=self.policy)
        return inv, inv.inverse()       # mirror is memoized on the plan

    def _seg_spheres(self, seg: int):
        """The segment's spheres, in segment (stack) order."""
        return tuple(self.spheres[i] for i in self.segments[seg])

    def stacked_inverse_plan(self, seg: int = 0):
        """One d³→n³ inverse plan batching segment ``seg``'s orbitals.

        The spheres differ only in their pack tables; the staged-padding
        FFT itself sees the shared d³ bounding box, so every k-point's
        cube can ride a single transform whose batch dim is
        nk_seg·nbands — sharding *k-points and bands* over the batch
        axes.  Equal-sized segments resolve to the *same* cache entry
        (the batch domain is the only per-segment key ingredient), so
        segmentation multiplies pack tables, not schedule searches.
        Used by the density build when :attr:`stacks_k` holds.
        """
        nks = len(self.segments[seg])
        bdom = Domain((0,), (nks * self.nbands - 1,))
        bbox = Domain((0, 0, 0), (self.d - 1,) * 3)
        return fftb.plan_for(
            self._pw_spec, domains=(bdom, bbox), grid=self.grid,
            sizes=(self.n,) * 3, inverse=True, backend=self.backend,
            policy=self.policy)

    def stacked_hamiltonian_plans(self, seg: int = 0):
        """(inverse, forward) ragged-batch stacked pair for the H apply.

        One ``StackedPlaneWaveFFT`` pair batching segment ``seg``'s
        nk_seg·nbands orbitals: each k-point's packed coefficients are
        padded to the segment's own lane width (``pad_width``) with the
        per-k validity baked into the pack/unpack index tables, so one
        Hamiltonian sweep is two batched distributed transforms per
        segment regardless of nk and nbands.  Served from the
        process-global PlanCache keyed by the segment's sphere set; the
        inner d³→n³ plan is :meth:`stacked_inverse_plan` — shared
        (object identity and cache accounting alike) with the density
        build and with every equal-sized segment.
        """
        spheres = self._seg_spheres(seg)
        cache = global_plan_cache()
        key = ("stacked-pw", self._pw_spec,
               domains_key(spheres), (len(spheres), self.nbands),
               grid_key(self.grid), (self.n,) * 3, self.backend,
               self.policy)
        inv = cache.get_or_build(
            key, lambda: make_stacked_planewave_pair(
                self.grid, self.n, list(spheres), self.nbands,
                backend=self.backend, batch_axes=self.batch_axes,
                fft_axes=self.fft_axes, policy=self.policy,
                plan=self.stacked_inverse_plan(seg))[0])
        return inv, inv.inverse()   # mirror is memoized on the plan

    def stacked_band_tables(self, seg: int = 0) -> StackedBandTables:
        """Dense kinetic/mask/precond tables for the stacked band update.

        Per segment — ``(nk_seg, pad_width)`` rows in segment order.
        Served from the process-global PlanCache alongside the stacked
        plan pair: the first request per sphere set builds the padded
        tables (host-side numpy + one replicated device_put), every later
        request — the next band sweep, the next SCF iteration — is a
        cache hit.  Values on valid lanes match the per-k ladders bitwise
        (same float64→float32 pipeline for ``kinetic``, the same float32
        ``1/(1 + kin)`` arithmetic for ``precond``), padded lanes are
        exact zeros in all three tables.
        """
        spheres = self._seg_spheres(seg)
        cache = global_plan_cache()
        key = ("stacked-band-tables", domains_key(spheres),
               (len(spheres), self.nbands), grid_key(self.grid), self.L)
        return cache.get_or_build(
            key, lambda: self._build_band_tables(spheres))

    def _build_band_tables(self, spheres) -> StackedBandTables:
        kin_np, valid = padded_kinetic_table(list(spheres), self.L)
        # built eagerly even when the first request comes from inside a
        # jit trace: the cache must hold arrays, never tracers
        with jax.ensure_compile_time_eval():
            kin = self.grid.replicate(jnp.asarray(kin_np))
            mask = self.grid.replicate(
                jnp.asarray(valid.astype(np.float32)))
            # same f32 ops as the per-k 1/(1 + kinetic(ik))
            # preconditioner, so valid lanes agree bitwise; mask zeroes
            # the padded lanes exactly
            precond = self.grid.replicate(mask / (1.0 + kin))
        return StackedBandTables(kinetic=kin, mask=mask, precond=precond)

    def cube_plans(self):
        """(forward, inverse) full-cube pair for density/potential fields."""
        fwd = fftb.plan_for(
            self._cube_spec, domains=self.cube, grid=self.grid,
            backend=self.backend, policy=self.policy)
        return fwd, fwd.inverse()       # mirror is memoized on the plan

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (f"PlaneWaveBasis(n={self.n}, d={self.d}, nk={self.nk}, "
                f"nbands={self.nbands}, grid={self.grid}, "
                f"batch_axes={self.batch_axes}, fft_axes={self.fft_axes}, "
                f"segments={len(self.segments)})")
