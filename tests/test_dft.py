"""repro.dft: multi-sphere k-point batches, G-space Hartree, SCF loop,
1D fft-only and 2D batch×fft processing grids, pipelined k-point updates."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (FftPlan, PlaneWaveFFT, ProcGrid, SphereDomain,
                        StackedPlaneWaveFFT, global_plan_cache)
from repro.dft import (HartreeSolver, PlaneWaveBasis, SCFConfig,
                       density_from_orbitals, run_scf)
from repro.dft.density import electron_count
from repro.dft.hamiltonian import (apply_hamiltonian,
                                   apply_hamiltonian_pipelined,
                                   apply_hamiltonian_stacked,
                                   orthonormalize, update_bands,
                                   update_bands_all_k)
from repro.dft.scf import AndersonMixer

KPTS2 = ((0.0, 0.0, 0.0), (0.5, 0.5, 0.5))


@pytest.fixture(scope="module")
def g1():
    return ProcGrid.create([1], ["dft_g"])


@pytest.fixture(scope="module")
def basis2(g1):
    return PlaneWaveBasis(16, kpts=KPTS2, nbands=3, grid=g1)


def _rand_bands(rng, nb, npk):
    c = (rng.standard_normal((nb, npk))
         + 1j * rng.standard_normal((nb, npk))).astype(np.complex64)
    return orthonormalize(jnp.asarray(c))


# -------------------------------------------------------------------- basis
def test_basis_builds_one_sphere_per_kpoint(basis2):
    s0, s1 = basis2.spheres
    assert isinstance(s0, SphereDomain) and isinstance(s1, SphereDomain)
    assert s0.center != s1.center          # k shifts the sphere center
    assert s0.extents == s1.extents == (8, 8, 8)   # shared bounding box
    assert basis2.npacked(0) != basis2.npacked(1)  # different point sets


def test_basis_kinetic_matches_cutoff_rule(basis2):
    for ik in range(basis2.nk):
        kin = np.asarray(basis2.kinetic(ik))
        g = basis2.gvectors(ik)
        ref = 0.5 * (g ** 2).sum(1) * (2 * np.pi / basis2.L) ** 2
        np.testing.assert_allclose(kin, ref, rtol=1e-6)
        # cut-off rule: every packed wave is inside the kinetic sphere
        e_cut = 0.5 * (2 * np.pi * basis2.d / (2 * basis2.L)) ** 2
        assert kin.max() <= e_cut + 1e-6


def test_basis_distinct_spheres_distinct_plans_repeats_hit(basis2):
    cache = global_plan_cache()
    inv0, fwd0 = basis2.plans_for_k(0)
    inv1, _ = basis2.plans_for_k(1)
    assert isinstance(inv0, PlaneWaveFFT)
    assert inv0 is not inv1                # distinct spheres → distinct plans
    assert inv0.sphere is basis2.spheres[0]
    hits = cache.stats["hits"]
    inv0b, fwd0b = basis2.plans_for_k(0)   # re-request: plan-cache hit
    assert inv0b is inv0 and fwd0b is fwd0
    assert cache.stats["hits"] == hits + 1


# ------------------------------------------------------------------ hartree
def test_hartree_matches_numpy_reference(basis2):
    rng = np.random.default_rng(0)
    rho = rng.random((16, 16, 16)).astype(np.float32)
    vh = np.asarray(HartreeSolver(basis2)(jnp.asarray(rho)))
    f = np.fft.fftfreq(16, d=1.0 / 16)
    gx, gy, gz = np.meshgrid(f, f, f, indexing="ij")
    g2 = (gx ** 2 + gy ** 2 + gz ** 2) * (2 * np.pi / basis2.L) ** 2
    kern = np.where(g2 > 0, 4 * np.pi / np.where(g2 > 0, g2, 1.0), 0.0)
    ref = np.real(np.fft.ifftn(np.fft.fftn(rho) * kern))
    np.testing.assert_allclose(vh, ref, rtol=1e-4, atol=1e-5)


def test_hartree_runs_on_full_cube_plan_pair(basis2):
    fwd, inv = basis2.cube_plans()
    assert isinstance(fwd, FftPlan) and not isinstance(fwd, PlaneWaveFFT)
    assert isinstance(inv, FftPlan) and not isinstance(inv, PlaneWaveFFT)
    assert fwd.tin.shape == (16, 16, 16)
    searches = FftPlan.searches
    fwd2, inv2 = basis2.cube_plans()       # cached + derived: no re-search
    assert fwd2 is fwd and inv2 is inv
    assert FftPlan.searches == searches


# ------------------------------------------------------------------ density
def test_density_integrates_to_electron_count(basis2):
    rng = np.random.default_rng(1)
    coeffs = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    occ = np.ones((basis2.nk, basis2.nbands))
    rho = density_from_orbitals(basis2, coeffs, occ)
    assert float(rho.min()) >= 0.0
    assert abs(electron_count(basis2, rho) - basis2.nbands) < 1e-3


def test_hamiltonian_is_hermitian(basis2):
    rng = np.random.default_rng(2)
    npk = basis2.npacked(0)
    c1 = _rand_bands(rng, basis2.nbands, npk)
    c2 = _rand_bands(rng, basis2.nbands, npk)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    h1 = apply_hamiltonian(basis2, 0, c1, v)
    h2 = apply_hamiltonian(basis2, 0, c2, v)
    lhs = complex(jnp.vdot(c2, h1))        # ⟨c2|H c1⟩
    rhs = complex(jnp.vdot(h2, c1))        # ⟨H c2|c1⟩
    assert abs(lhs - rhs) < 1e-3 * max(abs(lhs), 1.0)


# ------------------------------------------------------------------- mixing
def test_anderson_mixer_fixed_point_and_history():
    mixer = AndersonMixer(alpha=0.5, history=3, warmup=1)
    rho = jnp.ones((4, 4, 4))
    for _ in range(5):
        out = mixer.mix(rho, rho)          # already self-consistent
    np.testing.assert_allclose(np.asarray(out), np.asarray(rho), atol=1e-6)
    assert len(mixer._res) == 3            # history is trimmed


def test_anderson_beats_linear_on_a_linear_model():
    """ρ* = A ρ + b: Anderson reaches the fixed point faster than linear."""
    rng = np.random.default_rng(3)
    a = 0.9 * np.eye(8) + 0.05 * rng.standard_normal((8, 8))
    b = rng.standard_normal(8)

    def residual_after(mixer, iters):
        rho = jnp.zeros((8, 1, 1))
        for _ in range(iters):
            out = jnp.asarray((a @ np.asarray(rho).ravel() + b
                               ).reshape(8, 1, 1))
            rho = mixer.mix(rho, out)
        return float(jnp.linalg.norm(
            jnp.asarray(a @ np.asarray(rho).ravel() + b).reshape(8, 1, 1)
            - rho))

    lin = residual_after(AndersonMixer(0.5, history=1, warmup=99), 12)
    and_ = residual_after(AndersonMixer(0.5, history=6, warmup=2), 12)
    assert and_ < lin * 0.5


# ------------------------------------------------------------ 2D grids
def test_basis_2d_grid_defaults_and_specs():
    """(batch, fft) convention on a 2D grid; spec strings carry the axes.

    Abstract grids suffice — construction and validation never execute."""
    g2 = ProcGrid.create_abstract([2, 2])
    b = PlaneWaveBasis(16, kpts=KPTS2, nbands=4, grid=g2)
    assert b.batch_axes == (0,) and b.fft_axes == (1,)
    assert b.batch_procs == 2 and b.fft_procs == 2
    assert b._pw_spec == "b{0} x{1} y z -> b{0} X Y Z{1}"
    assert b._cube_spec == "x y z{1} -> X Y Z{1}"
    assert b.stacks_k                      # nk=2 divides the batch axis
    # a 1D grid keeps the pinned fft-only layout (and never stacks k)
    g1 = ProcGrid.create_abstract([4])
    b1 = PlaneWaveBasis(16, kpts=KPTS2, nbands=4, grid=g1)
    assert b1.batch_axes == () and b1.fft_axes == (0,)
    assert b1._pw_spec == "b x{0} y z -> b X Y Z{0}"
    assert not b1.stacks_k


def test_basis_2d_grid_validation_errors():
    g2 = ProcGrid.create_abstract([2, 2])
    with pytest.raises(ValueError, match="nbands 3 not divisible"):
        PlaneWaveBasis(16, kpts=KPTS2, nbands=3, grid=g2)
    with pytest.raises(ValueError, match="at least one fft axis"):
        PlaneWaveBasis(16, nbands=4, grid=g2, batch_axes=(0, 1))
    with pytest.raises(ValueError, match="divide over the fft-axis"):
        PlaneWaveBasis(14, diameter=7, nbands=4, grid=g2)
    with pytest.raises(ValueError, match="must be disjoint"):
        PlaneWaveBasis(16, nbands=4, grid=g2, batch_axes=(0,),
                       fft_axes=(0,))


def test_choose_dft_grid_shape_rules():
    from repro.sharding.grids import choose_dft_grid_shape
    # few devices relative to the diameter → 1D fft grid
    assert choose_dft_grid_shape(1, nbands=4, diameter=8) == (1,)
    assert choose_dft_grid_shape(2, nbands=4, diameter=8) == (2,)
    # past the pencil limit → batch×fft split
    assert choose_dft_grid_shape(4, nbands=4, diameter=8, nk=2) == (2, 2)
    # 8 devices, d=8: the pencil tier puts 2·2 devices on the transforms
    # (beating the best single fft axis pf=2) with pb=2 on the bands
    assert choose_dft_grid_shape(8, nbands=4, diameter=8) == (2, 2, 2)
    assert choose_dft_grid_shape(8, nbands=4, diameter=8, nk=2) == (2, 2, 2)
    # the batch factor must divide nbands (a hard basis requirement):
    # k-stacking never excuses it — though the pencil tier's smaller
    # pb=2 rescues nbands=2, which the 2D-only ladder dropped to 1D
    assert choose_dft_grid_shape(8, nbands=2, diameter=8, nk=2) == (2, 2, 2)
    assert choose_dft_grid_shape(8, nbands=3, diameter=8, nk=3) == (8,)
    # no valid split → fall back to 1D (basis raises the actionable error)
    assert choose_dft_grid_shape(4, nbands=3, diameter=7) == (4,)


def test_choose_dft_grid_shape_edge_cases():
    """Prime device counts, nk not dividing the batch extent, and
    nbands < ndevices: the chooser degrades predictably — a non-stackable
    2D split when one exists (basis then runs the pipelined fallback),
    the 1D fft grid when nothing divides."""
    from repro.sharding.grids import choose_dft_grid_shape
    # prime device counts past the pencil limit: the only fft factors
    # dividing both ndevices and the diameter are 1 (and pb = ndevices
    # never divides nbands) → 1D fallback, never a crash
    for p in (5, 7, 11, 13):
        assert choose_dft_grid_shape(p, nbands=4, diameter=8) == (p,)
    # nk not dividing any feasible batch factor: the stacks_k contract is
    # unmet, but a valid (pb | nbands) split still beats 1D — the basis
    # simply runs the pipelined per-k fallback on it (stacks_k False)
    assert choose_dft_grid_shape(4, nbands=4, diameter=8, nk=3) == (2, 2)
    assert choose_dft_grid_shape(8, nbands=4, diameter=8, nk=3) == (2, 2, 2)
    b = PlaneWaveBasis(16, kpts=((0, 0, 0), (0.3, 0, 0), (0, 0.3, 0)),
                       nbands=4, grid=ProcGrid.create_abstract([2, 2]))
    assert not b.stacks_k                     # nk=3 ∤ pb=2 → fallback
    # nbands smaller than every candidate batch factor → 1D fallback
    assert choose_dft_grid_shape(8, nbands=1, diameter=8) == (8,)
    # … but the pencil tier's pb=2 keeps nbands=2 on a 3-axis split
    # where the 2D-only ladder (pf ≤ 4 ⇒ pb ∈ {4, 8, 16}) fell to 1D
    assert choose_dft_grid_shape(16, nbands=2, diameter=16, nk=2) \
        == (2, 4, 2)
    assert choose_dft_grid_shape(16, nbands=3, diameter=8, nk=2) == (16,)
    # nbands ≥ the batch factor but not divisible → still 1D
    assert choose_dft_grid_shape(4, nbands=5, diameter=8) == (4,)
    # … while a composite nbands that does divide keeps the 2D split
    assert choose_dft_grid_shape(4, nbands=6, diameter=8) == (2, 2)


# ------------------------------------------------------ pipelined k-loop
def test_pipelined_hamiltonian_matches_serial(basis2):
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    blocks = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    piped = apply_hamiltonian_pipelined(basis2, blocks, v)
    for ik in range(basis2.nk):
        ref = apply_hamiltonian(basis2, ik, blocks[ik], v)
        assert float(jnp.abs(piped[ik] - ref).max()) == 0.0


def test_pipelined_band_update_matches_serial_to_1e10(basis2):
    """Acceptance: the pipelined k-loop reproduces the serial path — same
    per-k math, only the dispatch interleaving differs — so the updated
    coefficients and the density they produce match to 1e-10."""
    rng = np.random.default_rng(8)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    coeffs = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    serial, serial_eps, serial_applies = [], [], 0
    for ik in range(basis2.nk):
        c, eps, napply = update_bands(basis2, ik, coeffs[ik], v, steps=3)
        serial.append(c)
        serial_eps.append(eps)
        serial_applies += napply
    piped, piped_eps, nsweep = update_bands_all_k(basis2, coeffs, v,
                                                  steps=3)
    assert nsweep * basis2.nk == serial_applies   # same H-apply count
    for ik in range(basis2.nk):
        assert float(jnp.abs(piped[ik] - serial[ik]).max()) < 1e-10
        assert float(jnp.abs(piped_eps[ik] - serial_eps[ik]).max()) < 1e-10
    occ = np.ones((basis2.nk, basis2.nbands))
    rho_s = density_from_orbitals(basis2, serial, occ)
    rho_p = density_from_orbitals(basis2, piped, occ)
    assert float(jnp.abs(rho_p - rho_s).max()) < 1e-10


def test_scf_pipeline_flag_equivalent(basis2):
    """run_scf(pipeline=True) ≡ run_scf(pipeline=False), energy and ρ."""
    g1 = basis2.grid
    cfg = {"n": 16, "nbands": 3, "kpts": KPTS2, "max_iter": 6,
           "mix_warmup": 99}
    a = run_scf(SCFConfig(**cfg, pipeline=True), grid=g1)
    b = run_scf(SCFConfig(**cfg, pipeline=False), grid=g1)
    assert a.transforms == b.transforms
    assert abs(a.energy - b.energy) < 1e-10
    assert float(jnp.abs(a.rho - b.rho).max()) < 1e-10


# ------------------------------------------------------ stacked k batches
def test_stacked_hamiltonian_matches_pipelined_and_serial(basis2):
    """Acceptance: stacked ≡ pipelined ≡ serial H apply on ragged spheres
    (distinct npacked_k per k-point) — the stacked route pads each k to
    npacked_max but must reproduce the per-k math to 1e-10."""
    assert basis2.npacked(0) != basis2.npacked(1)   # genuinely ragged
    rng = np.random.default_rng(11)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    blocks = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    stacked = apply_hamiltonian_stacked(basis2, blocks, v)
    piped = apply_hamiltonian_pipelined(basis2, blocks, v)
    for ik in range(basis2.nk):
        ref = apply_hamiltonian(basis2, ik, blocks[ik], v)
        assert stacked[ik].shape == ref.shape       # unpadded per-k block
        assert float(jnp.abs(stacked[ik] - ref).max()) < 1e-10
        assert float(jnp.abs(piped[ik] - ref).max()) < 1e-10


def test_stacked_plans_cached_and_shared_with_density(basis2):
    """The stacked pair is one PlanCache entry; its inner d³→n³ plan IS
    the density build's stacked plan (object identity, no re-search)."""
    cache = global_plan_cache()
    inv, fwd = basis2.stacked_hamiltonian_plans()
    assert isinstance(inv, StackedPlaneWaveFFT)
    assert inv.plan is basis2.stacked_inverse_plan()
    assert fwd.plan is inv.plan.inverse()
    hits = cache.stats["hits"]
    searches = FftPlan.searches
    inv2, fwd2 = basis2.stacked_hamiltonian_plans()
    assert inv2 is inv and fwd2 is fwd
    assert cache.stats["hits"] > hits
    assert FftPlan.searches == searches


def test_stacked_padded_lanes_never_leak(basis2):
    """Garbage written into the padded lanes must not reach the packed
    outputs: unpack keeps only each line's z range, pack zeroes padded
    lanes."""
    inv, fwd = basis2.stacked_hamiltonian_plans()
    assert inv.padding_fraction > 0.0               # ragged ⇒ real padding
    rng = np.random.default_rng(12)
    blocks = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    stacked = jnp.asarray(inv.stack(blocks))
    valid = np.zeros((basis2.nk, inv.npacked_max), bool)
    for ik in range(basis2.nk):
        valid[ik, :basis2.npacked(ik)] = True
    lanes = np.repeat(valid, basis2.nbands, axis=0)  # (nk·nb, npmax)
    garbage = jnp.where(jnp.asarray(lanes), stacked,
                        jnp.asarray(1e6 + 1e6j, stacked.dtype))
    # unpack: padded-lane garbage never reaches the cube
    assert float(jnp.abs(inv.unpack(garbage)
                         - inv.unpack(stacked)).max()) == 0.0
    # pack after a round trip: padded lanes come out exactly zero
    out = np.asarray(inv.pack(fwd(inv(inv.unpack(garbage)))))
    assert np.abs(out[~lanes]).max() == 0.0
    # and the valid lanes round-trip to the inputs (forward ∘ inverse ≈ id)
    np.testing.assert_allclose(out[lanes], np.asarray(stacked)[lanes],
                               rtol=1e-3, atol=2e-5)


def test_stacked_band_update_matches_serial(basis2):
    """update_bands_all_k(stacked=True) reproduces the serial per-k path
    to 1e-10 — eigenvalues, coefficients, and the resulting density."""
    rng = np.random.default_rng(13)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    coeffs = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    serial, serial_eps = [], []
    for ik in range(basis2.nk):
        c, eps, _ = update_bands(basis2, ik, coeffs[ik], v, steps=3)
        serial.append(c)
        serial_eps.append(eps)
    stacked, stacked_eps, _ = update_bands_all_k(basis2, coeffs, v,
                                                 steps=3, stacked=True)
    for ik in range(basis2.nk):
        assert float(jnp.abs(stacked[ik] - serial[ik]).max()) < 1e-10
        assert float(jnp.abs(stacked_eps[ik]
                             - serial_eps[ik]).max()) < 1e-10
    occ = np.ones((basis2.nk, basis2.nbands))
    rho_s = density_from_orbitals(basis2, serial, occ)
    rho_k = density_from_orbitals(basis2, stacked, occ)
    assert float(jnp.abs(rho_k - rho_s).max()) < 1e-10


def test_stacked_band_tables_cached_and_exact(basis2):
    """The dense kinetic/mask/precond tables: padded lanes exactly zero,
    valid lanes bitwise-equal to the per-k ladders, and one PlanCache
    entry (second fetch is a hit, same object, no schedule search)."""
    cache = global_plan_cache()
    tab = basis2.stacked_band_tables()
    npm = basis2.npacked_max
    assert tab.kinetic.shape == tab.mask.shape == tab.precond.shape \
        == (basis2.nk, npm)
    for ik in range(basis2.nk):
        npk = basis2.npacked(ik)
        kin = np.asarray(basis2.kinetic(ik))
        np.testing.assert_array_equal(np.asarray(tab.kinetic[ik, :npk]),
                                      kin)
        np.testing.assert_array_equal(
            np.asarray(tab.precond[ik, :npk]),
            np.asarray(1.0 / (1.0 + basis2.kinetic(ik))))
        assert np.asarray(tab.mask[ik, :npk]).all()
        for a in (tab.kinetic, tab.mask, tab.precond):
            assert np.abs(np.asarray(a[ik, npk:])).max(initial=0.0) == 0.0
    hits = cache.stats["hits"]
    searches = FftPlan.searches
    tab2 = basis2.stacked_band_tables()
    assert tab2 is tab
    assert cache.stats["hits"] == hits + 1
    assert FftPlan.searches == searches


def test_stacked_engine_two_transforms_per_sweep_no_perk_linalg(basis2):
    """Acceptance instrumentation: one stacked band-update sweep is
    exactly two distributed transforms (one batched inverse, one batched
    forward — however many k-points ride it) and zero per-k Python
    linalg dispatches; the pipelined fallback pays 2·nk transforms and
    2·nk linalg calls per step."""
    from repro.dft import hamiltonian as H
    from repro.kernels import sphere_pack
    rng = np.random.default_rng(21)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    coeffs = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    basis2.stacked_hamiltonian_plans()          # warm the plan cache
    ex0, pk0 = FftPlan.executions, H.PERK_LINALG_CALLS
    d0 = dict(sphere_pack.DISPATCHES)
    _, _, nsweep = update_bands_all_k(basis2, coeffs, v, steps=2,
                                      stacked=True)
    assert nsweep == 4
    assert FftPlan.executions - ex0 == 2 * nsweep      # 2 per sweep
    assert H.PERK_LINALG_CALLS - pk0 == 0              # fully batched
    # the matmul route must not fire the fused pallas kernels
    assert dict(sphere_pack.DISPATCHES) == d0
    ex0, pk0 = FftPlan.executions, H.PERK_LINALG_CALLS
    update_bands_all_k(basis2, coeffs, v, steps=2, stacked=False)
    assert FftPlan.executions - ex0 == 2 * nsweep * basis2.nk
    assert H.PERK_LINALG_CALLS - pk0 == 2 * 2 * basis2.nk


# ---------------------------------------------- segmented ragged stacking
KPTS3 = ((0.0, 0.0, 0.0), (0.37, 0.21, 0.11), (0.5, 0.5, 0.5))


def test_basis_default_single_segment(basis2):
    """segment_padding=None keeps the pre-segmentation contract: one
    identity-ordered full-batch segment, pad_width == npacked_max — so
    every cache key and stacked code path is unchanged."""
    assert basis2.segment_padding is None
    assert basis2.segments == (tuple(range(basis2.nk)),)
    assert basis2.nsegments == 1
    for ik in range(basis2.nk):
        assert basis2.seg_of(ik) == 0
        assert basis2.pad_width(ik) == basis2.npacked_max


def test_basis_segmented_partition_and_budget(g1):
    """A padding budget partitions the k-points into similar-npacked
    segments whose realized padding stays under the budget; pad_width
    becomes per-segment."""
    budget = 0.02
    b = PlaneWaveBasis(16, kpts=KPTS3, nbands=3, grid=g1,
                       segment_padding=budget)
    flat = sorted(i for seg in b.segments for i in seg)
    assert flat == list(range(b.nk))        # exact partition of the k range
    assert b.nsegments >= 2                 # 3 ragged spheres under 2%
    assert len(b.segment_padding_fractions) == b.nsegments
    for s, seg in enumerate(b.segments):
        assert b.segment_padding_fractions[s] <= budget + 1e-9
        width = max(b.npacked(ik) for ik in seg)
        for ik in seg:
            assert b.seg_of(ik) == s
            assert b.pad_width(ik) == width
    # the global realized padding is a weighted mean of per-segment ones
    assert 0.0 <= b.padding_fraction <= budget + 1e-9


def test_basis_pencil_grid_specs():
    """(batch, fft, fft) pencil convention: first axis batch, the two
    trailing axes jointly decompose the transforms; the spec strings
    carry both fft mesh axes.  Abstract grids suffice — construction
    and validation never execute."""
    g3 = ProcGrid.create_abstract([2, 2, 2])
    b = PlaneWaveBasis(16, kpts=KPTS2, nbands=4, grid=g3)
    assert b.batch_axes == (0,) and b.fft_axes == (1, 2)
    assert b.batch_procs == 2 and b.fft_procs == 4
    assert b._pw_spec == "b{0} x{1,2} y z -> b{0} X Y Z{1,2}"
    assert b._cube_spec == "x y z{1,2} -> X Y Z{1,2}"
    assert b.stacks_k                       # nk=2 divides pb=2


def test_segmentation_restores_k_stacking():
    """nk=3 cannot stack as one batch on a pb=2 grid (3 ∤ 2), but
    segment sizes are constrained to divide the batch-axis size, so a
    segmented basis recovers the stacked route segment by segment."""
    g2 = ProcGrid.create_abstract([2, 2])
    kpts3 = ((0, 0, 0), (0.3, 0, 0), (0, 0.3, 0))
    b0 = PlaneWaveBasis(16, kpts=kpts3, nbands=4, grid=g2)
    assert not b0.stacks_k                  # nk=3 ∤ pb=2 → fallback
    b = PlaneWaveBasis(16, kpts=kpts3, nbands=4, grid=g2,
                       segment_padding=0.25)
    assert b.stacks_k                       # every segment shards evenly
    for seg in b.segments:
        assert b.batch_procs % len(seg) == 0
        assert (len(seg) * b.nbands) % b.batch_procs == 0


def test_segmented_stacked_bitwise_vs_perk(g1):
    """Acceptance: segmented stacked H applies and band updates are
    BITWISE equal to the per-k path — the per-k oracle pads its linalg
    to the k's segment lane width, so both routes execute identical
    GEMM contraction lengths and the f32 sums associate identically."""
    from repro.dft.density import _density_stacked
    b = PlaneWaveBasis(16, kpts=KPTS3, nbands=3, grid=g1,
                       segment_padding=0.02)
    assert b.nsegments == 2
    rng = np.random.default_rng(17)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    coeffs = [_rand_bands(rng, b.nbands, b.npacked(ik))
              for ik in range(b.nk)]
    stacked = apply_hamiltonian_stacked(b, coeffs, v)
    for ik in range(b.nk):
        ref = apply_hamiltonian(b, ik, coeffs[ik], v)
        assert float(jnp.abs(stacked[ik] - ref).max()) == 0.0
    serial, serial_eps = [], []
    for ik in range(b.nk):
        c, eps, _ = update_bands(b, ik, coeffs[ik], v, steps=3)
        serial.append(c)
        serial_eps.append(eps)
    ex0 = FftPlan.executions
    stk, stk_eps, nsweep = update_bands_all_k(b, coeffs, v, steps=3,
                                              stacked=True)
    # per-segment engine: one batched inverse + one batched forward per
    # sweep per segment
    assert FftPlan.executions - ex0 == 2 * nsweep * b.nsegments
    for ik in range(b.nk):
        assert float(jnp.abs(stk[ik] - serial[ik]).max()) == 0.0
        assert float(jnp.abs(stk_eps[ik] - serial_eps[ik]).max()) == 0.0
    # density sums per-segment contributions — summation *order* differs
    # from the per-k accumulation, so f32 noise (not bitwise) is expected
    occ = np.ones((b.nk, b.nbands))
    rho_ref = density_from_orbitals(b, serial, occ)
    rho_seg = _density_stacked(b, serial, occ)
    assert (float(jnp.abs(rho_seg - rho_ref).max())
            / float(rho_ref.max())) < 1e-6


def test_scf_jit_step_matches_eager_and_dispatches_only_at_trace(basis2):
    """Acceptance: the fused jit step reproduces the eager stacked run
    (identical f32 linear mixing ⇒ energies agree to f32 energy-reduction
    precision) and performs zero per-iteration Python transform
    dispatches — the FftPlan execution count is identical for 3- and
    6-iteration runs (trace-time only) with zero per-k linalg calls."""
    from repro.dft import hamiltonian as H
    g1 = basis2.grid
    cfg = {"n": 16, "nbands": 3, "kpts": KPTS2, "max_iter": 6,
           "mix_warmup": 99, "mix_history": 1}
    eager = run_scf(SCFConfig(**cfg, stack_k=True), grid=g1)
    ex0, pk0 = FftPlan.executions, H.PERK_LINALG_CALLS
    jit6 = run_scf(SCFConfig(**cfg, stack_k=True, jit_step=True), grid=g1)
    d6 = FftPlan.executions - ex0
    assert H.PERK_LINALG_CALLS - pk0 == 0
    assert jit6.jitted and jit6.band_update == "stacked"
    assert jit6.transforms == eager.transforms   # same analytic ledger
    assert jit6.iterations == eager.iterations == 6
    assert abs(jit6.energy - eager.energy) < 1e-4
    assert np.abs(jit6.eigenvalues - eager.eigenvalues).max() < 1e-4
    assert float(jnp.abs(jit6.rho - eager.rho).max()) \
        < 1e-4 * float(eager.rho.max())
    ex0 = FftPlan.executions
    jit3 = run_scf(SCFConfig(**dict(cfg, max_iter=3), stack_k=True,
                             jit_step=True), grid=g1)
    assert jit3.iterations == 3
    assert FftPlan.executions - ex0 == d6    # dispatches ∝ traces, not its
    # the fused step needs the stacked engine — per-k fallback is refused
    with pytest.raises(ValueError, match="jit_step=True requires"):
        run_scf(SCFConfig(**cfg, stack_k=False, jit_step=True), grid=g1)


def test_scf_jit_step_anderson_converges(basis2):
    """Full Anderson-mixed jitted SCF converges to the eager answer (the
    jitted DIIS runs in f32 against the eager mixer's f64 history, so the
    bound is mixing precision, not bitwise)."""
    res = run_scf(SCFConfig(n=16, nbands=4, kpts=KPTS2, max_iter=50,
                            stack_k=True, jit_step=True),
                  grid=basis2.grid)
    assert res.converged, (res.energies, res.residuals)
    assert res.jitted and res.stacked
    assert abs(res.energy - (-1.9197)) < 5e-3, res.energy
    for eps in res.eigenvalues:
        assert np.all(np.diff(eps) >= -1e-6)


def test_scf_stack_k_flag_equivalent(basis2):
    """run_scf(stack_k=True) ≡ run_scf(stack_k=False): forcing the ragged
    stacked H sweeps changes dispatch, not results — the pipelined path
    stays available as the equivalence oracle."""
    g1 = basis2.grid
    cfg = {"n": 16, "nbands": 3, "kpts": KPTS2, "max_iter": 6,
           "mix_warmup": 99}
    a = run_scf(SCFConfig(**cfg, stack_k=True), grid=g1)
    b = run_scf(SCFConfig(**cfg, stack_k=False), grid=g1)
    assert a.stacked and not b.stacked
    assert a.padding_fraction > 0.0 and b.padding_fraction == 0.0
    assert a.transforms == b.transforms
    assert abs(a.energy - b.energy) < 1e-10
    assert float(jnp.abs(a.rho - b.rho).max()) < 1e-10
    # forcing the stacked route without the all-k loop is contradictory —
    # refused loudly rather than silently running serial per-k
    with pytest.raises(ValueError, match="stack_k=True requires"):
        run_scf(SCFConfig(**cfg, stack_k=True, pipeline=False), grid=g1)


# ---------------------------------------------------------------------- SCF
def test_scf_converges_two_kpoints_multi_band():
    """Acceptance: 2 k-points × 4 bands converges, energy monotone after
    the mixing warm-up, per-k sphere plans served from the PlanCache, and
    the Hartree term computed via the full-cube plan pair.

    Runs on however many devices the process sees — 1 in the default CI
    job, 4 in the multi-device job (XLA_FLAGS forced device count)."""
    import jax
    grid = ProcGrid.create([jax.device_count()],
                           ["dft_scf"])        # fresh axis → cold plans
    cache = global_plan_cache()
    misses0 = cache.stats["misses"]
    cfg = SCFConfig(n=16, nbands=4, kpts=KPTS2, max_iter=50)
    res = run_scf(cfg, grid=grid)
    assert res.converged, (res.energies, res.residuals)
    de = abs(res.energies[-1] - res.energies[-2])
    assert de < cfg.e_tol
    # monotone decrease once mixing has warmed up (small f32 slack)
    tail = res.energies[cfg.mix_warmup + 1:]
    assert all(b <= a + 2e-5 for a, b in zip(tail, tail[1:])), tail
    # 2 distinct sphere plans + 1 cube plan built, everything else hits
    assert cache.stats["misses"] == misses0 + 3
    assert res.cache_stats["hits"] > 10 * res.cache_stats["misses"]
    # eigenvalues come out sorted per k
    for eps in res.eigenvalues:
        assert np.all(np.diff(eps) >= -1e-6)
    # both wells bind: lowest two bands are split by less than well depth
    assert res.energy < 0.0
    assert res.transforms > 100


def test_scf_2d_grid_4dev(dist):
    """Acceptance: SCF convergence on a 2×2 (batch×fft) grid with 4 forced
    host devices — bands sharded over the batch axis, k-points stacked
    into the ragged nk·nbands batch for both the density build and the
    Hamiltonian apply — plus stacked ≡ pipelined ≡ serial H applies and
    band updates to 1e-10 on the distributed grid, the batched engine's
    two-transforms-per-sweep / zero-per-k-linalg instrumentation, and the
    fused jit step converging on the distributed grid."""
    script = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import FftPlan, ProcGrid, global_plan_cache
from repro.dft import PlaneWaveBasis, SCFConfig, run_scf
from repro.dft import hamiltonian as Hmod
from repro.dft.density import density_from_orbitals, electron_count
from repro.dft.hamiltonian import (apply_hamiltonian,
                                   apply_hamiltonian_pipelined,
                                   apply_hamiltonian_stacked,
                                   orthonormalize, update_bands,
                                   update_bands_all_k)
assert jax.device_count() == 4
grid = ProcGrid.create([2, 2], ["dft_b", "dft_f"])
basis = PlaneWaveBasis(16, kpts=((0,0,0),(0.5,0.5,0.5)), nbands=4,
                       grid=grid)
assert basis.stacks_k
assert basis.npacked(0) != basis.npacked(1)   # ragged sphere batch
rng = np.random.default_rng(0)
coeffs = [orthonormalize(jnp.asarray(
    (rng.standard_normal((4, basis.npacked(ik)))
     + 1j*rng.standard_normal((4, basis.npacked(ik)))).astype(np.complex64)))
    for ik in range(2)]
occ = np.ones((2, 4))

# stacked (k×bands batched) density == per-k reference accumulation
rho = density_from_orbitals(basis, coeffs, occ)
ref = jnp.zeros((16,)*3, jnp.float32)
for ik in range(2):
    inv, _ = basis.plans_for_k(ik)
    psi = inv(inv.unpack(coeffs[ik]))
    f = jnp.asarray((basis.weights[ik] * occ[ik]).astype(np.float32))
    ref = ref + jnp.tensordot(f, jnp.abs(psi)**2, axes=(0, 0))
ref = ref * jnp.float32(basis.n**3 / basis.dv)
assert float(jnp.abs(rho - ref).max()) / float(ref.max()) < 1e-5
assert abs(electron_count(basis, rho) - 4.0) < 1e-3

# stacked == pipelined == serial H apply on the distributed ragged batch
v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
hs = apply_hamiltonian_stacked(basis, coeffs, v)
hp = apply_hamiltonian_pipelined(basis, coeffs, v)
for ik in range(2):
    href = apply_hamiltonian(basis, ik, coeffs[ik], v)
    assert float(jnp.abs(hs[ik] - href).max()) < 1e-10
    assert float(jnp.abs(hp[ik] - href).max()) < 1e-10

# stacked band update == serial band update — coefficients, densities AND
# eigenvalues (regression: mixed-placement eager linalg used to double the
# reported Ritz values on multi-device 2D grids; _replicated pins every
# block before the concatenates/contractions)
serial, eps_ser = [], []
for ik in range(2):
    ck, ek, _ = update_bands(basis, ik, coeffs[ik], v, steps=2)
    serial.append(ck); eps_ser.append(ek)
ex0, pk0 = FftPlan.executions, Hmod.PERK_LINALG_CALLS
stacked, eps_stk, nsweep = update_bands_all_k(basis, coeffs, v, steps=2)
# batched-engine instrumentation holds on the distributed grid too:
# each sweep is exactly two distributed transforms (one batched inverse,
# one batched forward, all nk*nbands orbitals aboard), zero per-k linalg
assert FftPlan.executions - ex0 == 2 * nsweep, FftPlan.executions - ex0
assert Hmod.PERK_LINALG_CALLS - pk0 == 0
for ik in range(2):
    assert float(jnp.abs(stacked[ik] - serial[ik]).max()) < 1e-10
    assert float(jnp.abs(eps_stk[ik] - eps_ser[ik]).max()) < 1e-10
    # Ritz values are the Rayleigh quotients of the returned bands
    hck = apply_hamiltonian(basis, ik, serial[ik], v)
    rq = np.sort(np.real(np.asarray(
        jnp.sum(jnp.conj(serial[ik]) * hck, axis=1))))
    assert np.abs(rq - np.asarray(eps_ser[ik])).max() < 1e-5
rho_s = density_from_orbitals(basis, serial, occ)
rho_k = density_from_orbitals(basis, stacked, occ)
assert float(jnp.abs(rho_k - rho_s).max()) < 1e-10

# full SCF on the 2D grid converges to the 1-device reference energy and
# rides the stacked route; everything is pre-built above except the cube
# pair, so exactly one plan-cache miss remains
cache = global_plan_cache()
misses0 = cache.stats["misses"]
cfg = SCFConfig(n=16, nbands=4, kpts=((0,0,0),(0.5,0.5,0.5)), max_iter=50)
res = run_scf(cfg, grid=grid)
assert res.converged, (res.energies, res.residuals)
assert res.grid_shape == (2, 2)
assert res.stacked and res.padding_fraction > 0.0
assert res.band_update == "stacked" and not res.jitted
assert cache.stats["misses"] == misses0 + 1   # only the cube plan is new
assert abs(res.energy - (-1.9197)) < 5e-3, res.energy

# the fused jit step on the same grid: every plan already cached (zero
# new misses), zero per-k linalg, converges to the eager stacked energy
# to mixing precision (its DIIS runs in f32)
misses1 = cache.stats["misses"]
pk0 = Hmod.PERK_LINALG_CALLS
resj = run_scf(SCFConfig(n=16, nbands=4, kpts=((0,0,0),(0.5,0.5,0.5)),
                         max_iter=50, jit_step=True), grid=grid)
assert resj.converged, (resj.energies, resj.residuals)
assert resj.jitted and resj.band_update == "stacked"
assert cache.stats["misses"] == misses1
assert Hmod.PERK_LINALG_CALLS == pk0
assert abs(resj.energy - res.energy) < 1e-3, (resj.energy, res.energy)
print("OK", res.iterations, resj.iterations, round(res.energy, 5))
"""
    out = dist(script, n_devices=4)
    assert "OK" in out


def test_scf_pencil_grid_8dev(dist):
    """Acceptance: SCF on the chooser's (2, 2, 2) batch×fft×fft pencil
    grid with 8 forced host devices — two decomposed fft axes — converges
    to the 1-device energy on the stacked route; a segmented run (tight
    padding budget → per-k segments) converges to the same energy with
    zero realized padding and rides the jit step unchanged."""
    script = """
import numpy as np, jax
from repro.dft import PlaneWaveBasis, SCFConfig, run_scf
from repro.sharding.grids import choose_dft_grid
assert jax.device_count() == 8
grid = choose_dft_grid(nbands=4, nk=2, diameter=8)
assert grid.shape == (2, 2, 2), grid.shape

basis = PlaneWaveBasis(16, kpts=((0,0,0),(0.5,0.5,0.5)), nbands=4,
                       grid=grid)
assert basis.batch_axes == (0,) and basis.fft_axes == (1, 2)
assert basis.fft_procs == 4 and basis.stacks_k

cfg = SCFConfig(n=16, nbands=4, kpts=((0,0,0),(0.5,0.5,0.5)), max_iter=50)
res = run_scf(cfg, grid=grid)
assert res.converged, (res.energies, res.residuals)
assert res.grid_shape == (2, 2, 2)
assert res.stacked and res.band_update == "stacked"
assert res.segments == 1 and res.padding_fraction > 0.0
assert abs(res.energy - (-1.9197)) < 5e-3, res.energy

# segmented: the 2% budget splits the 280/254-packed spheres into two
# per-k segments (each 0% padding); same converged energy
cfg2 = SCFConfig(n=16, nbands=4, kpts=((0,0,0),(0.5,0.5,0.5)),
                 max_iter=50, segment_padding=0.02)
res2 = run_scf(cfg2, grid=grid)
assert res2.converged, (res2.energies, res2.residuals)
assert res2.stacked and res2.band_update == "stacked"
assert res2.segments == 2
assert res2.padding_fraction == 0.0
assert tuple(res2.segment_padding_fractions) == (0.0, 0.0)
assert abs(res2.energy - res.energy) < 1e-3, (res2.energy, res.energy)

# the fused jit step on the segmented pencil basis
res3 = run_scf(SCFConfig(n=16, nbands=4, kpts=((0,0,0),(0.5,0.5,0.5)),
                         max_iter=50, segment_padding=0.02,
                         jit_step=True), grid=grid)
assert res3.converged and res3.jitted and res3.segments == 2
assert abs(res3.energy - res.energy) < 1e-3, (res3.energy, res.energy)
print("OK", res.iterations, res2.iterations, res3.iterations,
      round(res.energy, 5))
"""
    out = dist(script, n_devices=8)
    assert "OK" in out


# ---------------------------------------- fused pallas sphere-pack route
@pytest.fixture(scope="module")
def basis2_pallas(g1):
    return PlaneWaveBasis(16, kpts=KPTS2, nbands=3, grid=g1,
                          backend="pallas")


def test_stacked_hamiltonian_pallas_bitwise_vs_matmul(basis2, basis2_pallas):
    """Acceptance: the fused pallas sphere-pack route through the full
    stacked Hamiltonian apply is bitwise-equal to the composed XLA matmul
    route on the same ragged sphere batch, matches the per-k oracle to
    1e-10, and actually dispatches both fused kernels (no silent
    fallback to the composed path)."""
    from repro.kernels import sphere_pack
    assert basis2_pallas.backend == "pallas"
    inv, fwd = basis2_pallas.stacked_hamiltonian_plans()
    assert inv._fused_in_parts() is not None     # fusion guards held
    assert fwd._fused_out_parts() is not None
    rng = np.random.default_rng(11)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    blocks = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    d0 = dict(sphere_pack.DISPATCHES)
    hp = apply_hamiltonian_stacked(basis2_pallas, blocks, v)
    assert sphere_pack.DISPATCHES["unpack_dft"] == d0["unpack_dft"] + 1
    assert sphere_pack.DISPATCHES["dft_pack"] == d0["dft_pack"] + 1
    hm = apply_hamiltonian_stacked(basis2, blocks, v)
    for ik in range(basis2.nk):
        assert float(jnp.abs(hp[ik] - hm[ik]).max()) == 0.0   # bitwise
        ref = apply_hamiltonian(basis2, ik, blocks[ik], v)
        assert float(jnp.abs(hp[ik] - ref).max()) < 1e-10


def test_stacked_engine_pallas_dispatch_parity(basis2_pallas):
    """The fused kernels replace a *stage*, not a plan: one pallas band
    sweep is still exactly two plan executions (the derived remainder and
    lead plans keep the composed route's accounting) plus exactly one
    fused dispatch per direction per sweep, and zero per-k linalg."""
    from repro.dft import hamiltonian as H
    from repro.kernels import sphere_pack
    rng = np.random.default_rng(21)
    v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
    coeffs = [_rand_bands(rng, basis2_pallas.nbands,
                          basis2_pallas.npacked(ik))
              for ik in range(basis2_pallas.nk)]
    basis2_pallas.stacked_hamiltonian_plans()   # warm the plan cache
    ex0, pk0 = FftPlan.executions, H.PERK_LINALG_CALLS
    d0 = dict(sphere_pack.DISPATCHES)
    _, _, nsweep = update_bands_all_k(basis2_pallas, coeffs, v, steps=2,
                                      stacked=True)
    assert nsweep == 4
    assert FftPlan.executions - ex0 == 2 * nsweep      # parity with matmul
    assert H.PERK_LINALG_CALLS - pk0 == 0
    assert sphere_pack.DISPATCHES["unpack_dft"] - d0["unpack_dft"] == nsweep
    assert sphere_pack.DISPATCHES["dft_pack"] - d0["dft_pack"] == nsweep


def test_stacked_pack_dispatch_has_no_concatenate(basis2):
    """Satellite: the zero-slot concatenate is hoisted to table-build time
    — the per-dispatch ``pack`` trace is gather + where only, so the
    dispatch path never re-materializes the widened source each call."""
    import jax
    inv, fwd = basis2.stacked_hamiltonian_plans()
    rng = np.random.default_rng(5)
    blocks = [_rand_bands(rng, basis2.nbands, basis2.npacked(ik))
              for ik in range(basis2.nk)]
    cube = inv.unpack(jnp.asarray(inv.stack(blocks)))
    jaxpr = str(jax.make_jaxpr(inv.pack)(cube))
    assert "concatenate" not in jaxpr
    # and the fast path still zeroes the padded lanes exactly
    out = np.asarray(inv.pack(cube))
    valid = np.repeat(inv.valid_lanes(), basis2.nbands, axis=0)
    assert np.abs(out[~valid]).max(initial=0.0) == 0.0


def test_stacked_hamiltonian_pallas_4dev(dist):
    """Acceptance: fused pallas route bitwise-equal to the XLA matmul
    route through the full stacked Hamiltonian apply on a 2×2 (batch×fft)
    grid with 4 forced host devices — the pack-side lane localization +
    psum merge must reproduce the composed gather exactly — and a full
    SCF run on backend='pallas' converges to the reference energy with
    the resolved backend surfaced on the result."""
    script = """
import numpy as np, jax, jax.numpy as jnp
from repro.core import ProcGrid
from repro.dft import PlaneWaveBasis, SCFConfig, run_scf
from repro.dft.hamiltonian import (apply_hamiltonian,
                                   apply_hamiltonian_stacked,
                                   orthonormalize)
from repro.kernels import sphere_pack
assert jax.device_count() == 4
grid = ProcGrid.create([2, 2], ["pal_b", "pal_f"])
kpts = ((0,0,0),(0.5,0.5,0.5))
bp = PlaneWaveBasis(16, kpts=kpts, nbands=4, grid=grid, backend="pallas")
bm = PlaneWaveBasis(16, kpts=kpts, nbands=4, grid=grid)
assert bp.backend == "pallas" and bm.backend == "matmul"
assert bp.stacks_k and bp.npacked(0) != bp.npacked(1)
inv, fwd = bp.stacked_hamiltonian_plans()
assert inv._fused_in_parts() is not None    # fusion engages on the 2D grid
assert fwd._fused_out_parts() is not None
rng = np.random.default_rng(0)
coeffs = [orthonormalize(jnp.asarray(
    (rng.standard_normal((4, bp.npacked(ik)))
     + 1j*rng.standard_normal((4, bp.npacked(ik)))).astype(np.complex64)))
    for ik in range(2)]
v = jnp.asarray(rng.standard_normal((16, 16, 16)).astype(np.float32))
d0 = dict(sphere_pack.DISPATCHES)
hp = apply_hamiltonian_stacked(bp, coeffs, v)
assert sphere_pack.DISPATCHES["unpack_dft"] == d0["unpack_dft"] + 1
assert sphere_pack.DISPATCHES["dft_pack"] == d0["dft_pack"] + 1
hm = apply_hamiltonian_stacked(bm, coeffs, v)
for ik in range(2):
    assert float(jnp.abs(hp[ik] - hm[ik]).max()) == 0.0     # bitwise
    href = apply_hamiltonian(bm, ik, coeffs[ik], v)
    assert float(jnp.abs(hp[ik] - href).max()) < 1e-10

res = run_scf(SCFConfig(n=16, nbands=4, kpts=kpts, max_iter=50,
                        backend="pallas"), grid=grid)
assert res.converged, (res.energies, res.residuals)
assert res.backend == "pallas" and res.stacked
assert abs(res.energy - (-1.9197)) < 5e-3, res.energy
print("OK", res.iterations, round(res.energy, 5))
"""
    out = dist(script, n_devices=4)
    assert "OK" in out


def test_scf_distributed_4dev(dist):
    """Acceptance: same problem on 4 simulated devices — sphere plans from
    the cache, cube pair for Hartree, convergence to the 1-device energy."""
    script = """
from repro.core import global_plan_cache
from repro.dft import SCFConfig, run_scf
import jax
assert jax.device_count() == 4
cfg = SCFConfig(n=16, nbands=4, kpts=((0,0,0),(0.5,0.5,0.5)), max_iter=50)
res = run_scf(cfg)
assert res.converged, res.energies
assert res.cache_stats["misses"] == 3      # 2 spheres + 1 cube
assert res.cache_stats["hits"] >= 1        # repeated spheres hit the cache
assert abs(res.energy - (-1.9197)) < 5e-3, res.energy
print("OK", res.iterations, round(res.energy, 5))
"""
    out = dist(script, n_devices=4)
    assert "OK" in out
