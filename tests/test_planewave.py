"""Plane-wave sphere transform: CSR offsets, pack/unpack, staged padding,
ragged k-stacked batches."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (ProcGrid, SphereDomain, make_planewave_pair,
                        make_stacked_planewave_pair, padded_pack_tables,
                        sphere_for_cutoff)


@pytest.fixture(scope="module")
def sph16():
    return SphereDomain.from_diameter(16)


def test_sphere_extents_and_cutoff(sph16):
    assert sph16.extents == (16, 16, 16)
    # every packed point satisfies |g - c|² ≤ r² (the paper's E_cut rule)
    m = sph16.mask()
    idx = np.argwhere(m)
    c = np.asarray(sph16.center)
    assert (((idx - c) ** 2).sum(1) <= sph16.radius ** 2 + 1e-9).all()


def test_sphere_occupancy_close_to_pi_over_6(sph16):
    # sphere volume / cube volume = π/6 ≈ 0.524
    occ = sph16.npacked / 16 ** 3
    assert 0.45 < occ < 0.58


def test_csr_offsets_consistent(sph16):
    off = sph16.offsets
    lens = off["z_hi"] - off["z_lo"]
    assert (lens > 0).all()
    assert off["row_ptr"][-1] == sph16.npacked
    np.testing.assert_array_equal(np.diff(off["row_ptr"]), lens)
    # xy projection is a disk: per-x column counts are symmetric
    xs = off["col_x"]
    counts = np.bincount(xs, minlength=16)
    np.testing.assert_array_equal(counts, counts[::-1])


def test_pack_indices_bijective(sph16):
    idx = sph16.pack_indices()
    assert len(np.unique(idx)) == sph16.npacked


@pytest.mark.parametrize("d,kpt", [(16, (0.0, 0.0, 0.0)), (9, (0.0, 0.0, 0.0)),
                                   (8, (0.5, 0.25, 0.5))])
def test_pack_indices_match_column_loop(d, kpt):
    """The vectorized table equals the column-by-column CSR walk."""
    from repro.core import kpoint_sphere
    s = kpoint_sphere(d, kpt)
    ex, ey, ez = s.extents
    (xl, yl, zl), off = s.lower, s.offsets
    ref = [((x - xl) * ey + (y - yl)) * ez + (z - zl)
           for x, y, lo, hi in zip(off["col_x"], off["col_y"], off["z_lo"],
                                   off["z_hi"])
           for z in range(lo, hi)]
    np.testing.assert_array_equal(s.pack_indices(), np.asarray(ref))


def test_pack_unpack_roundtrip(sph16):
    g = ProcGrid.create([1])
    inv, _ = make_planewave_pair(g, 32, sph16, 2)
    rng = np.random.default_rng(0)
    packed = (rng.standard_normal((2, sph16.npacked))
              + 1j * rng.standard_normal((2, sph16.npacked))
              ).astype(np.complex64)
    cube = inv.unpack(jnp.asarray(packed))
    assert cube.shape == (2, 16, 16, 16)
    back = np.asarray(inv.pack(cube))
    np.testing.assert_array_equal(back, packed)
    # everything outside the sphere is zero
    outside = np.asarray(cube)[:, ~sph16.mask()]
    assert np.abs(outside).max() == 0


def test_staged_padding_equals_padded_reference(sph16):
    """The paper's central numerical claim: staged pad+FFT ≡ pad-then-FFT."""
    g = ProcGrid.create([1])
    n = 32
    inv, fwd = make_planewave_pair(g, n, sph16, 2)
    rng = np.random.default_rng(1)
    packed = (rng.standard_normal((2, sph16.npacked))
              + 1j * rng.standard_normal((2, sph16.npacked))
              ).astype(np.complex64)
    cube = np.asarray(inv.unpack(jnp.asarray(packed)))
    full = np.zeros((2, n, n, n), np.complex64)
    full[:, :16, :16, :16] = cube
    ref = np.fft.ifftn(full, axes=(1, 2, 3))
    y = np.asarray(inv(jnp.asarray(cube)))
    np.testing.assert_allclose(y, ref, rtol=3e-4, atol=1e-6)


def test_forward_truncation(sph16):
    g = ProcGrid.create([1])
    n = 32
    _, fwd = make_planewave_pair(g, n, sph16, 2)
    rng = np.random.default_rng(2)
    x = (rng.standard_normal((2, n, n, n))
         + 1j * rng.standard_normal((2, n, n, n))).astype(np.complex64)
    y = np.asarray(fwd(jnp.asarray(x)))
    ref = np.fft.fftn(x, axes=(1, 2, 3))[:, :16, :16, :16]
    np.testing.assert_allclose(y, ref, rtol=3e-4,
                               atol=3e-3 * np.abs(ref).max())


def test_roundtrip_identity_on_sphere(sph16):
    g = ProcGrid.create([1])
    inv, fwd = make_planewave_pair(g, 32, sph16, 2)
    rng = np.random.default_rng(3)
    packed = (rng.standard_normal((2, sph16.npacked))
              + 1j * rng.standard_normal((2, sph16.npacked))
              ).astype(np.complex64)
    cube = inv.unpack(jnp.asarray(packed))
    rt = fwd(inv(cube))
    got = np.asarray(inv.pack(inv.mask_cube(rt)))
    np.testing.assert_allclose(got, packed, rtol=1e-3, atol=2e-5)


# ------------------------------------------------------ ragged k batches
def _ragged_spheres():
    """Two spheres sharing one bounding box with distinct point sets —
    the k-shifted-center situation the ragged batch layer exists for."""
    s0 = SphereDomain.from_diameter(8)
    s1 = SphereDomain(radius=4.0, center=(3.9, 3.9, 3.9), lower=(0, 0, 0),
                      upper=(7, 7, 7))
    assert s0.npacked != s1.npacked
    return [s0, s1]


def test_padded_pack_tables_dump_slot_and_validity():
    spheres = _ragged_spheres()
    idx, valid = padded_pack_tables(spheres)
    npmax = max(s.npacked for s in spheres)
    assert idx.shape == valid.shape == (2, npmax)
    dump = 8 * 8 * 8
    for k, s in enumerate(spheres):
        np.testing.assert_array_equal(idx[k, :s.npacked], s.pack_indices())
        assert (idx[k, s.npacked:] == dump).all()    # padded → dump slot
        assert valid[k, :s.npacked].all()
        assert not valid[k, s.npacked:].any()
    with pytest.raises(ValueError, match="bounding box"):
        padded_pack_tables([spheres[0], SphereDomain.from_diameter(6)])


def test_stacked_pair_matches_per_sphere_reference():
    """The stacked ragged batch reproduces each sphere's own plan pair —
    padding changes the batch shape, never the numbers."""
    g = ProcGrid.create([1])
    spheres = _ragged_spheres()
    nb, n = 2, 16
    inv, fwd = make_stacked_planewave_pair(g, n, spheres, nb)
    assert inv.nk == 2 and inv.npacked_max == max(s.npacked
                                                  for s in spheres)
    assert 0.0 < inv.padding_fraction < 0.5
    rng = np.random.default_rng(5)
    blocks = [jnp.asarray((rng.standard_normal((nb, s.npacked))
                           + 1j * rng.standard_normal((nb, s.npacked))
                           ).astype(np.complex64)) for s in spheres]
    psi = inv(inv.unpack(inv.stack(blocks)))
    assert psi.shape == (2 * nb, n, n, n)
    back = inv.split(inv.pack(fwd(psi)))
    for k, s in enumerate(spheres):
        pinv, pfwd = make_planewave_pair(g, n, s, nb)
        ref = pinv(pinv.unpack(blocks[k]))
        np.testing.assert_array_equal(
            np.asarray(psi[k * nb:(k + 1) * nb]), np.asarray(ref))
        np.testing.assert_allclose(np.asarray(back[k]),
                                   np.asarray(blocks[k]),
                                   rtol=1e-3, atol=2e-5)


def test_stacked_pair_shares_inner_plan_and_accounts_tables():
    """plan= wraps an existing d³→n³ FftPlan (no second build); the ragged
    tables are private bytes on top of the shared DFT-matrix tables."""
    from repro.core import FftPlan
    g = ProcGrid.create_abstract([1])
    spheres = _ragged_spheres()
    inv0, _ = make_stacked_planewave_pair(g, 16, spheres, 2)
    searches = FftPlan.searches
    inv, fwd = make_stacked_planewave_pair(g, 16, spheres, 2,
                                           plan=inv0.plan)
    assert inv.plan is inv0.plan
    assert FftPlan.searches == searches          # wrapped, not re-planned
    assert fwd.plan is inv0.plan.inverse()
    tables = int(inv._pad_idx.nbytes) + int(inv._valid.nbytes)
    assert inv.private_bytes() >= tables
    assert inv.estimated_bytes() == inv.private_bytes() + sum(
        inv.shared_table_bytes().values())
    assert inv.shared_table_bytes() == inv0.plan.shared_table_bytes()
    assert "Stacked" in inv.describe()


def test_padded_kinetic_table_matches_perk_ladders():
    """The dense (nk, npacked_max) kinetic table agrees bitwise with the
    per-k ladders on valid lanes and is exactly zero on padded lanes."""
    import numpy as np
    from repro.core import padded_kinetic_table
    from repro.dft import PlaneWaveBasis
    g = ProcGrid.create([1], ["pw_kin"])
    b = PlaneWaveBasis(16, kpts=((0, 0, 0), (0.5, 0.5, 0.5)), nbands=2,
                       grid=g)
    kin, valid = padded_kinetic_table(b.spheres, b.L)
    assert kin.shape == valid.shape == (2, b.npacked_max)
    for ik in range(2):
        npk = b.npacked(ik)
        assert valid[ik, :npk].all() and not valid[ik, npk:].any()
        np.testing.assert_array_equal(kin[ik, :npk],
                                      np.asarray(b.kinetic(ik)))
        assert (kin[ik, npk:] == 0.0).all()


def test_staged_moves_less_data_than_padded():
    """Fig. 9 mechanism: the staged transform's transpose moves ≥4× less."""
    from repro.core import Domain, DistTensor, FftPlan
    g = ProcGrid.create_abstract([4])
    n = 32
    sph = sphere_for_cutoff(n)            # d = 16
    inv, _ = make_planewave_pair(g, n, sph, 4)
    staged = sum(s["bytes_per_device"] for s in inv.comm_stats())
    b = Domain((0,), (3,))
    cube = Domain((0, 0, 0), (n - 1, n - 1, n - 1))
    ti = DistTensor.create((b, cube), "b x{0} y z", g)
    to = DistTensor.create((b, cube), "B X Y Z{0}", g)
    padded = FftPlan(ti, to, [("x", "X"), ("y", "Y"), ("z", "Z")],
                     inverse=True)
    full = sum(s["bytes_per_device"] for s in padded.comm_stats())
    assert staged * 4 <= full


def test_staged_fewer_flops_than_padded():
    from repro.core import Domain, DistTensor, FftPlan
    g = ProcGrid.create([1])
    n = 32
    sph = sphere_for_cutoff(n)
    inv, _ = make_planewave_pair(g, n, sph, 4)
    b = Domain((0,), (3,))
    cube = Domain((0, 0, 0), (n - 1, n - 1, n - 1))
    ti = DistTensor.create((b, cube), "b x{0} y z", g)
    to = DistTensor.create((b, cube), "B X Y Z{0}", g)
    padded = FftPlan(ti, to, [("x", "X"), ("y", "Y"), ("z", "Z")],
                     inverse=True)
    assert inv.flop_count() < padded.flop_count() * 0.65


def test_distributed_planewave(dist):
    script = """
import numpy as np, jax.numpy as jnp
from repro.core import ProcGrid, SphereDomain, make_planewave_pair
g = ProcGrid.create([8])
n = 32
sph = SphereDomain.from_diameter(16)
inv, fwd = make_planewave_pair(g, n, sph, 4)
rng = np.random.default_rng(1)
packed = (rng.standard_normal((4, sph.npacked))
          + 1j*rng.standard_normal((4, sph.npacked))).astype(np.complex64)
cube = np.asarray(inv.unpack(jnp.asarray(packed)))
full = np.zeros((4, n, n, n), np.complex64); full[:, :16, :16, :16] = cube
ref = np.fft.ifftn(full, axes=(1,2,3))
y = np.asarray(inv(jnp.asarray(cube)))
assert np.abs(y-ref).max() / np.abs(ref).max() < 5e-6
print("OK")
"""
    assert "OK" in dist(script)


def test_batch_plus_fft_grid_2d(dist):
    """2D processing grid: batch axis × fft axis (paper's >dims scaling)."""
    script = """
import numpy as np, jax.numpy as jnp
from repro.core import ProcGrid, SphereDomain, make_planewave_pair
g = ProcGrid.create([2, 4])
n = 32
sph = SphereDomain.from_diameter(16)
inv, fwd = make_planewave_pair(g, n, sph, 4, batch_axes=(0,), fft_axes=(1,))
rng = np.random.default_rng(1)
packed = (rng.standard_normal((4, sph.npacked))
          + 1j*rng.standard_normal((4, sph.npacked))).astype(np.complex64)
cube = np.asarray(inv.unpack(jnp.asarray(packed)))
full = np.zeros((4, n, n, n), np.complex64); full[:, :16, :16, :16] = cube
ref = np.fft.ifftn(full, axes=(1,2,3))
y = np.asarray(inv(jnp.asarray(cube)))
assert np.abs(y-ref).max() / np.abs(ref).max() < 5e-6
print("OK")
"""
    assert "OK" in dist(script)
