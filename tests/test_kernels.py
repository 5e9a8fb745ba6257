"""Per-kernel sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.dft_matmul import dft_matmul


def _cx(rng, shape):
    return (rng.standard_normal(shape)
            + 1j * rng.standard_normal(shape)).astype(np.complex64)


@pytest.mark.parametrize("B", [1, 8, 33, 256])
@pytest.mark.parametrize("n", [8, 16, 128])
def test_dft_apply_square_shapes(B, n):
    rng = np.random.default_rng(B * 1000 + n)
    x = _cx(rng, (B, n))
    y = np.asarray(ops.dft_apply(jnp.asarray(x)))
    r = np.asarray(ref.dft_apply_ref(jnp.asarray(x)))
    np.testing.assert_allclose(y, r, rtol=2e-4, atol=2e-4 * n)


@pytest.mark.parametrize("n_in,n_out", [(8, 32), (16, 16), (32, 8),
                                        (24, 48), (128, 64)])
@pytest.mark.parametrize("inverse", [False, True])
def test_dft_apply_rectangular(n_in, n_out, inverse):
    rng = np.random.default_rng(n_in * 100 + n_out + inverse)
    x = _cx(rng, (16, n_in))
    y = np.asarray(ops.dft_apply(jnp.asarray(x), n_out, inverse=inverse))
    r = np.asarray(ref.dft_apply_ref(jnp.asarray(x), n_out,
                                     inverse=inverse))
    np.testing.assert_allclose(y, r, rtol=2e-4, atol=1e-5 * max(n_in, 1))


def test_raw_kernel_vs_complex_matmul():
    rng = np.random.default_rng(7)
    B, K, N = 64, 32, 48
    xr = rng.standard_normal((B, K)).astype(np.float32)
    xi = rng.standard_normal((B, K)).astype(np.float32)
    wr = rng.standard_normal((N, K)).astype(np.float32)
    wi = rng.standard_normal((N, K)).astype(np.float32)
    yr, yi = dft_matmul(jnp.asarray(xr), jnp.asarray(xi), jnp.asarray(wr),
                        jnp.asarray(wi), bm=32, bn=16, interpret=True)
    rr, ri = ref.complex_matmul_ref(xr, xi, wr, wi)
    np.testing.assert_allclose(np.asarray(yr), rr, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(yi), ri, rtol=1e-4, atol=1e-3)


def test_kernel_twiddle_epilogue():
    rng = np.random.default_rng(8)
    B, K, N = 32, 16, 16
    xr, xi, wr, wi, tr, ti = [
        rng.standard_normal(s).astype(np.float32)
        for s in [(B, K), (B, K), (N, K), (N, K), (B, N), (B, N)]]
    yr, yi = dft_matmul(*map(jnp.asarray, (xr, xi, wr, wi, tr, ti)),
                        bm=16, bn=16, interpret=True)
    rr, ri = ref.complex_matmul_ref(xr, xi, wr, wi)
    err = np.abs(np.asarray(yr) - (rr * tr - ri * ti)).max()
    eri = np.abs(np.asarray(yi) - (rr * ti + ri * tr)).max()
    assert err < 1e-3 and eri < 1e-3


@pytest.mark.parametrize("n", [64, 360, 1024])
@pytest.mark.parametrize("inverse", [False, True])
def test_four_step_vs_fft(n, inverse):
    rng = np.random.default_rng(n + inverse)
    x = _cx(rng, (4, n))
    y = np.asarray(ops.four_step_dft(jnp.asarray(x), inverse=inverse))
    r = np.asarray(ref.four_step_ref(jnp.asarray(x), inverse=inverse))
    scale = np.abs(r).max()
    np.testing.assert_allclose(y, r, rtol=0, atol=3e-6 * n * max(scale, 1))


def test_four_step_rejects_prime():
    with pytest.raises(ValueError):
        ops.four_step_dft(jnp.zeros((2, 13), jnp.complex64))


def test_local_dft_backends_agree():
    from repro.core.local_fft import local_dft
    rng = np.random.default_rng(9)
    x = jnp.asarray(_cx(rng, (3, 5, 24)))
    outs = [np.asarray(local_dft(x, 2, 32, backend=b))
            for b in ("jnp", "matmul", "pallas")]
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(outs[0], outs[2], rtol=2e-4, atol=1e-4)


# ---------------------------------------------- fused sphere-pack kernels
def _sphere_batch(d, kpts):
    from repro.core import kpoint_sphere
    return [kpoint_sphere(d, kp) for kp in kpts]


def _composed_unpack_dft(spheres, nbands, pr, pi, wr, wi):
    """Oracle: scatter into the zero cube, then the matmul-backend GEMM."""
    ex, ey, ez = spheres[0].extents
    B = pr.shape[0]
    n = wr.shape[0]
    cr = np.zeros((B, ex * ey * ez), np.float32)
    ci = np.zeros((B, ex * ey * ez), np.float32)
    for b in range(B):
        s = spheres[b // nbands]
        idx = s.pack_indices()
        cr[b, idx] = pr[b, :s.npacked]
        ci[b, idx] = pi[b, :s.npacked]
    xr = jnp.asarray(cr.reshape(B * ex * ey, ez))
    xi = jnp.asarray(ci.reshape(B * ex * ey, ez))
    yr = xr @ jnp.asarray(wr).T - xi @ jnp.asarray(wi).T
    yi = xr @ jnp.asarray(wi).T + xi @ jnp.asarray(wr).T
    return (np.asarray(yr).reshape(B, ex, ey, n),
            np.asarray(yi).reshape(B, ex, ey, n))


def _composed_dft_pack(spheres, nbands, xr, xi, wr, wi, npm):
    """Oracle: last-stage GEMM into the cube, then the CSR gather."""
    B, ex, ey, n = xr.shape
    d = wr.shape[0]
    fr = jnp.asarray(xr.reshape(B * ex * ey, n))
    fi = jnp.asarray(xi.reshape(B * ex * ey, n))
    yr = np.asarray(fr @ jnp.asarray(wr).T - fi @ jnp.asarray(wi).T
                    ).reshape(B, ex * ey * d)
    yi = np.asarray(fr @ jnp.asarray(wi).T + fi @ jnp.asarray(wr).T
                    ).reshape(B, ex * ey * d)
    pr = np.zeros((B, npm), np.float32)
    pi = np.zeros((B, npm), np.float32)
    for b in range(B):
        s = spheres[b // nbands]
        idx = s.pack_indices()
        pr[b, :s.npacked] = yr[b, idx]
        pi[b, :s.npacked] = yi[b, idx]
    return pr, pi


@pytest.mark.parametrize("d,n,nbands,kpts", [
    (8, 16, 3, ((0, 0, 0), (0.5, 0.5, 0.5))),
    (6, 12, 2, ((0, 0, 0),)),
    (4, 8, 1, ((0.25, 0, 0.5), (0, 0, 0), (0.5, 0.5, 0))),
])
def test_unpack_dft_bitwise_vs_composed(d, n, nbands, kpts):
    from repro.core.local_fft import dft_matrix_device
    from repro.kernels import sphere_pack

    spheres = _sphere_batch(d, kpts)
    B = len(spheres) * nbands
    npm = max(s.npacked for s in spheres)
    rng = np.random.default_rng(d * 100 + n)
    # garbage beyond each row's npacked lanes: the line tables must never
    # read it (padded tails of the ragged stacked batch are untrusted)
    pr = rng.standard_normal((B, npm)).astype(np.float32)
    pi = rng.standard_normal((B, npm)).astype(np.float32)
    start, zlo, cnt, flag = sphere_pack.line_tables(spheres, nbands)
    wr, wi, _ = dft_matrix_device(n, d, True)
    yr, yi = sphere_pack.unpack_dft(
        jnp.asarray(pr), jnp.asarray(pi), jnp.asarray(start),
        jnp.asarray(zlo), jnp.asarray(cnt), jnp.asarray(flag), wr, wi,
        interpret=True)
    # the oracle reads only valid lanes — zero the tails it would scatter
    pr_v, pi_v = pr.copy(), pi.copy()
    for b in range(B):
        pr_v[b, spheres[b // nbands].npacked:] = 0.0
        pi_v[b, spheres[b // nbands].npacked:] = 0.0
    rr, ri = _composed_unpack_dft(spheres, nbands, pr_v, pi_v,
                                  np.asarray(wr), np.asarray(wi))
    assert np.abs(np.asarray(yr) - rr).max() == 0.0
    assert np.abs(np.asarray(yi) - ri).max() == 0.0


@pytest.mark.parametrize("d,n,nbands,kpts", [
    (8, 16, 3, ((0, 0, 0), (0.5, 0.5, 0.5))),
    (6, 12, 2, ((0, 0, 0),)),
])
def test_dft_pack_bitwise_and_padded_lanes_zero(d, n, nbands, kpts):
    from repro.core.local_fft import dft_matrix_device
    from repro.kernels import sphere_pack

    spheres = _sphere_batch(d, kpts)
    B = len(spheres) * nbands
    npm = max(s.npacked for s in spheres)
    rng = np.random.default_rng(d * 7 + n)
    ex, ey, _ = spheres[0].extents
    xr = rng.standard_normal((B, ex, ey, n)).astype(np.float32)
    xi = rng.standard_normal((B, ex, ey, n)).astype(np.float32)
    start, zlo, cnt, flag = sphere_pack.line_tables(spheres, nbands)
    wr, wi, _ = dft_matrix_device(d, n, False)
    pr, pi = sphere_pack.dft_pack(
        jnp.asarray(xr), jnp.asarray(xi), jnp.asarray(start),
        jnp.asarray(zlo), jnp.asarray(cnt), jnp.asarray(flag), wr, wi,
        npacked=npm, interpret=True)
    rr, ri = _composed_dft_pack(spheres, nbands, xr, xi,
                                np.asarray(wr), np.asarray(wi), npm)
    assert np.abs(np.asarray(pr) - rr).max() == 0.0
    assert np.abs(np.asarray(pi) - ri).max() == 0.0
    # padded lanes are exact +0.0 whatever the slab held (the ragged
    # two-sphere case has them; a single sphere pads nothing)
    pad = np.arange(npm)[None, :] >= np.repeat(
        [s.npacked for s in spheres], nbands)[:, None]
    assert pad.any() == (len(spheres) > 1)
    assert np.all(np.asarray(pr)[pad] == 0.0)
    assert np.all(np.asarray(pi)[pad] == 0.0)


def test_unpack_dft_zero_skip_planes():
    """A plane with flag=0 writes exact zeros without reading lanes."""
    from repro.core.local_fft import dft_matrix_device
    from repro.kernels import sphere_pack

    spheres = _sphere_batch(6, ((0, 0, 0),))
    start, zlo, cnt, flag = sphere_pack.line_tables(spheres, 2)
    B, npm = 2, spheres[0].npacked
    rng = np.random.default_rng(3)
    pr = rng.standard_normal((B, npm)).astype(np.float32)
    pi = rng.standard_normal((B, npm)).astype(np.float32)
    wr, wi, _ = dft_matrix_device(12, 6, True)
    flag0 = flag.copy()
    flag0[2] = 0                      # force the skip path on plane x=2
    yr, _ = sphere_pack.unpack_dft(
        jnp.asarray(pr), jnp.asarray(pi), jnp.asarray(start),
        jnp.asarray(zlo), jnp.asarray(cnt), jnp.asarray(flag0), wr, wi,
        interpret=True)
    assert np.all(np.asarray(yr)[:, 2] == 0.0)
    assert np.any(np.asarray(yr)[:, 1] != 0.0)


def test_line_tables_round_trip():
    """(start, zlo, cnt) reconstruct pack_indices exactly, per sphere."""
    from repro.kernels import sphere_pack

    spheres = _sphere_batch(8, ((0, 0, 0), (0.5, 0.5, 0.5)))
    ex, ey, ez = spheres[0].extents
    start, zlo, cnt, flag = sphere_pack.line_tables(spheres, 1)
    for k, s in enumerate(spheres):
        flat = []
        for l in range(ex * ey):
            for j in range(cnt[k, l]):
                flat.append(l * ez + zlo[k, l] + j)
                assert start[k, l] + j == len(flat) - 1
        assert np.array_equal(np.asarray(flat), s.pack_indices())
    assert flag.shape == (ex,) and flag.any()


def test_realized_backend_and_flops():
    from repro.core.local_fft import (MATMUL_MAX_N, dft_flops,
                                      realized_backend)
    assert realized_backend(16, 32, "matmul") == "matmul"
    assert realized_backend(16, 32, "pallas") == "pallas"
    assert realized_backend(16, 32, "jnp") == "jnp"
    big = MATMUL_MAX_N + 1
    assert realized_backend(big, big, "matmul") == "jnp"
    assert realized_backend(16, big, "pallas") == "jnp"
    with pytest.raises(ValueError):
        realized_backend(8, 8, "fftw")
    # above the crossover, flops are priced at the realized jnp backend
    assert dft_flops(big, big, 4, "matmul") == dft_flops(big, big, 4, "jnp")
    assert dft_flops(32, 16, 4, "pallas") == 8 * 32 * 16 * 4
