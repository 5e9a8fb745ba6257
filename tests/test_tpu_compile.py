"""Compile the hot-path kernels for a described TPU v5e, with no chip.

The TPU compiler refuses what Pallas interpret mode accepts: gathers across
vregs, blocks that break the (8, 128) tiling, more scoped VMEM than a
kernel may use.  These tests compile the kernels at the widths the chip
smoke run uses, so such refusals show here first.  The topology is
described inside a fixture (never at import), which skips where it cannot
be described.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.check.preflight import preflight_basis
from repro.core import kpoint_sphere
from repro.core.local_fft import local_dft
from repro.kernels import ops, sphere_pack
from repro.kernels.dft_matmul import dft_matmul

KPTS = ((0.0, 0.0, 0.0), (0.5, 0.5, 0.5))
#: the fused-route width the chip smoke run takes: the largest FFTB118
#: admits on one chip (n=128, d=64, 2 k-points × 4 bands)
PALLAS_N, PALLAS_D, PALLAS_NB = 128, 64, 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _sphere_args(sharding, nk, nb, d, n, pack_side):
    spheres = [kpoint_sphere(d, k) for k in KPTS[:nk]]
    B, npk = nk * nb, max(s.npacked for s in spheres)
    tab = _spec(sharding, (B, d * d), jnp.int32)
    flag = _spec(sharding, (d,), jnp.int32)
    if pack_side:
        x = _spec(sharding, (B, d, d, n))
        w = _spec(sharding, (d, n))
    else:
        x = _spec(sharding, (B, npk))
        w = _spec(sharding, (n, d))
    return npk, (x, x, tab, tab, tab, flag, w, w)


# ------------------------------------------------------------ kernels
@pytest.mark.parametrize("B,K,N", [(256, 128, 256), (65536, 64, 128),
                                   (65536, 128, 64)])
def test_dft_matmul_compiles(one_chip, B, K, N):
    bn = ops._pick_block(N, 128)
    c = _compile(lambda xr, xi, wr, wi: dft_matmul(
        xr, xi, wr, wi, bm=256, bn=bn, interpret=False),
        _spec(one_chip, (B, K)), _spec(one_chip, (B, K)),
        _spec(one_chip, (N, K)), _spec(one_chip, (N, K)))
    assert "tpu_custom_call" in c.as_text()


def test_unpack_dft_compiles_at_smoke_width(one_chip):
    _, args = _sphere_args(one_chip, 2, PALLAS_NB, PALLAS_D, PALLAS_N,
                           pack_side=False)
    c = _compile(lambda *a: sphere_pack.unpack_dft(*a, interpret=False),
                 *args)
    assert "tpu_custom_call" in c.as_text()


def test_dft_pack_compiles_at_smoke_width(one_chip):
    npk, args = _sphere_args(one_chip, 2, PALLAS_NB, PALLAS_D, PALLAS_N,
                             pack_side=True)
    c = _compile(lambda *a: sphere_pack.dft_pack(*a, npacked=npk,
                                                 interpret=False), *args)
    assert "tpu_custom_call" in c.as_text()


# -------------------------------------------------- Fig. 9 line stage
@pytest.mark.parametrize("backend", ["matmul", "pallas"])
def test_fig9_line_dft_stage_compiles(one_chip, backend):
    """First inverse stage of the Fig. 9 transform: (lines, 128) lines
    padded to 256 — an f32 (lines, 128) × (128, 256) GEMM pair."""
    lines = 8 * 128 * 128

    def stage(x):
        if backend == "pallas":
            return ops.dft_apply(x, 256, inverse=True, interpret=False)
        return local_dft(x, 1, 256, inverse=True, backend=backend)

    low = jax.jit(stage).lower(_spec(one_chip, (lines, 128), jnp.complex64))
    c = low.compile()
    if backend == "pallas":
        assert "tpu_custom_call" in c.as_text()
    else:
        # a TPU's default f32 dot is one bf16 pass: the stage must ask
        # for full f32 precision
        assert "HIGHEST" in low.as_text()


# ------------------------------------------------------ fused SCF step
def test_scf_step_compiles_from_abstract_arguments(topo):
    """The jitted SCF step takes its cube fields and band tables as
    arguments, so it compiles for a chip that is only described — how
    the chip smoke run's band counts are sized without a chip."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ProcGrid
    from repro.core.compat import mesh_from_devices
    from repro.dft import SCFConfig, StackedBandTables
    from repro.dft.basis import PlaneWaveBasis
    from repro.dft.hartree import HartreeSolver
    from repro.dft.scf import jit_mixer_init, make_scf_step
    from repro.sharding.grids import DFT_AXES_1D

    n, d, nb = 16, 8, 2
    grid = ProcGrid(mesh_from_devices(np.array(topo.devices[:1]),
                                      DFT_AXES_1D), DFT_AXES_1D)
    cfg = SCFConfig(n=n, diameter=d, nbands=nb, kpts=KPTS, stack_k=True,
                    jit_step=True)
    basis = PlaneWaveBasis(n, diameter=d, kpts=KPTS, nbands=nb, grid=grid)
    width = basis.stacked_hamiltonian_plans(0)[0].npacked_max
    rep = NamedSharding(grid.mesh, P())

    def spec(shape, dtype=jnp.float32):
        return _spec(rep, shape, dtype)

    mix = jax.tree.map(lambda a: spec(a.shape, a.dtype), jax.eval_shape(
        lambda: jit_mixer_init(n ** 3, cfg.mix_history)))
    tables = (StackedBandTables(*(spec((len(KPTS), width)),) * 3),)
    occ = np.full((len(KPTS), nb), 2.0)
    step = make_scf_step(cfg, basis, HartreeSolver(basis), occ,
                         float(occ.sum()))
    c = _compile(step, spec((n,) * 3),
                 (spec((len(KPTS), nb, width), jnp.complex64),), mix,
                 spec((n,) * 3), spec((n,) * 3, jnp.complex64), tables)
    assert c.memory_analysis().temp_size_in_bytes > 0


# ---------------------------------------------- FFTB118 vs the compiler
def test_fftb118_and_compiler_agree_at_smoke_width(one_chip):
    assert preflight_basis(PALLAS_N, diameter=PALLAS_D, kpts=KPTS,
                           nbands=PALLAS_NB, grid_shape=(1,),
                           backend="pallas") == []
    _, args = _sphere_args(one_chip, 2, PALLAS_NB, PALLAS_D, PALLAS_N,
                           pack_side=False)
    _compile(lambda *a: sphere_pack.unpack_dft(*a, interpret=False), *args)


@pytest.mark.parametrize("kernel", ["unpack_dft", "dft_pack"])
def test_fftb118_and_compiler_both_refuse_wide_batch(one_chip, kernel):
    """d=32, n=64 with 64 bands on one chip: preflight refuses it, and
    the compiler runs out of scoped VMEM for either kernel."""
    diags = preflight_basis(64, diameter=32, kpts=KPTS[:1], nbands=64,
                            grid_shape=(1,), backend="pallas")
    assert [dg.code for dg in diags] == ["FFTB118"]
    pack_side = kernel == "dft_pack"
    npk, args = _sphere_args(one_chip, 1, 64, 32, 64, pack_side=pack_side)
    extra = {"npacked": npk} if pack_side else {}
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile(lambda *a: getattr(sphere_pack, kernel)(
            *a, interpret=False, **extra), *args)
