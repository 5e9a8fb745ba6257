"""``required_work`` of each traffic kind against counts made by hand, and
the reference's sphere geometry against the program's."""
from __future__ import annotations

import math

import numpy as np
import pytest

from bench.reference import sphere as ref_sphere
from bench.registry import Registry

REG = Registry()
FIG9 = {"n": 256, "diameter": 128,
        "kpts": [[0.0, 0.0, 0.0], [0.5, 0.5, 0.5]]}


def test_sphere_points_of_the_fig9_width():
    # counted once on the chip's own build of the program (PR 11 probe)
    assert ref_sphere.packed_points(128, (0, 0, 0)).size == 1099136
    assert ref_sphere.packed_points(128, (0.5, 0.5, 0.5)).size == 1097914


@pytest.mark.parametrize("d", [8, 16, 32])
@pytest.mark.parametrize("k", [(0.0, 0.0, 0.0), (0.5, 0.5, 0.5),
                               (0.25, 0.0, 0.5)])
def test_reference_geometry_matches_the_program(d, k):
    from repro.core import kpoint_sphere

    np.testing.assert_array_equal(ref_sphere.packed_points(d, k),
                                  kpoint_sphere(d, k).pack_indices())


def test_sphere_round_trip_work():
    kind = REG.kind("sphere_roundtrip")
    flops, nbytes = kind.required_work(FIG9, REG.traffic("sphere-2x16"))
    cells = 2 ** 24
    assert flops == 2 * 5 * cells * 24                  # 4.03e9
    npk = (1099136 + 1097914) / 2
    assert nbytes == 2 * (npk * 8 + cells * 8)          # 2.86e8
    assert nbytes == 286011856


def test_cube_round_trip_work():
    kind = REG.kind("cube_roundtrip")
    flops, nbytes = kind.required_work(FIG9, {"batch": 16})
    assert flops == 4026531840
    assert nbytes == 4 * 2 ** 24 * 8                    # 537 MB


def test_scf_iteration_work():
    kind = REG.kind("scf_iterations")
    cfg = dict(FIG9, nbands=2, inner_steps=2)
    flops, nbytes = kind.required_work(cfg, {})
    fft = 5 * 2 ** 24 * 24
    npk = (1099136 + 1097914) / 2
    # 2 steps × 2 sweeps × 4 orbitals round trips, 4 density inverses,
    # 2 Hartree cube round trips
    assert flops == pytest.approx(16 * 2 * fft + 4 * fft + 2 * 2 * fft)
    assert nbytes == pytest.approx(16 * 2 * (npk + 2 ** 24) * 8
                                   + 4 * (npk + 2 ** 24) * 8
                                   + 2 * 4 * 2 ** 24 * 8)


def test_roofline_reader_takes_the_larger_bound():
    reader = REG.metric_reader("roofline_share")
    from bench import trace as bt

    tr = bt.Trace([bt.Device("/device:TPU:0", [
        ["jit_bench_inverse(1)", 0.0, 3e6, 1],
        ["jit_bench_forward(2)", 3e6, 1e6, 2]], [])], [])
    logged = []
    info = {"peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
            "work": (2e9, 1e3), "units_per_step": 2, "chips": 1,
            "programs": ("bench_inverse", "bench_forward"),
            "log": logged.append}
    # flops bound 4 ms against 4 ms on the device: 100%
    assert reader.read(tr, info) == pytest.approx(100.0)
    assert "compute-bound" in logged[-1]
    info["work"] = (1.0, 1e6)         # bytes bound 2 ms: 50%
    assert reader.read(tr, info) == pytest.approx(50.0)
    assert "memory-bound" in logged[-1]
    info["peaks"] = None
    assert reader.read(tr, info) is None


def test_work_is_per_orbital_not_per_batch():
    kind = REG.kind("sphere_roundtrip")
    mix = REG.traffic("sphere-2x16")
    assert kind.required_work(dict(FIG9, nbands={"1": 16}), mix) == \
        kind.required_work(dict(FIG9, nbands={"1": 32}), mix)
    assert math.isfinite(kind.required_work(FIG9, {})[0])


# ------------------------------------------------ work of each named layer
SMALL = {"n": 16, "diameter": 8, "kpts": [[0.0, 0.0, 0.0], [0.5, 0.5, 0.5]]}
#: the two spheres' packed points at d = 8, counted by the program's own
#: ``kpoint_sphere`` (``test_reference_geometry_matches_the_program``)
NPK8 = (280, 254)
FFT16 = 5 * 4096 * 12                  # 5·N·log2 N at N = 16³


def test_small_spheres_hold_the_counted_points():
    assert tuple(ref_sphere.packed_points(8, k).size
                 for k in SMALL["kpts"]) == NPK8


def test_sphere_layer_work():
    kind = REG.kind("sphere_roundtrip")
    work = kind.scope_work(SMALL, REG.traffic("sphere-2x16"))
    assert set(work) == {"fftb.line_dft", "fftb.unpack", "fftb.pack"}
    # an orbital (267 points on average): two transforms, each the
    # packed points on one side and the 4096-point cube on the other
    assert work["fftb.line_dft"] == (2 * FFT16, 2 * (267 + 4096) * 8)
    assert work["fftb.unpack"] == (0.0, 2 * 267 * 8)
    assert work["fftb.pack"] == (0.0, 2 * 267 * 8)


def test_cube_layer_work():
    work = REG.kind("cube_roundtrip").scope_work(SMALL, {"batch": 2})
    # one cube: two transforms, each reading and writing 4096 points
    assert work == {"fftb.line_dft": (2 * FFT16, 2 * 2 * 4096 * 8)}


def test_scf_layer_work():
    kind = REG.kind("scf_iterations")
    cfg = dict(SMALL, nbands=2, inner_steps=2)
    work = kind.scope_work(cfg, {})
    packed = 2 * sum(NPK8)             # 2 bands of each k-point: 1068
    # 2 steps × 2 H applies of 4 orbitals: 16 round trips (32 sphere
    # transforms, 16 unpacks, 16 packs); the density: 4 unpacks and 4
    # inverses; two Hartree solves: 4 cube transforms
    assert work["fftb.line_dft"] == (
        (32 + 4 + 4) * FFT16,
        (9 * (packed + 4 * 4096) + 4 * 2 * 4096) * 8)
    assert work["fftb.unpack"] == (0.0, 5 * 2 * packed * 8)
    assert work["fftb.pack"] == (0.0, 4 * 2 * packed * 8)
