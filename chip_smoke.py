#!/usr/bin/env python3
"""Smoke run of FFTB-JAX on one TPU chip, at the paper's own width.

Drives the system's main path through its normal entry points, in one
process, with every input generated from ``--seed``:

1. device     — a TPU must be there; on any other platform exit 2.
2. transform  — the stacked sphere↔cube pair (``make_stacked_planewave_pair``)
                at the Fig. 9 width (n=256, d=128), inverse then forward,
                checked against a NumPy float64 FFT for two bands.
3. scf        — the README demo to convergence against its pinned CPU
                energy, then ``SCF_ITERS`` iterations of the jitted
                full-width SCF; its residual must end below its start.
4. service    — ``TransformService`` at n=256 with d=128 spheres at two
                k-shifts and three tenants, checked against its eager
                oracle.
5. pallas     — a stacked H apply on the fused sphere-pack kernels at the
                largest width preflight FFTB118 admits, against the matmul
                route; the kernels must have run (``DISPATCHES``) and be in
                the compiled program (``tpu_custom_call``).

The last line of standard output is one JSON object
``{"ok": true, "device": {...}}``.  Any failed check raises, and the
script exits non-zero without that line.

``--chips 4`` runs only the distributed phase: the same full-width
transform and a few jitted full-width SCF iterations on the 1D ``(4,)``
fft grid and the 2×2 batch×fft grid, each against a 1-chip grid in the
same process (and the transform against the NumPy reference).

Memory is printed two ways: ``memory_analysis()`` of the chip's own
compile (argument, output, temp and code bytes of one program), and the
runtime's ``peak_bytes_in_use`` — a process-wide high-water mark of the
array buffers it allocated since start, cumulative across phases, which
does not count a program's temporaries.

Run:  python chip_smoke.py [--seed 0] [--chips 1|4]
Band counts are cut from the paper's 256 to what one chip holds; the cut
is printed.  The numbers printed are smoke readings, not benchmarks.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

import jax
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# ---------------------------------------------------------------- sizes
KPTS = ((0.0, 0.0, 0.0), (0.5, 0.5, 0.5))
#: bands per k-point for the full-width transform: 2 k × 16 bands is the
#: largest power of two whose compiled peak (argument + temp bytes of
#: ``memory_analysis()``) stays under ~70% of 16 GB (v5e compile
#: rehearsal: 7.2 GiB at 16, 13.8 GiB at 32)
NB_TRANSFORM = 16
#: bands per k-point for the full-width jitted SCF step, by the same rule
#: (v5e compile rehearsal: 8.5 GiB at 2, 13.4 GiB at 4; on the 4-chip
#: grids 9.0 GiB per chip at 2)
NB_SCF = 2
#: outer iterations of the full-width SCF.  With seed 0 its residual
#: climbs for a dozen iterations before it falls (it converges in 55);
#: 16 end below the start
SCF_ITERS = 16
SCF_INNER_STEPS = 2
#: full-width SCF iterations that --chips 4 compares across grids
SCF4_ITERS = 4
#: paper Fig. 9 bands
NB_PAPER = 256

# ----------------------------------------------------------- tolerances
#: max|y − ref| / max|ref| of an f32 transform against the f64 NumPy FFT
TRANSFORM_RTOL = 1e-4
#: the README demo's converged energy, pinned on the CPU
#: (tests/test_dft.py::test_scf_jit_step_anderson_converges)
DEMO_ENERGY, DEMO_ENERGY_TOL = -1.9197, 5e-3
#: fused Pallas route against the XLA matmul route, same f32 GEMMs
PALLAS_RTOL = 1e-5
#: SCF energies of one run on a 4-chip grid against the 1-chip run
SCF_GRID_RTOL = 1e-4


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_err(got, ref) -> float:
    got = np.asarray(got)
    ref = np.asarray(ref)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def peak_bytes(dev) -> int | None:
    """Process-wide buffer high-water mark; blind to program temps."""
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def program_bytes(mem: dict) -> str:
    return " ".join(f"{k}_bytes={v}" for k, v in mem.items())


def timed_compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def timed_call(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# ------------------------------------------------------------- phase 1
def device_check(chips: int):
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} but {len(devs)} devices",
              file=sys.stderr)
        sys.exit(2)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "unknown"
    say(f"device kind={dev.device_kind} count={len(devs)} "
        f"jax={jax.__version__} jaxlib={importlib.metadata.version('jaxlib')}"
        f" libtpu={libtpu}")
    return devs


# ------------------------------------------------------------- phase 2
def random_blocks(rng, spheres, nb):
    return [(rng.standard_normal((nb, s.npacked))
             + 1j * rng.standard_normal((nb, s.npacked))).astype(np.complex64)
            for s in spheres]


def numpy_inverse(sphere, coeffs, n):
    """f64 reference: the d³ box padded into the corner of the n³ cube."""
    d = sphere.extents[0]
    box = np.zeros(d ** 3, np.complex128)
    box[sphere.pack_indices()] = coeffs
    full = np.zeros((n, n, n), np.complex128)
    full[:d, :d, :d] = box.reshape(d, d, d)
    return np.fft.ifftn(full)


def stacked_input(inv, blocks, nb):
    """Host ``(nk·nb, npacked_max)`` stack, padded lanes zero."""
    out = np.zeros((len(blocks) * nb, inv.npacked_max), np.complex64)
    for k, blk in enumerate(blocks):
        out[k * nb:(k + 1) * nb, :blk.shape[1]] = blk
    return out


def transform_run(grid, n, spheres, nb, blocks, label, dev, **axes):
    """Inverse then forward over the band batch; returns (psi rows, back)."""
    from repro.core import make_stacked_planewave_pair
    from repro.obs.metrics import program_memory

    inv, fwd = make_stacked_planewave_pair(grid, n, spheres, nb, **axes)
    rows = np.array([0, nb])          # band 0 of each k-point

    def roundtrip(c):
        psi = inv.unpack_transform(c)
        return psi[rows], fwd.transform_pack(psi)

    host = stacked_input(inv, blocks, nb)
    compiled, t_compile = timed_compile(roundtrip, host)
    x = jax.device_put(host, compiled.input_shardings[0][0])
    timed_call(compiled, x)                          # warm
    (psi, back), t_steady = timed_call(compiled, x)
    say(f"transform[{label}] grid={grid.shape} n={n} d={spheres[0].extents[0]}"
        f" orbitals={host.shape[0]} compile_s={t_compile:.3f} "
        f"steady_s={t_steady:.4f} {program_bytes(program_memory(compiled))} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    return np.asarray(psi), np.asarray(back), host


def numpy_refs(spheres, blocks, n):
    """f64 inverse of band 0 of each k-point."""
    return [numpy_inverse(s, blocks[k][0], n) for k, s in enumerate(spheres)]


def check_transform(label, psi, back, host, refs):
    errs = [rel_err(psi[k], ref) for k, ref in enumerate(refs)]
    rt = rel_err(back, host)
    say(f"transform[{label}] inverse_vs_numpy_f64 max_rel_err="
        f"{max(errs):.3e} roundtrip max_rel_err={rt:.3e} "
        f"tol={TRANSFORM_RTOL:g}")
    check(max(errs) <= TRANSFORM_RTOL, f"{label}: inverse vs NumPy {errs}")
    check(rt <= TRANSFORM_RTOL, f"{label}: round trip {rt}")


def phase_transform(seed, dev):
    from repro.configs.fftb_paper import CONFIG
    from repro.core import ProcGrid, kpoint_sphere

    n, d = CONFIG.n, CONFIG.diameter
    say(f"cut: bands {NB_PAPER} -> {NB_TRANSFORM} per k-point x "
        f"{len(KPTS)} k-points for the transform, {NB_SCF} per k-point "
        f"for the SCF step (n={n}, d={d} kept)")
    spheres = [kpoint_sphere(d, k) for k in KPTS]
    blocks = random_blocks(np.random.default_rng(seed), spheres,
                           NB_TRANSFORM)
    psi, back, host = transform_run(ProcGrid.create([1]), n, spheres,
                                    NB_TRANSFORM, blocks, "1chip", dev)
    check(np.isfinite(psi).all() and np.isfinite(back).all(),
          "transform: non-finite output")
    check_transform("1chip", psi, back, host,
                    numpy_refs(spheres, blocks, n))


# ------------------------------------------------------------- phase 3
def scf_run(label, cfg, grid):
    from repro.dft import run_scf

    def report(it, energy, resid):
        say(f"scf[{label}] iter={it} energy={energy:.8f} "
            f"residual={resid:.6e}")

    res = run_scf(cfg, grid=grid, callback=report)
    secs = [r["seconds"] for r in res.iteration_records]
    secs[0] -= res.compile_seconds       # the first iteration compiles
    say(f"scf[{label}] grid={res.grid_shape} n={cfg.n} nbands={cfg.nbands}"
        f" nk={len(cfg.kpts)} iterations={res.iterations} "
        f"converged={res.converged} energy={res.energy:.8f} "
        f"compile_s={res.compile_seconds:.3f} "
        f"iter_s=[{','.join(f'{t:.3f}' for t in secs)}] "
        f"{program_bytes(res.step_memory)}")
    check(np.all(np.isfinite(res.energies)), f"{label}: non-finite energy")
    return res


def scf_config(seed, n, d, nbands, iters=SCF_ITERS, **axes):
    from repro.dft import SCFConfig

    return SCFConfig(n=n, diameter=d, nbands=nbands, kpts=KPTS,
                     max_iter=iters, inner_steps=SCF_INNER_STEPS,
                     stack_k=True, jit_step=True, seed=seed, **axes)


def phase_scf(seed, dev):
    from repro.core import ProcGrid
    from repro.dft import SCFConfig

    g1 = ProcGrid.create([1])
    demo = scf_run("demo", SCFConfig(n=16, nbands=4, kpts=KPTS,
                                     max_iter=100, stack_k=True,
                                     jit_step=True), g1)
    say(f"scf[demo] energy_vs_cpu_pinned |dE|="
        f"{abs(demo.energy - DEMO_ENERGY):.3e} tol={DEMO_ENERGY_TOL:g}")
    check(demo.converged, "demo SCF did not converge")
    check(abs(demo.energy - DEMO_ENERGY) < DEMO_ENERGY_TOL,
          f"demo energy {demo.energy}")

    from repro.configs.fftb_paper import CONFIG
    full = scf_run("full", scf_config(seed, CONFIG.n, CONFIG.diameter,
                                      NB_SCF), g1)
    say(f"scf[full] peak_bytes_in_use={peak_bytes(dev)}")
    check(full.residuals[-1] < full.residuals[0],
          f"full-width residual did not fall: {full.residuals}")


# ------------------------------------------------------------- phase 4
def phase_service(seed, dev):
    from repro.configs.fftb_paper import CONFIG
    from repro.core import ProcGrid, kpoint_sphere
    from repro.serve import TransformService
    from repro.serve.transform_service import EAGER_RTOL

    n, d = CONFIG.n, CONFIG.diameter
    rng = np.random.default_rng(seed + 1)
    sph = [kpoint_sphere(d, k) for k in KPTS]
    veff = rng.standard_normal((n,) * 3).astype(np.float32)
    work = [("alpha", sph[0], veff), ("beta", sph[1], None),
            ("gamma", sph[0], None), ("alpha", sph[1], veff)]
    svc = TransformService(ProcGrid.create([1], ["dft_f"]), n,
                           padding_budget=0.5, max_rows=8, warm_async=False)
    reqs = [(t, random_blocks(rng, [s], 2)[0], s, v) for t, s, v in work]
    t0 = time.perf_counter()
    handles = [svc.submit(t, c, s, v_eff=v) for t, c, s, v in reqs]
    svc.run_until_idle(timeout=600)
    outs = [h.result(60) for h in handles]
    t_serve = time.perf_counter() - t0
    errs = [rel_err(o, svc.eager_apply(c, s, v))
            for o, (_, c, s, v) in zip(outs, reqs)]
    m = svc.metrics.summary()
    say(f"service n={n} d={d} requests={m['requests']} "
        f"dispatches={m['dispatches']} coalesced={m['coalesced_dispatches']}"
        f" serve_s={t_serve:.3f} max_rel_err_vs_eager={max(errs):.3e} "
        f"tol={EAGER_RTOL:g} peak_bytes_in_use={peak_bytes(dev)} "
        f"(a dispatch runs several programs: no one memory_analysis)")
    say("service metrics " + json.dumps(m, sort_keys=True, default=str))
    check(m["requests"] == len(work), "service: not every request answered")
    check(all(np.isfinite(o).all() for o in outs), "service: non-finite")
    check(max(errs) <= EAGER_RTOL, f"service vs eager: {errs}")


# ------------------------------------------------------------- phase 5
def largest_pallas_width():
    """(n, d, nbands) — the widest cube, then the most bands, FFTB118
    admits for the stacked fused route on one chip."""
    from repro.check.preflight import preflight_basis

    for d in (128, 64, 32):
        for nb in (16, 8, 4, 2, 1):
            if not preflight_basis(2 * d, diameter=d, kpts=KPTS, nbands=nb,
                                   grid_shape=(1,), backend="pallas"):
                return 2 * d, d, nb
    raise AssertionError("FFTB118 admits no pallas width")


def phase_pallas(seed, dev):
    import jax.numpy as jnp
    from repro.core import ProcGrid
    from repro.dft import PlaneWaveBasis
    from repro.dft.hamiltonian import apply_hamiltonian_padded
    from repro.kernels import sphere_pack
    from repro.obs.metrics import program_memory

    n, d, nb = largest_pallas_width()
    g1 = ProcGrid.create([1])
    bases = {b: PlaneWaveBasis(n, diameter=d, kpts=KPTS, nbands=nb,
                               grid=g1, backend=b)
             for b in ("pallas", "matmul")}
    rng = np.random.default_rng(seed + 2)
    inv, _ = bases["pallas"].stacked_hamiltonian_plans()
    blocks = random_blocks(rng, bases["pallas"].spheres, nb)
    c_pad = jnp.asarray(stacked_input(inv, blocks, nb).reshape(
        len(KPTS), nb, inv.npacked_max))
    v = jnp.asarray(rng.standard_normal((n,) * 3).astype(np.float32))

    out = {}
    d0 = dict(sphere_pack.DISPATCHES)
    for name, basis in bases.items():
        def h_apply(c, v, basis=basis):
            return apply_hamiltonian_padded(basis, c, v)
        compiled, t_compile = timed_compile(h_apply, c_pad, v)
        custom = "tpu_custom_call" in compiled.as_text()
        timed_call(compiled, c_pad, v)               # warm
        out[name], t_steady = timed_call(compiled, c_pad, v)
        say(f"pallas[{name}] n={n} d={d} nk={len(KPTS)} nbands={nb} "
            f"compile_s={t_compile:.3f} steady_s={t_steady:.4f} "
            f"tpu_custom_call={custom} {program_bytes(program_memory(compiled))} "
            f"peak_bytes_in_use={peak_bytes(dev)}")
        if name == "pallas":
            check(custom, "fused route: no tpu_custom_call in the HLO")
    fired = {k: sphere_pack.DISPATCHES[k] - d0[k] for k in d0}
    err = rel_err(out["pallas"], out["matmul"])
    say(f"pallas dispatches={fired} max_rel_err_vs_matmul={err:.3e} "
        f"tol={PALLAS_RTOL:g}")
    check(all(v > 0 for v in fired.values()),
          f"fused kernels did not dispatch: {fired}")
    check(err <= PALLAS_RTOL, f"pallas vs matmul: {err}")


# ------------------------------------------------------------ four chips
def phase_four_chips(seed, devs):
    from repro.configs.fftb_paper import CONFIG
    from repro.core import ProcGrid, kpoint_sphere
    from repro.core.compat import mesh_from_devices
    from repro.sharding.grids import DFT_AXES_1D, DFT_AXES_2D

    n, d = CONFIG.n, CONFIG.diameter
    one = ProcGrid(mesh_from_devices(np.array(devs[:1]), DFT_AXES_1D),
                   DFT_AXES_1D)
    grids = {"1chip": (one, {}),
             "fft4": (ProcGrid.create([4], DFT_AXES_1D),
                      {"batch_axes": (), "fft_axes": (0,)}),
             "batch2xfft2": (ProcGrid.create([2, 2], DFT_AXES_2D),
                             {"batch_axes": (0,), "fft_axes": (1,)})}
    say(f"cut: bands {NB_PAPER} -> {NB_TRANSFORM} per k-point x "
        f"{len(KPTS)} k-points for the transform, {NB_SCF} per k-point "
        f"for the SCF step (n={n}, d={d} kept)")
    spheres = [kpoint_sphere(d, k) for k in KPTS]
    blocks = random_blocks(np.random.default_rng(seed), spheres,
                           NB_TRANSFORM)
    refs = numpy_refs(spheres, blocks, n)
    ref = {}
    for label, (grid, axes) in grids.items():
        psi, back, host = transform_run(grid, n, spheres, NB_TRANSFORM,
                                        blocks, label, devs[0], **axes)
        check_transform(label, psi, back, host, refs)
        if label == "1chip":
            ref = {"psi": psi, "back": back}
            continue
        e_psi, e_back = (rel_err(psi, ref["psi"]),
                         rel_err(back, ref["back"]))
        say(f"transform[{label}] vs_1chip psi max_rel_err={e_psi:.3e} "
            f"back max_rel_err={e_back:.3e} tol={TRANSFORM_RTOL:g}")
        check(max(e_psi, e_back) <= TRANSFORM_RTOL, f"{label} vs 1 chip")

    energies = {}
    for label, (grid, axes) in grids.items():
        res = scf_run(label, scf_config(seed, n, d, NB_SCF,
                                        iters=SCF4_ITERS, **axes), grid)
        energies[label] = np.asarray(res.energies)
        if label == "1chip":
            continue
        e = float(np.abs(energies[label] - energies["1chip"]).max()
                  / np.abs(energies["1chip"]).max())
        say(f"scf[{label}] vs_1chip energies max_rel_err={e:.3e} "
            f"tol={SCF_GRID_RTOL:g}")
        check(e <= SCF_GRID_RTOL, f"{label} SCF vs 1 chip: {e}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    devs = device_check(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    say(f"compile_cache dir={enable_compile_cache()}")
    t0 = time.perf_counter()
    phases = ([phase_four_chips] if args.chips == 4 else
              [phase_transform, phase_scf, phase_service, phase_pallas])
    for phase in phases:
        t = time.perf_counter()
        phase(args.seed, devs if args.chips == 4 else devs[0])
        say(f"phase {phase.__name__[6:]} ok seconds="
            f"{time.perf_counter() - t:.1f}")
    say(f"total seconds={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
