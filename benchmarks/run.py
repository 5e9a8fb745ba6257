"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * table1_*            — executable feature matrix (capability probes)
  * local_fft_*         — local line-DFT backends (measured, CPU)
  * pw_staged/padded_*  — staged-pad vs full-pad plane-wave (measured, CPU)
  * fig9_*              — strong-scaling model for the paper's five Fig. 9
                          variants on TPU-v5e constants, fed by FftPlan's
                          comm/flop model at each processor count
  * train/decode_step   — reduced-config step microbenches (measured, CPU)

``derived`` column: modeled ms for fig9 rows, speedup/ratios elsewhere.
The SCF scenarios (``scf`` on a 1D fft grid, ``scf-2d`` pipelined on a
batch×fft 2D grid, ``scf-stacked`` with the batched stacked band-update
engine on the same 2D grid, ``scf-jit`` adding the fused jit-compiled SCF
step, ``scf-3d`` on a batch×fft×fft *pencil* grid with segmented ragged
stacking — each recording its grid shape, padding fraction, segment
count, band-update route and per-iteration wall time) additionally write
machine-readable schema-5 ``BENCH_scf.json`` (transforms/s, iterations
to convergence, plan-cache hit rate, per-segment realized padding, plus
a per-scenario ``metrics`` delta from the ``repro.obs`` registry so
regressions attribute to a phase) so the perf trajectory can be tracked
across commits; CI's bench-trajectory job uploads it and gates
regressions against ``benchmarks/baseline.json`` via
``benchmarks/compare.py`` (schema-3/4 baselines still load).  The
``band_update`` field rides the record so the gate catches a silent
fallback from the stacked engine to the per-k path; the stacked/jit/3d
scenarios additionally hard-fail here if the route they exist to measure
did not engage.  The JSON is written atomically (temp file + rename) so
an interrupted run can't leave a truncated artifact.

``--scenarios gate`` resolves the scenario list from the committed
baseline (``--baseline``), so the CI gate jobs and the baseline-drift
automation share one source of truth for what is gated — adding a
scenario to the baseline is what starts gating it, with no workflow
edits.  ``--merge`` folds this run's records into an existing
``--json-out`` instead of replacing it: CI's bench-trajectory job runs
the 4-device scenarios first, then merges the 8-device ``scf-3d`` record
into the same BENCH_scf.json before a single gate invocation (the gate
fails on baseline scenarios missing from the current run, so the merged
artifact is what gets compared).

Run: PYTHONPATH=src python -m benchmarks.run [--quick] [--json-out PATH]
         [--scenarios scf,scf-2d,scf-stacked,scf-jit,scf-pallas,scf-3d
          | gate]
         [--merge] [--baseline PATH] [--trace-out PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

#: selectable benchmark scenarios (--scenarios comma list, default all;
#: the literal ``gate`` resolves to whatever the baseline gates)
SCENARIOS = ("table1", "plan_cache", "local_fft", "planewave", "fig9",
             "serve-transform",
             "scf", "scf-2d", "scf-stacked", "scf-jit", "scf-3d",
             "scf-pallas", "steps")


def _timeit(fn, *args, warmup=2, iters=5):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6          # µs


def bench_table1(rows):
    """Paper Table 1 — capabilities, as executable probes."""
    import jax
    import jax.numpy as jnp
    from repro.core import (ProcGrid, SphereDomain, Domain, fftb,
                            make_planewave_pair)
    g1 = ProcGrid.create([1])
    t0 = time.perf_counter()
    dom = Domain((0, 0, 0), (15, 15, 15))
    fx = fftb("x{0} y z -> X Y Z{0}", domains=dom, grid=g1)
    # block before stopping the clock — jax dispatch is asynchronous, and
    # an un-drained call would time only the dispatch (see
    # repro.obs.trace.timed_call for the canonical pattern)
    jax.block_until_ready(fx(jnp.ones((16, 16, 16), jnp.complex64)))
    rows.append(("table1_ctoc_cuboid", (time.perf_counter() - t0) * 1e6, 1))
    t0 = time.perf_counter()
    sph = SphereDomain.from_diameter(8)
    inv, fwd = make_planewave_pair(g1, 16, sph, 4)
    jax.block_until_ready(inv(jnp.ones((4, 8, 8, 8), jnp.complex64)))
    rows.append(("table1_sphere_batched", (time.perf_counter() - t0) * 1e6,
                 1))
    for nd in (1, 2, 3):
        g = ProcGrid.create_abstract([1] * nd)
        rows.append((f"table1_grid_{nd}d", 0.0, g.ndim))


def bench_plan_cache(rows):
    """Plan build cost vs cached lookup — the serving-path win."""
    from repro.core import Domain, ProcGrid, fftb, PlanCache
    g = ProcGrid.create_abstract([8])
    dom = Domain((0, 0, 0), (63, 63, 63))
    cache = PlanCache()
    spec = "b x{0} y z -> b X Y Z{0}"
    b = Domain((0,), (255,))
    t0 = time.perf_counter()
    fftb.plan_for(spec, domains=(b, dom), grid=g, cache=cache)
    build_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    iters = 100
    for _ in range(iters):
        fftb.plan_for(spec, domains=(b, dom), grid=g, cache=cache)
    hit_us = (time.perf_counter() - t0) * 1e6 / iters
    rows.append(("plan_build_cold", build_us, 1))
    rows.append(("plan_cache_hit", hit_us,
                 round(build_us / max(hit_us, 1e-3), 1)))   # speedup ×


def bench_local_fft(rows, quick=False):
    import jax
    import jax.numpy as jnp
    from repro.core.local_fft import local_dft
    rng = np.random.default_rng(0)
    sizes = [64, 128] if quick else [64, 128, 256]
    batch = 512
    for n in sizes:
        x = jnp.asarray((rng.standard_normal((batch, n))
                         + 1j * rng.standard_normal((batch, n))
                         ).astype(np.complex64))
        for backend in ("jnp", "matmul"):
            f = jax.jit(lambda a, b=backend: local_dft(a, -1, backend=b))
            us = _timeit(f, x)
            # derived: GFLOP/s using the 8·n² matmul-form flop count
            gflops = 8 * n * n * batch / (us * 1e-6) / 1e9
            rows.append((f"local_fft_{backend}_n{n}", us, round(gflops, 2)))
        # rectangular (pad-fused) form — the plane-wave stage shape
        f = jax.jit(lambda a, m=2 * n: local_dft(a, -1, m, backend="matmul"))
        us = _timeit(f, x)
        rows.append((f"local_fft_rect_n{n}to{2*n}", us,
                     round(8 * 2 * n * n * batch / (us * 1e-6) / 1e9, 2)))


def bench_planewave(rows, quick=False):
    """§2.2/Fig. 2-3: staged-pad vs pad-everything-first, measured."""
    import jax
    import jax.numpy as jnp
    from repro.core import (Domain, DistTensor, FftPlan, ProcGrid,
                            make_planewave_pair, sphere_for_cutoff)
    g = ProcGrid.create([1])
    n = 32 if quick else 64
    sph = sphere_for_cutoff(n)
    d = sph.extents[0]
    nb = 4
    inv, _ = make_planewave_pair(g, n, sph, nb)
    rng = np.random.default_rng(1)
    cube = jnp.asarray((rng.standard_normal((nb, d, d, d))
                        + 1j * rng.standard_normal((nb, d, d, d))
                        ).astype(np.complex64))
    us_staged = _timeit(inv.plan._sharded_fn, cube)
    b = Domain((0,), (nb - 1,))
    cdom = Domain((0, 0, 0), (n - 1, n - 1, n - 1))
    ti = DistTensor.create((b, cdom), "b x{0} y z", g)
    to = DistTensor.create((b, cdom), "B X Y Z{0}", g)
    padded = FftPlan(ti, to, [("x", "X"), ("y", "Y"), ("z", "Z")],
                     inverse=True)
    full = jnp.zeros((nb, n, n, n), jnp.complex64)
    full = full.at[:, :d, :d, :d].set(cube)
    us_padded = _timeit(padded._sharded_fn, full)
    rows.append((f"pw_staged_n{n}", us_staged,
                 round(inv.flop_count() / 1e6, 1)))
    rows.append((f"pw_padded_n{n}", us_padded,
                 round(padded.flop_count() / 1e6, 1)))
    rows.append((f"pw_speedup_n{n}", 0.0, round(us_padded / us_staged, 2)))
    rows.append((f"pw_data_ratio_n{n}", 0.0,
                 round(n ** 3 / sph.npacked, 2)))   # paper's ~16× claim


# ---------------------------------------------------------------- Fig. 9
_PEAK = 197e12          # bf16 FLOP/s per chip (TPU v5e)
_LINK = 50e9            # B/s per ICI link
_LAT = 5e-6             # per-collective latency (s)
_EFF = 0.35             # sustained fraction of peak for line DFTs
_HALF_BW = 65536        # message size reaching half link bandwidth (B)


def _fig9_time(plan, nb_msgs_scale=1):
    """LogGP-style: per-peer message size below ~64 KiB degrades effective
    bandwidth — exactly why the paper's unbatched variants collapse beyond
    64 GPUs while batched ones keep scaling (its central Fig. 9 claim)."""
    comp = plan.flop_count() / plan.grid.nprocs / (_PEAK * _EFF)
    comm = 0.0
    for st in plan.comm_stats():
        msg = st["bytes_per_device"] / max(st["procs"] - 1, 1)
        bw = _LINK * msg / (msg + _HALF_BW)
        comm += st["bytes_per_device"] / bw + _LAT * nb_msgs_scale
    return (comp + comm) * 1e3                                # ms


def bench_fig9(rows):
    """Paper Fig. 9: 256³ FFT, batch 256, sphere d=128 — five variants
    across processor counts, priced by the plan's comm/flop model."""
    from repro.core import (Domain, DistTensor, FftPlan, ProcGrid,
                            SphereDomain, make_planewave_pair)
    n, nb, d = 256, 256, 128
    for P in (4, 8, 16, 32, 64, 128, 256, 512, 1024):
        b = Domain((0,), (nb - 1,))
        cube = Domain((0, 0, 0), (n - 1, n - 1, n - 1))
        sph = SphereDomain.from_diameter(d)

        # --- 1D grid, batched (dark blue) ---
        if P <= n:
            g = ProcGrid.create_abstract([P])
            ti = DistTensor.create((b, cube), "b x{0} y z", g)
            to = DistTensor.create((b, cube), "B X Y Z{0}", g)
            plan = FftPlan(ti, to, [("x", "X"), ("y", "Y"), ("z", "Z")])
            rows.append((f"fig9_1d_batched_p{P}", 0.0,
                         round(_fig9_time(plan), 3)))
            # --- 1D grid, unbatched (light blue): 256 separate small
            # transforms → per-message latency dominates at scale
            ti1 = DistTensor.create(cube, "x{0} y z", g)
            to1 = DistTensor.create(cube, "X Y Z{0}", g)
            p1 = FftPlan(ti1, to1, [("x", "X"), ("y", "Y"), ("z", "Z")])
            t1 = _fig9_time(p1) * nb + _LAT * nb * 1e3
            rows.append((f"fig9_1d_unbatched_p{P}", 0.0, round(t1, 3)))

        # --- 2D grid, batched (dark orange) ---
        if P >= 4:
            good = 1
            px = 1
            while px * px <= P:
                if P % px == 0 and (P // px) <= n and px <= n:
                    good = px
                px += 1
            g2 = ProcGrid.create_abstract([good, P // good])
            ti2 = DistTensor.create((b, cube), "b x{0} y{1} z", g2)
            to2 = DistTensor.create((b, cube), "B X Y{0} Z{1}", g2)
            plan2 = FftPlan(ti2, to2, [("x", "X"), ("y", "Y"), ("z", "Z")])
            rows.append((f"fig9_2d_batched_p{P}", 0.0,
                         round(_fig9_time(plan2), 3)))

        # --- plane-wave staged (red) ---
        if P <= d:
            gpw = ProcGrid.create_abstract([P])
            inv, _ = make_planewave_pair(gpw, n, sph, nb)
            rows.append((f"fig9_planewave_p{P}", 0.0,
                         round(_fig9_time(inv.plan), 3)))
        else:                       # parallelize batch beyond the dims
            fft_p = d
            bat_p = P // d
            if nb % bat_p == 0:
                gpw = ProcGrid.create_abstract([bat_p, fft_p])
                inv, _ = make_planewave_pair(gpw, n, sph, nb,
                                             batch_axes=(0,),
                                             fft_axes=(1,))
                rows.append((f"fig9_planewave_p{P}", 0.0,
                             round(_fig9_time(inv.plan), 3)))


def bench_scf(rows, quick=False, grid_shape=None, tag="scf",
              stack_k=None, jit_step=False, segment_padding=None,
              backend=None):
    """repro.dft SCF scenario — the paper's end-to-end workload.

    Two k-points (two distinct sphere plans) + the full-cube Hartree pair,
    mixing-driven SCF, on a 1D fft-only grid (``tag='scf'``), a 2D
    batch×fft grid (``tag='scf-2d'``, grid_shape e.g. (2, 2) — bands shard
    the batch axis), or a 3D batch×fft×fft pencil grid (``tag='scf-3d'``,
    grid_shape e.g. (2, 2, 2) — two decomposed fft axes).  ``stack_k``
    pins the H-sweep route: False keeps the pipelined per-k dispatch (so
    ``scf-2d`` stays comparable across commits), True rides the ragged
    k-stacked batch and the batched band-update engine (``scf-stacked``);
    ``jit_step`` additionally fuses each outer iteration into one
    jit-compiled step (``scf-jit``); ``segment_padding`` caps per-segment
    realized padding so the stacked batch splits into segments instead of
    padding every k to the global max (``scf-3d``); ``backend`` pins the
    line-DFT backend — ``"pallas"`` routes the Hamiltonian hot path
    through the fused sphere-pack kernels (``scf-pallas``), and the
    *resolved* backend lands in the scenario record so the gate catches a
    silent downgrade.  Returns the
    machine-readable schema-5 record merged into BENCH_scf.json;
    ``grid_shape`` is what the trajectory gate keys scenarios by,
    ``band_update``/``segments`` let it catch a silent fallback to the
    per-k path or a changed segmentation, and ``seconds_per_iteration``
    tracks per-sweep wall time next to ``transforms_per_s``.
    """
    import jax
    from repro.core import ProcGrid, global_plan_cache
    from repro.dft import SCFConfig, run_scf
    from repro.sharding.grids import DFT_AXES_1D, DFT_AXES_2D, DFT_AXES_3D
    if grid_shape is None:
        grid_shape = (jax.device_count(),)
    grid_shape = tuple(grid_shape)
    names = {1: DFT_AXES_1D, 2: DFT_AXES_2D, 3: DFT_AXES_3D}[len(grid_shape)]
    grid = ProcGrid.create(list(grid_shape), list(names))
    cfg = SCFConfig(n=16, nbands=4, kpts=((0, 0, 0), (0.5, 0.5, 0.5)),
                    max_iter=20 if quick else 50,
                    e_tol=1e-4 if quick else 1e-5,
                    r_tol=1e-3 if quick else 1e-4,
                    stack_k=stack_k, jit_step=jit_step,
                    segment_padding=segment_padding, backend=backend)
    global_plan_cache().clear()
    res = run_scf(cfg, grid=grid)
    c = res.cache_stats
    lookups = c["hits"] + c["misses"]
    hit_rate = c["hits"] / max(lookups, 1)
    label = tag.replace("-", "_")
    rows.append((f"{label}_outer_iteration",
                 res.seconds_per_iteration * 1e6,
                 res.iterations))
    rows.append((f"{label}_transforms_per_s", 0.0,
                 round(res.transforms_per_s, 1)))
    rows.append((f"{label}_cache_hit_rate", 0.0, round(hit_rate, 4)))
    return {
        "scenario": {
            "n": cfg.n, "nbands": cfg.nbands, "kpts": list(cfg.kpts),
            "max_iter": cfg.max_iter, "e_tol": cfg.e_tol,
            "devices": jax.device_count(), "quick": bool(quick),
            "jit_step": bool(cfg.jit_step),
            "segment_padding": segment_padding,
            "backend": res.backend,
        },
        "grid_shape": list(grid_shape),
        "grid_rank": len(grid_shape),
        "pipeline": bool(cfg.pipeline),
        "stacked": bool(res.stacked),
        "band_update": res.band_update,
        "jitted": bool(res.jitted),
        "padding_fraction": round(res.padding_fraction, 4),
        "segments": res.segments,
        "segment_padding_fractions": [
            round(f, 4) for f in res.segment_padding_fractions],
        "converged": bool(res.converged),
        "scf_iterations": res.iterations,
        "total_energy": res.energy,
        "transforms": res.transforms,
        "transforms_unit": "per-band 3D transforms (plans batch bands)",
        "transforms_per_s": round(res.transforms_per_s, 2),
        "seconds": round(res.seconds, 3),
        "seconds_per_iteration": round(res.seconds_per_iteration, 4),
        "plan_cache": {"hits": c["hits"], "misses": c["misses"],
                       "hit_rate": round(hit_rate, 4)},
    }


def bench_serve_transform(rows, quick=False):
    """Transform-service scenario: a mixed-tenant trace, coalesced.

    Three tenants replay a fixed trace over three sphere shapes (two
    cutoffs × two k-shifts) in waves of 8 against one ``TransformService``
    on an fft-only grid sized to the device count.  Plans warm on a
    throwaway replay first; the measured window then records sustained
    requests/s, per-request latency percentiles, realized padding and
    plan-cache behaviour — the numbers the schema-4 gate checks
    (``requests_per_s`` higher-is-better, ``latency_p99_ms``
    lower-is-better, next to the universal ``transforms_per_s``).
    ``converged`` here means the run was healthy: every request resolved,
    no deadline/dispatch errors.
    """
    import jax
    from repro.core import ProcGrid, global_plan_cache, kpoint_sphere
    from repro.serve import TransformService

    n, d = 16, 8
    padding_budget, max_rows = 0.5, 8
    n_requests = 24 if quick else 96
    grid_shape = (jax.device_count(),)
    grid = ProcGrid.create(list(grid_shape), ["dft_f"])
    global_plan_cache().clear()
    svc = TransformService(grid, n, padding_budget=padding_budget,
                           max_rows=max_rows, warm_async=False)

    # the small-cutoff tenant needs a diameter the fft axis can shard
    d_small = next(c for c in (6, 4, 8) if c % jax.device_count() == 0)
    spheres = [kpoint_sphere(d), kpoint_sphere(d, (0.5, 0.5, 0.5)),
               kpoint_sphere(d_small)]
    rng = np.random.default_rng(0)
    veff = rng.standard_normal((n,) * 3).astype(np.float32)

    def request(i):
        tenant = ("alpha", "beta", "gamma")[i % 3]
        sphere = spheres[i % 3]
        nbands = (2, 2, 1)[i % 3]
        c = (rng.standard_normal((nbands, sphere.npacked))
             + 1j * rng.standard_normal((nbands, sphere.npacked))
             ).astype(np.complex64)
        return tenant, c, sphere, (veff if i % 2 == 0 else None)

    trace = [request(i) for i in range(n_requests)]

    def replay():
        for i in range(0, len(trace), 8):
            for tenant, c, sphere, v in trace[i:i + 8]:
                svc.submit(tenant, c, sphere, v_eff=v)
            svc.run_until_idle()

    replay()                      # warm: plans built, executors traced
    svc.metrics.reset()
    replay()                      # measured window
    m = svc.metrics.summary()

    healthy = m["requests"] == n_requests and not m["errors"]
    rows.append(("serve_requests_per_s", 0.0, m["requests_per_s"]))
    rows.append(("serve_latency_p99_ms", 0.0, m["latency_p99_ms"]))
    rows.append(("serve_padding_fraction", 0.0,
                 m["padding_fraction_mean"]))
    return {
        "scenario": {
            "n": n, "d": d, "d_small": d_small,
            "tenants": 3, "requests": n_requests,
            "padding_budget": padding_budget, "max_rows": max_rows,
            "devices": jax.device_count(), "quick": bool(quick),
        },
        "grid_shape": list(grid_shape),
        "pipeline": False,
        "stacked": True,
        "band_update": "coalesced",
        "converged": healthy,
        "requests": m["requests"],
        "requests_per_s": m["requests_per_s"],
        "transforms": m["transforms"],
        "transforms_unit": "per-band sphere<->cube round trips",
        "transforms_per_s": m["transforms_per_s"],
        "latency_p50_ms": m["latency_p50_ms"],
        "latency_p99_ms": m["latency_p99_ms"],
        "dispatches": m["dispatches"],
        "coalesced_dispatches": m["coalesced_dispatches"],
        "padding_fraction": m["padding_fraction_mean"],
        "plan_cache": m["plan_cache"],
        "per_tenant": m["per_tenant"],
    }


def bench_steps(rows):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models.model_zoo import build
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import init_opt_state, make_train_step
    cfg = get_config("tinyllama-1.1b").reduced()
    bundle = build(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)),
                                   jnp.int32)}
    batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
    step = make_train_step(bundle, AdamWConfig(), donate=False)
    opt = init_opt_state(params)
    us = _timeit(lambda: step(params, opt, batch)[2]["loss"])
    tokens = 4 * 64
    rows.append(("train_step_reduced", us,
                 round(tokens / (us * 1e-6), 0)))       # tokens/s
    cache = bundle.init_cache(4, 128, jnp.float32)
    lengths = jnp.full((4,), 64, jnp.int32)
    dec = jax.jit(bundle.decode)
    tok = jnp.ones((4, 1), jnp.int32)
    us = _timeit(lambda: dec(params, tok, cache, lengths)[0])
    rows.append(("decode_step_reduced", us, round(4 / (us * 1e-6), 0)))


def _metrics_window(fn):
    """Run a scenario, embedding the obs-registry delta in its record.

    ``record["metrics"]`` is ``diff_snapshot`` over the window the
    scenario ran in — counter deltas (fftb executions, cache builds,
    per-k linalg calls) that let ``compare.py`` attribute a regression
    to a phase rather than just flag the end-to-end number.
    """
    from repro.obs.metrics import diff_snapshot, global_metrics
    before = global_metrics().snapshot()
    record = fn()
    record["metrics"] = diff_snapshot(before, global_metrics().snapshot())
    return record


def atomic_json_dump(record, path: str) -> None:
    """Write JSON via a temp file + atomic rename.

    An interrupted benchmark run (CI timeout, OOM-kill) must not leave a
    truncated ``BENCH_scf.json`` behind — the artifact either has the old
    complete contents or the new complete contents, never half of one.
    """
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".bench-", suffix=".json")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


#: the fixed SCF scenario shape (bench_scf's SCFConfig) — the 2D split
#: must divide these or PlaneWaveBasis rejects the grid
SCF_NBANDS = 4
SCF_DIAMETER = 8
SCF_NK = 2


def scf_2d_grid_shape(ndevices: int) -> tuple[int, int] | None:
    """(batch, fft) split for the scf-2d scenario, None when infeasible.

    Delegates to ``repro.sharding.grids.choose_dft_grid_shape`` — the same
    policy ``--grid auto`` gives users — so the benchmark measures a grid
    the product code would actually pick.  None (skip the scenario, don't
    abort the run) when the chooser stays 1D: fewer than 4 devices, or no
    split dividing the scenario's band count / sphere diameter.
    """
    from repro.sharding.grids import choose_dft_grid_shape
    if ndevices < 4:
        return None
    shape = choose_dft_grid_shape(ndevices, nbands=SCF_NBANDS,
                                  diameter=SCF_DIAMETER, nk=SCF_NK)
    return shape if len(shape) == 2 else None


def scf_stacked_grid_shape(ndevices: int) -> tuple[int, int] | None:
    """The scf-2d split, kept only when the k-stacked batch shards evenly.

    ``basis.stacks_k`` needs the batch factor to carry whole k-points
    (``nk | pb``; the other stacks_k condition, pb | nk·nbands, already
    follows from the chooser's pb | nbands requirement) — otherwise the
    scenario would silently measure the pipelined fallback, so skip it.
    """
    shape = scf_2d_grid_shape(ndevices)
    if shape is None:
        return None
    if shape[0] % SCF_NK:
        return None
    return shape


#: scf-3d's per-segment padding budget.  The scenario's two d=8 spheres
#: pack 280 and 254 coefficients — stacking both in one segment realizes
#: ~4.6% padding, so a 2% budget deterministically splits them into two
#: per-k segments (each realizing 0%), exercising the segmented route
#: end to end.  With the pencil grid's batch factor pb=2, singleton
#: segments still stack (pb % 1 == 0 and 1·nbands % pb == 0).
SCF_SEGMENT_PADDING = 0.02


def scf_3d_grid_shape(ndevices: int) -> tuple[int, int, int] | None:
    """(batch, fft, fft) pencil split for scf-3d, None when infeasible.

    Same chooser as the other grid pickers; the pencil tier engages from
    8 devices for the scenario shape (nbands=4, d=8 → (2, 2, 2)).  None
    when the chooser stays 1D/2D — fewer than 8 devices, or no per-axis
    fft split within the chooser's max-fft-fraction guard.
    """
    from repro.sharding.grids import choose_dft_grid_shape
    if ndevices < 8:
        return None
    shape = choose_dft_grid_shape(ndevices, nbands=SCF_NBANDS,
                                  diameter=SCF_DIAMETER, nk=SCF_NK)
    return shape if len(shape) == 3 else None


def require_stacked_route(record: dict, tag: str) -> dict:
    """Hard-fail when a stacked-route scenario fell back to per-k.

    ``scf-stacked``/``scf-jit`` exist to measure the batched band-update
    engine; a record that quietly took the per-k path would be compared
    against stacked baselines and read as a perf cliff (or mask one).
    The gate also rejects such records via the ``band_update`` config
    key, but the run itself should refuse to emit them.
    """
    if record.get("band_update") != "stacked":
        raise SystemExit(
            f"{tag}: band-update route was {record.get('band_update')!r}, "
            "expected 'stacked' — the scenario's grid no longer satisfies "
            "basis.stacks_k; fix the grid choice rather than benchmarking "
            "the fallback under a stacked label")
    return record


def require_backend(record: dict, tag: str, backend: str) -> dict:
    """Hard-fail when a backend-pinned scenario silently ran another route.

    ``scf-pallas`` exists to measure the fused sphere-pack kernels; its
    record must carry the requested backend *and*, for "pallas", show
    fused kernel dispatches in the scenario's metrics window — a record
    whose H sweeps quietly composed unpack/plan/pack would be compared
    against fused baselines and mask (or fake) a perf cliff.
    """
    got = record.get("scenario", {}).get("backend")
    if got != backend:
        raise SystemExit(
            f"{tag}: resolved backend was {got!r}, expected {backend!r} — "
            "refusing to emit a mislabeled record")
    if backend == "pallas":
        fused = record.get("metrics", {}).get("sphere_pack", {})
        if not (fused.get("unpack_dft", 0) > 0
                and fused.get("dft_pack", 0) > 0):
            raise SystemExit(
                f"{tag}: no fused sphere-pack dispatches in the metrics "
                f"window ({fused}) — the H sweeps fell back to the "
                "composed unpack/plan/pack route; fix the fusion guards "
                "rather than benchmarking the fallback under a pallas "
                "label")
    return record


def write_scenario_records(scf_records: dict, json_out: str,
                           merge: bool = False) -> dict:
    """Atomically write the schema-5 artifact; with ``merge``, fold the
    new records into whatever ``json_out`` already holds.

    The merge path is how CI's 8-device scf-3d step joins the 4-device
    scenarios in one BENCH_scf.json: the gate fails on baseline
    scenarios missing from the artifact it is handed, so both runs must
    land in the same file before the single compare invocation.  Same
    scenario name twice → the later run wins (a deliberate re-measure).
    Returns the merged scenario dict that was written.
    """
    merged = dict(scf_records)
    if merge and os.path.exists(json_out):
        with open(json_out) as f:
            prev = json.load(f)
        merged = dict(prev.get("scenarios", {}))
        merged.update(scf_records)
    atomic_json_dump({"schema": 5, "scenarios": merged}, json_out)
    return merged


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json-out", default="BENCH_scf.json",
                    help="path for the machine-readable SCF record")
    ap.add_argument("--merge", action="store_true",
                    help="fold this run's scenario records into an "
                         "existing --json-out instead of replacing it "
                         "(CI's 8-device scf-3d step merges into the "
                         "4-device artifact before the single gate call)")
    ap.add_argument("--scenarios", default="all",
                    help="comma list from %s, or the literal 'gate' to "
                         "run exactly the scenarios the baseline gates"
                         % ",".join(SCENARIOS))
    ap.add_argument("--baseline",
                    default=os.path.join(os.path.dirname(__file__),
                                         "baseline.json"),
                    help="baseline JSON that '--scenarios gate' resolves "
                         "the scenario list from (default: the committed "
                         "benchmarks/baseline.json)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome-trace/Perfetto JSON of the run "
                         "(per-stage spans with sync at span exit — "
                         "perturbs timings, never gate a traced run)")
    args = ap.parse_args(argv)
    if args.trace_out:
        from repro.obs.trace import get_tracer
        get_tracer().enable(sync=True, per_stage=True)
    if args.scenarios == "all":
        wanted = set(SCENARIOS)
    elif args.scenarios == "gate":
        # single source of truth for the gated scenario list: whatever
        # the committed baseline knows is what CI runs — adding a
        # scenario to the baseline starts gating it, no workflow edits
        try:
            with open(args.baseline) as f:
                base = json.load(f)["scenarios"]
        except (OSError, KeyError, json.JSONDecodeError) as e:
            ap.error(f"--scenarios gate: cannot resolve scenario list "
                     f"from {args.baseline}: {e}")
        wanted = set(base) & set(SCENARIOS)
        stale = sorted(set(base) - set(SCENARIOS))
        if stale:
            print(f"# WARNING: baseline gates unknown scenario(s) "
                  f"{stale} — this harness cannot run them")
        if not wanted:
            ap.error(f"--scenarios gate: {args.baseline} gates no "
                     "scenario this harness knows")
        print(f"# gate scenarios from {args.baseline}: "
              f"{', '.join(sorted(wanted))}")
    else:
        wanted = {s.strip() for s in args.scenarios.split(",") if s.strip()}
        bad = wanted - set(SCENARIOS)
        if bad:
            ap.error(f"unknown scenarios {sorted(bad)}; "
                     f"choose from {SCENARIOS}")
    rows: list[tuple[str, float, object]] = []
    scf_records: dict[str, dict] = {}
    if "table1" in wanted:
        bench_table1(rows)
    if "plan_cache" in wanted:
        bench_plan_cache(rows)
    if "local_fft" in wanted:
        bench_local_fft(rows, args.quick)
    if "planewave" in wanted:
        bench_planewave(rows, args.quick)
    if "fig9" in wanted:
        bench_fig9(rows)
    if "serve-transform" in wanted:
        scf_records["serve-transform"] = _metrics_window(
            lambda: bench_serve_transform(rows, args.quick))
    if "scf" in wanted:
        scf_records["scf"] = _metrics_window(
            lambda: bench_scf(rows, args.quick, tag="scf"))
    if "scf-2d" in wanted:
        import jax
        shape = scf_2d_grid_shape(jax.device_count())
        if shape is None:
            print(f"# scf-2d skipped: no feasible batch×fft split for "
                  f"{jax.device_count()} device(s) — needs >= 4 with the "
                  f"batch factor dividing nbands={SCF_NBANDS} and the fft "
                  f"factor dividing d={SCF_DIAMETER} "
                  "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        else:
            # stack_k pinned off: scf-2d tracks the pipelined per-k path,
            # scf-stacked below tracks the ragged k-stacked H apply
            scf_records["scf-2d"] = _metrics_window(
                lambda: bench_scf(rows, args.quick, grid_shape=shape,
                                  tag="scf-2d", stack_k=False))
    if "scf-stacked" in wanted:
        import jax
        shape = scf_stacked_grid_shape(jax.device_count())
        if shape is None:
            print(f"# scf-stacked skipped: no batch×fft split for "
                  f"{jax.device_count()} device(s) whose batch factor "
                  f"carries the nk·nbands = {SCF_NK}·{SCF_NBANDS} stacked "
                  "batch (XLA_FLAGS=--xla_force_host_platform_device_"
                  "count=4)")
        else:
            scf_records["scf-stacked"] = require_stacked_route(
                _metrics_window(
                    lambda: bench_scf(rows, args.quick, grid_shape=shape,
                                      tag="scf-stacked", stack_k=True)),
                "scf-stacked")
    if "scf-jit" in wanted:
        import jax
        shape = scf_stacked_grid_shape(jax.device_count())
        if shape is None:
            print(f"# scf-jit skipped: needs the scf-stacked grid (a "
                  f"batch×fft split whose batch factor carries the "
                  f"nk·nbands = {SCF_NK}·{SCF_NBANDS} stacked batch); "
                  f"{jax.device_count()} device(s) have none "
                  "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        else:
            scf_records["scf-jit"] = require_stacked_route(
                _metrics_window(
                    lambda: bench_scf(rows, args.quick, grid_shape=shape,
                                      tag="scf-jit", stack_k=True,
                                      jit_step=True)),
                "scf-jit")
    if "scf-pallas" in wanted:
        import jax
        # the probe must exist before the metrics window opens so the
        # record's delta starts from this scenario, not process start
        import repro.kernels.sphere_pack  # noqa: F401
        shape = scf_stacked_grid_shape(jax.device_count())
        if shape is None:
            print(f"# scf-pallas skipped: needs the scf-stacked grid (a "
                  f"batch×fft split whose batch factor carries the "
                  f"nk·nbands = {SCF_NK}·{SCF_NBANDS} stacked batch); "
                  f"{jax.device_count()} device(s) have none "
                  "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
        else:
            scf_records["scf-pallas"] = require_backend(
                require_stacked_route(
                    _metrics_window(
                        lambda: bench_scf(rows, args.quick,
                                          grid_shape=shape,
                                          tag="scf-pallas", stack_k=True,
                                          backend="pallas")),
                    "scf-pallas"),
                "scf-pallas", "pallas")
    if "scf-3d" in wanted:
        import jax
        shape = scf_3d_grid_shape(jax.device_count())
        if shape is None:
            print(f"# scf-3d skipped: no batch×fft×fft pencil split for "
                  f"{jax.device_count()} device(s) — needs >= 8 with the "
                  f"batch factor dividing nbands={SCF_NBANDS} and each "
                  f"fft factor within the d={SCF_DIAMETER} sphere's "
                  "per-axis guard (XLA_FLAGS=--xla_force_host_platform_"
                  "device_count=8)")
        else:
            scf_records["scf-3d"] = require_stacked_route(
                _metrics_window(
                    lambda: bench_scf(rows, args.quick, grid_shape=shape,
                                      tag="scf-3d", stack_k=True,
                                      segment_padding=SCF_SEGMENT_PADDING)),
                "scf-3d")
    if "steps" in wanted:
        # --quick drops steps from the default "all" sweep, but an
        # explicitly requested scenario always runs
        if args.scenarios != "all":
            bench_steps(rows)
        elif not args.quick:
            bench_steps(rows)
        else:
            print("# steps skipped under --quick (request it explicitly "
                  "with --scenarios steps to run anyway)")
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if scf_records:
        merged = write_scenario_records(scf_records, args.json_out,
                                        merge=args.merge)
        print(f"# wrote {args.json_out} "
              f"(scenarios: {', '.join(merged)})")
    if args.trace_out:
        from repro.obs.trace import get_tracer
        tr = get_tracer()
        tr.disable()
        tr.export_chrome(args.trace_out)
        print(f"# wrote {args.trace_out} ({len(tr.events())} trace "
              "events) — traced timings are not gate-comparable")


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
