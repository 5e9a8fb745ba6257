"""Roofline analysis (§g deliverable): three terms per (arch × shape) from
the dry-run artifacts in experiments/dryrun.json.

    compute    = FLOPs_dev / peak_FLOPs          (197 TF bf16, v5e)
    memory     = bytes_dev / HBM_bw              (819 GB/s)
    collective = coll_bytes_dev / link_bw        (50 GB/s/link ICI)

`cost_analysis()` under SPMD reports *per-device* numbers (verified:
a 1024² matmul sharded 8-ways reports 2.68e8 = 2.1e9/8 FLOPs), so terms
divide by per-chip rates directly. FLOPs/bytes/collectives come from the
*accounting* records (unrolled scans, see dryrun.account_cell) when
available — rolled-scan records under-count loop bodies.

MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); decode/prefill use the
token count of the step (B·S for prefill, B for decode).

Run: PYTHONPATH=src python -m benchmarks.roofline [--mesh single]
"""
from __future__ import annotations

import argparse
import json
import os

PEAK = 197e12
HBM = 819e9
LINK = 50e9

RESULTS = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "dryrun.json")


def model_flops(arch: str, shape_name: str) -> float:
    from repro.configs.base import SHAPES, get_config
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * n * tokens
    return 2.0 * n * shape.batch          # decode: one token per sequence


def analyse(db: dict, mesh: str = "single"):
    mesh_tag = {"single": "16x16", "multi": "2x16x16"}[mesh]
    rows = []
    for key, v in sorted(db.items()):
        if "|acct" in key or "skipped" in v or "error" in v:
            continue
        if v.get("mesh") != mesh_tag or "flops" not in v:
            continue
        arch, shape = v["arch"], v["shape"]
        acct = db.get(f"{arch}|{shape}|{mesh}|acct")
        use_acct = bool(acct and "flops" in acct)
        if use_acct:
            # floor the L1/L2 extrapolation at the rolled-scan raw value
            # (a hard lower bound: scan bodies counted once) — guards
            # against negative slopes from per-depth XLA differences
            src = {k: max(acct[k], v[k])
                   for k in ("flops", "bytes_accessed", "collective_total")}
        else:
            src = v
        n_dev = v["n_devices"]
        t_comp = src["flops"] / PEAK
        t_mem = src["bytes_accessed"] / HBM
        t_coll = src["collective_total"] / LINK
        dom = max((t_comp, "compute"), (t_mem, "memory"),
                  (t_coll, "collective"))[1]
        if arch.startswith("fftb-paper"):
            mf = src["flops"] * n_dev          # the FFT *is* the model
        else:
            mf = model_flops(arch, shape)
        hlo_total = src["flops"] * n_dev
        rows.append({
            "arch": arch, "shape": shape, "mesh": mesh_tag,
            "t_compute_s": t_comp, "t_memory_s": t_mem,
            "t_collective_s": t_coll, "dominant": dom,
            "model_flops": mf,
            "useful_ratio": mf / hlo_total if hlo_total else 0.0,
            "peak_gib": v.get("peak_bytes_per_device", 0) / 2 ** 30,
            "accounted": use_acct,
        })
    return rows


def _bytes_accessed(jitted, *args) -> float | None:
    """'bytes accessed' from XLA's cost analysis, None when unavailable.

    CPU/interpret builds sometimes return no analysis (or a list per
    computation); treat every failure as "measured unavailable" so the
    report degrades to modeled-only instead of crashing.
    """
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else None
        if not cost:
            return None
        val = cost.get("bytes accessed")
        return float(val) if val is not None else None
    except Exception:
        return None


def analyse_sphere_kernels(n: int = 16, d: int = 8, nk: int = 2,
                           nbands: int = 4):
    """Measured-vs-modeled bytes for the fused sphere-pack kernels.

    Compares the composed hot-path legs (``unpack`` + plan / plan +
    ``pack``) against the fused pallas routes
    (``unpack_transform``/``transform_pack``) on a 1-device grid.  The
    byte model counts the packed operands and the first/last-stage slab
    once each; the composed route additionally writes the zero-padded
    (B, d³) bounding cube and reads it back for the line-DFT GEMM —
    16·B·d³ modeled bytes per direction that the fused kernels never
    touch (the two saved cube materializations).  Measured numbers come
    from XLA's ``cost_analysis`` when the backend provides one.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import (ProcGrid, kpoint_sphere,
                            make_stacked_planewave_pair)

    grid = ProcGrid.create([1])
    kpts = ((0.0, 0.0, 0.0), (0.5, 0.5, 0.5), (0.0, 0.5, 0.0))
    spheres = [kpoint_sphere(d, kp) for kp in kpts[:nk]]
    inv, fwd = make_stacked_planewave_pair(grid, n, spheres, nbands,
                                           backend="pallas")
    B = nk * nbands
    npm = inv.npacked_max
    rng = np.random.default_rng(0)
    packed = jnp.asarray(
        (rng.standard_normal((B, npm))
         + 1j * rng.standard_normal((B, npm))).astype(np.complex64))
    cube = inv(inv.unpack(packed))

    slab = 8.0 * B * d * d * n          # first/last-stage (B, d, d, n)
    pack_io = 8.0 * B * npm             # packed lanes, complex64
    cube_rw = 16.0 * B * d ** 3         # cube write + GEMM read-back
    rows = []
    for name, composed, fused, x in (
            ("unpack_dft",
             jax.jit(lambda p: inv(inv.unpack(p))),
             jax.jit(inv.unpack_transform), packed),
            ("dft_pack",
             jax.jit(lambda c: fwd.pack(fwd(c))),
             jax.jit(fwd.transform_pack), cube)):
        m_comp = _bytes_accessed(composed, x)
        m_fus = _bytes_accessed(fused, x)
        rows.append({
            "kernel": name,
            "modeled_composed_bytes": pack_io + slab + cube_rw,
            "modeled_fused_bytes": pack_io + slab,
            "modeled_saved_bytes": cube_rw,
            "measured_composed_bytes": m_comp,
            "measured_fused_bytes": m_fus,
            "measured_saved_bytes": (m_comp - m_fus)
            if m_comp is not None and m_fus is not None else None,
        })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi"])
    ap.add_argument("--csv", default="")
    ap.add_argument("--sphere-kernels", action="store_true",
                    help="report measured-vs-modeled bytes for the fused "
                         "sphere-pack pallas kernels against the composed "
                         "unpack/plan/pack route")
    args = ap.parse_args(argv)
    if args.sphere_kernels:
        rows = analyse_sphere_kernels()
        print(f"{'kernel':12s} {'route':9s} {'modeled_MiB':>12s} "
              f"{'measured_MiB':>13s}")
        for r in rows:
            for route in ("composed", "fused"):
                meas = r[f"measured_{route}_bytes"]
                print(f"{r['kernel']:12s} {route:9s} "
                      f"{r[f'modeled_{route}_bytes'] / 2 ** 20:12.3f} "
                      + (f"{meas / 2 ** 20:13.3f}" if meas is not None
                         else f"{'n/a':>13s}"))
            saved = r["measured_saved_bytes"]
            print(f"{'':12s} {'saved':9s} "
                  f"{r['modeled_saved_bytes'] / 2 ** 20:12.3f} "
                  + (f"{saved / 2 ** 20:13.3f}" if saved is not None
                     else f"{'n/a':>13s}")
                  + "   (bounding-cube write + read the fusion skips)")
        return rows
    with open(RESULTS) as f:
        db = json.load(f)
    rows = analyse(db, args.mesh)
    hdr = (f"{'arch':22s} {'shape':12s} {'compute_s':>10s} {'memory_s':>10s}"
           f" {'coll_s':>10s} {'dominant':>10s} {'useful':>7s} {'peakGiB':>8s}")
    print(hdr)
    for r in rows:
        print(f"{r['arch']:22s} {r['shape']:12s} {r['t_compute_s']:10.3e} "
              f"{r['t_memory_s']:10.3e} {r['t_collective_s']:10.3e} "
              f"{r['dominant']:>10s} {r['useful_ratio']:7.2f} "
              f"{r['peak_gib']:8.2f}" + ("" if r["accounted"] else "  (raw)"))
    if args.csv:
        import csv
        with open(args.csv, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0]))
            w.writeheader()
            w.writerows(rows)
        print(f"wrote {args.csv}")
    return rows


if __name__ == "__main__":
    main()
