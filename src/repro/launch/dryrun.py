"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces the artifacts the roofline analysis reads:
  * compiled.memory_analysis()  — proves the cell fits 16 GB/chip,
  * compiled.cost_analysis()    — HLO FLOPs / bytes accessed,
  * collective bytes parsed from the optimized HLO text (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute).

Results append incrementally to experiments/dryrun.json so interrupted
sweeps resume.  The paper's own workload (fftb-paper: batched plane-wave
FFT 256³, sphere d=128, 256 bands) runs through the same harness.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both] [--force]
  python -m repro.launch.dryrun --paper
"""
import argparse
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, ARCH_IDS, applicable, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import build
from repro.sharding import ctx, rules
from repro.train.train_step import make_train_step, init_opt_state
from repro.optim.adamw import AdamWConfig

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun.json")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8,
                "c128": 16}

_COLL = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")


def collective_bytes(hlo: str) -> dict[str, int]:
    """Per-device *operand* bytes of every collective in optimized HLO.

    Optimized HLO prints operands by name only, so sizes are derived from
    the RESULT type: all-reduce/all-to-all/collective-permute results equal
    their operands; all-gather operands are result/participants;
    reduce-scatter operands are result×participants.  Participant counts
    come from replica_groups (explicit {{...}} or iota [G,P]<=[N] form).
    """
    out: dict[str, int] = {c: 0 for c in _COLL}
    shape_re = re.compile(r"(\w+)\[([0-9,]*)\]")
    line_re = re.compile(
        r"=\s*((?:\([^=]*?\))|(?:\S+))\s+"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(-start)?\(")
    for line in hlo.splitlines():
        m = line_re.search(line)
        if not m:
            continue
        restype, op, start = m.group(1), m.group(2), m.group(3)
        total = 0
        for dt, dims in shape_re.findall(restype):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        if start and restype.startswith("("):
            total //= 2          # async start returns (operand, result)
        # participants
        p = 1
        g = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
        if g:
            p = len(g.group(1).split(","))
        else:
            g = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
            if g:
                p = int(g.group(2))
        if op == "all-gather" and p:
            total //= p
        elif op == "reduce-scatter":
            total *= p
        out[op] += total
    return out


# --------------------------------------------------------------- inputs
def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    B, S = shape.batch, shape.seq
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((B, S), jnp.int32),
                 "labels": sds((B, S), jnp.int32)}
        if cfg.family == "vlm":
            # image tokens replace part of the sequence (stub embeddings)
            n_img = cfg.n_img_tokens
            batch = {"tokens": sds((B, S - n_img), jnp.int32),
                     "labels": sds((B, S - n_img), jnp.int32),
                     "image_embeds": sds((B, n_img, cfg.d_model),
                                         jnp.bfloat16)}
        if cfg.family == "encdec":
            batch["frames"] = sds((B, cfg.enc_seq, cfg.d_model),
                                  jnp.bfloat16)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), jnp.int32)}
        if cfg.family == "vlm":
            n_img = cfg.n_img_tokens
            batch = {"tokens": sds((B, S - n_img), jnp.int32),
                     "image_embeds": sds((B, n_img, cfg.d_model),
                                         jnp.bfloat16)}
        if cfg.family == "encdec":
            batch["frames"] = sds((B, cfg.enc_seq, cfg.d_model),
                                  jnp.bfloat16)
        return batch
    # decode: one new token against a cache of length S
    return {"tokens": sds((B, 1), jnp.int32),
            "lengths": sds((B,), jnp.int32)}


def _eval_shape_tree(fn, *args, **kw):
    return jax.eval_shape(fn, *args, **kw)


def _shardings(tree_specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------ accounting
def account_cell(arch: str, shape_name: str, mesh, *, verbose=True):
    """Honest per-device FLOP/byte/collective totals.

    XLA's cost_analysis counts while-loop (lax.scan) bodies ONCE, so the
    scanned-layer cells above under-report by ~the layer count.  Here the
    same cell is lowered twice with all scans UNROLLED at depths L=1 and
    L=2 (hybrid: 1 and 2 groups); scan bodies are homogeneous, so every
    cost is exactly linear in depth and extrapolates to the full depth:
        cost(L) = cost(1) + (cost(2) − cost(1))·(L − 1).
    Microbatching is folded to 1 for this pass (same token count → same
    matmul work; only the accumulate-adds differ, negligible).
    """
    import dataclasses as _dc
    from repro.models import flags as _flags
    cfg = get_config(arch)
    if cfg.family == "hybrid":
        plen = len(cfg.block_pattern)
        depths = (plen, 2 * plen)
        l_full = (cfg.n_layers // plen)
        unit = plen
    else:
        depths = (1, 2)
        l_full = cfg.n_layers
        unit = 1
    recs = []
    for L in depths:
        cfg_l = _dc.replace(cfg, n_layers=L,
                            enc_layers=min(cfg.enc_layers, L) if
                            cfg.enc_layers else 0)
        with _flags.unrolled():
            recs.append(lower_cell(arch, shape_name, mesh, verbose=False,
                                   cfg_override=cfg_l))
    r1, r2 = recs
    steps = l_full - 1

    def extra(key):
        if isinstance(r1[key], dict):
            return {k: r1[key][k] + (r2[key][k] - r1[key][k]) * steps
                    for k in r1[key]}
        return r1[key] + (r2[key] - r1[key]) * steps

    out = {
        "arch": arch, "shape": shape_name, "mesh": r1["mesh"],
        "n_devices": r1["n_devices"],
        "flops": extra("flops"),
        "bytes_accessed": extra("bytes_accessed"),
        "collective_bytes": extra("collective_bytes"),
        "collective_total": extra("collective_total"),
        "depths": list(depths), "l_full": l_full,
        "method": "unrolled-L1L2-extrapolation",
    }
    if cfg.family == "hybrid" and cfg.n_layers % len(cfg.block_pattern):
        # 38 = 12 groups + 2 tail rec layers: scale by true/extrapolated
        scale = cfg.n_layers / (l_full * len(cfg.block_pattern))
        for k in ("flops", "bytes_accessed", "collective_total"):
            out[k] *= scale
        out["collective_bytes"] = {k: v * scale
                                   for k, v in out["collective_bytes"].items()}
        out["tail_scale"] = scale
    if verbose:
        print(f"[{out['mesh']}] acct {arch} × {shape_name}: "
              f"flops={out['flops']:.3e} bytes={out['bytes_accessed']:.3e} "
              f"coll={out['collective_total']:.3e}", flush=True)
    return out


# ----------------------------------------------------------------- cells
def lower_cell(arch: str, shape_name: str, mesh, *, verbose=True,
               cfg_override=None, mb_override=None):
    cfg = cfg_override or get_config(arch)
    shape = SHAPES[shape_name]
    bundle = build(cfg)
    key = jax.random.PRNGKey(0)
    batch_axes = rules.batch_axis(mesh, shape.batch)
    # sequence parallelism for the long-activation cells (train/prefill)
    seq_axis = "model" if shape.kind in ("train", "prefill") else None
    _cm = ctx.use(mesh, batch_axes, seq_axis)
    _cm.__enter__()
    params_sds = jax.eval_shape(bundle.init, key)
    pspecs = rules.param_specs(params_sds, mesh)
    pshard = _shardings(pspecs, mesh)
    batch_sds_all = input_specs(arch, shape_name)
    bspec = rules.data_specs(cfg, shape, mesh)
    bspec = {k: v for k, v in bspec.items() if k in batch_sds_all}
    for k in batch_sds_all:
        bspec.setdefault(k, P(*([batch_axes]
                                + [None] * (batch_sds_all[k].ndim - 1))))
    bshard = _shardings(bspec, mesh)
    t0 = time.perf_counter()

    if shape.kind == "train":
        # memory-reduced (bf16) optimizer states once f32 m/v would exceed
        # ~40% of HBM: params×10B/dev > 6.5 GiB → switch (8-bit-Adam-style)
        pbytes = sum(x.size for x in jax.tree.leaves(params_sds))
        opt_dtype = jnp.bfloat16 if pbytes * 10 / mesh.size > 6.5 * 2**30 \
            else jnp.float32
        opt_sds = jax.eval_shape(
            lambda p: init_opt_state(p, dtype=opt_dtype), params_sds)
        ospecs = rules.param_specs(opt_sds, mesh)
        oshard = _shardings(ospecs, mesh)
        # microbatching: keep ≤ ~16k tokens per device per microbatch —
        # the standard activation-memory lever at scale
        dp = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dp *= mesh.shape[a]
        b_loc = max(shape.batch // dp, 1)
        # wider models carry proportionally bigger activations per token;
        # MoE intermediates scale with top_k·d_ff (≈6× a dense MLP on dbrx)
        tok_budget = 16384 if cfg.d_model < 8192 else 4096
        if cfg.family == "moe" and cfg.top_k * cfg.d_ff > 4 * cfg.d_model:
            tok_budget = 4096
        mb = max(1, (b_loc * shape.seq) // tok_budget)
        while b_loc % mb:
            mb -= 1
        if mb_override is not None:
            mb = mb_override
        step = make_train_step(bundle, AdamWConfig(), mesh, donate=False,
                               microbatches=mb)
        batch_sds = input_specs(arch, shape_name)
        fn = jax.jit(lambda p, o, b: step(p, o, b),
                     in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None))
        lowered = fn.lower(params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        cache_sds = jax.eval_shape(
            lambda: bundle.init_cache(shape.batch, shape.seq, jnp.bfloat16))
        cspecs = rules.cache_specs(cfg, shape.batch, mesh, cache_sds)
        cshard = _shardings(cspecs, mesh)
        batch_sds = input_specs(arch, shape_name)

        def fn(params, batch, cache):
            return bundle.prefill(params, batch, cache)

        lowered = jax.jit(
            fn, in_shardings=(pshard, bshard, cshard),
            out_shardings=(None, cshard)).lower(
            params_sds, batch_sds, cache_sds)
    else:  # decode
        capacity = shape.seq
        cache_sds = jax.eval_shape(
            lambda: bundle.init_cache(shape.batch, capacity, jnp.bfloat16))
        cspecs = rules.cache_specs(cfg, shape.batch, mesh, cache_sds)
        cshard = _shardings(cspecs, mesh)
        b = rules.batch_axis(mesh, shape.batch)
        tok_shard = _shardings({"tokens": P(b, None), "lengths": P(b)},
                               mesh)
        ins = input_specs(arch, shape_name)

        def fn(params, tokens, cache, lengths):
            return bundle.decode(params, tokens, cache, lengths)

        # cache is donated (in-place update), exactly as the serving
        # engine runs it — halves the measured cache footprint
        lowered = jax.jit(
            fn, in_shardings=(pshard, tok_shard["tokens"], cshard,
                              tok_shard["lengths"]),
            out_shardings=(None, cshard),
            donate_argnums=(2,)).lower(
            params_sds, ins["tokens"], cache_sds, ins["lengths"])

    _cm.__exit__(None, None, None)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "n_devices": mesh.size,
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll,
        "collective_total": float(sum(coll.values())),
        "mem": {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "code": mem.generated_code_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
        },
        "peak_bytes_per_device": mem.argument_size_in_bytes
        + mem.output_size_in_bytes + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes,
        "t_lower_s": t_lower, "t_compile_s": t_compile,
    }
    if verbose:
        print(f"[{rec['mesh']}] {arch} × {shape_name}: "
              f"flops={rec['flops']:.3e} bytes={rec['bytes_accessed']:.3e} "
              f"coll={rec['collective_total']:.3e} "
              f"peak={rec['peak_bytes_per_device']/2**30:.2f}GiB "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)",
              flush=True)
    return rec


def lower_paper_workload(mesh, *, verbose=True, backend="matmul",
                         variant="planewave"):
    """The paper's Fig. 9 workload as a dry-run cell.

    variant: planewave (staged pad, batched) | padded (full-cube baseline).
    """
    from repro.configs.fftb_paper import CONFIG as PC
    from repro.core import (Domain, ProcGrid, SphereDomain, DistTensor,
                            FftPlan, make_planewave_pair)
    fft_axes = tuple(i for i, a in enumerate(mesh.axis_names)
                     if a == "model")
    batch_axes = tuple(i for i, a in enumerate(mesh.axis_names)
                       if a != "model")
    grid = ProcGrid.from_mesh(mesh, mesh.axis_names)
    t0 = time.perf_counter()
    if variant == "planewave":
        sph = SphereDomain.from_diameter(PC.diameter)
        inv, _ = make_planewave_pair(grid, PC.n, sph, PC.nb,
                                     backend=backend,
                                     batch_axes=batch_axes,
                                     fft_axes=fft_axes)
        plan = inv.plan
        d = PC.diameter
        in_shape = (PC.nb, d, d, d)
    else:
        n, nb = PC.n, PC.nb
        bdom = Domain((0,), (nb - 1,))
        cube = Domain((0, 0, 0), (n - 1, n - 1, n - 1))
        bspec = "{%s}" % ",".join(str(a) for a in batch_axes)
        fspec = "{%s}" % ",".join(str(a) for a in fft_axes)
        ti = DistTensor.create((bdom, cube), f"b{bspec} x{fspec} y z", grid)
        to = DistTensor.create((bdom, cube), f"B{bspec} X Y Z{fspec}", grid)
        plan = FftPlan(ti, to, [("x", "X"), ("y", "Y"), ("z", "Z")],
                       inverse=True, backend=backend)
        in_shape = (nb, n, n, n)
    sds = jax.ShapeDtypeStruct(in_shape, jnp.complex64)
    lowered = plan._sharded_fn.lower(sds)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    rec = {
        "arch": f"fftb-paper-{variant}", "shape": f"n{PC.n}-d{PC.diameter}-b{PC.nb}",
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "n_devices": mesh.size,
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll,
        "collective_total": float(sum(coll.values())),
        "model_comm_bytes": list(plan.comm_stats()),
        "mem": {"argument": mem.argument_size_in_bytes,
                "output": mem.output_size_in_bytes,
                "temp": mem.temp_size_in_bytes},
        "peak_bytes_per_device": mem.argument_size_in_bytes
        + mem.output_size_in_bytes + mem.temp_size_in_bytes,
        "t_lower_s": t_lower, "t_compile_s": t_compile,
        "plan": plan.describe(),
    }
    if verbose:
        print(f"[{rec['mesh']}] {rec['arch']}: flops={rec['flops']:.3e} "
              f"coll={rec['collective_total']:.3e} "
              f"peak={rec['peak_bytes_per_device']/2**30:.2f}GiB "
              f"(compile {t_compile:.0f}s)", flush=True)
    return rec


# ------------------------------------------------------------------ main
def _load():
    try:
        with open(RESULTS) as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def _store(db):
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    tmp = RESULTS + ".tmp"
    with open(tmp, "w") as f:
        json.dump(db, f, indent=1)
    os.replace(tmp, RESULTS)


def main():
    # 512 virtual host devices for the production meshes; set before the
    # first device query initializes the CPU backend, and only for the
    # CLI, so importing this module leaves the caller's devices alone
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--paper", action="store_true")
    ap.add_argument("--paper-variant", default="planewave")
    ap.add_argument("--account", action="store_true",
                    help="unrolled accounting pass (honest scan FLOPs)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    db = _load()
    failures = []

    def run(arch, shape_name, mname, mesh):
        key = f"{arch}|{shape_name}|{mname}"
        if args.account:
            key += "|acct"
        ok, why = applicable(get_config(arch), SHAPES[shape_name])
        if not ok:
            db[key] = {"arch": arch, "shape": shape_name, "mesh": mname,
                       "skipped": why}
            _store(db)
            print(f"SKIP {key}: {why}")
            return
        if key in db and not db[key].get("error") and not args.force:
            print(f"cached {key}")
            return
        try:
            fn = account_cell if args.account else lower_cell
            db[key] = fn(arch, shape_name, mesh)
        except Exception as e:  # record the failure, keep sweeping
            db[key] = {"arch": arch, "shape": shape_name, "mesh": mname,
                       "error": f"{type(e).__name__}: {e}"}
            failures.append(key)
            print(f"FAIL {key}: {e}", flush=True)
        _store(db)

    if args.paper:
        for mname, mesh in meshes:
            key = f"fftb-paper-{args.paper_variant}|{mname}"
            if key in db and not db[key].get("error") and not args.force:
                print(f"cached {key}")
                continue
            db[key] = lower_paper_workload(mesh,
                                           variant=args.paper_variant)
            _store(db)
        return

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for mname, mesh in meshes:
        for arch in archs:
            for shape_name in shapes:
                run(arch, shape_name, mname, mesh)
    if failures:
        print(f"\n{len(failures)} failures: {failures}")
        raise SystemExit(1)
    print("\nall cells OK")


if __name__ == "__main__":
    main()
