#!/usr/bin/env python3
"""Readings of a cell's compared numbers over many seeds, and its control.

    python bench/control.py --workload NAME --seeds 11,12,... \
        --control-seeds 21,22,23 --seconds 1 [--out FILE]

In one process (the chip's set-up is paid once): for every ``--seeds``
seed a whole run of the cell as ``run.py`` makes it, with a short window;
then, for every ``--control-seeds`` seed, the same run with the program
traced one precision below the configuration's (``highest`` → ``high``:
the line-DFT GEMMs' ``DFT_PRECISION`` and JAX's default matmul
precision) — the control, which has to come out not correct.  The
program's largest readings and the control's smallest are what each
cell's limits (``bench/limits/<workload>.json``) are set between.

The benchmark's own runs never run this.  Prints one JSON line.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path.pop(0)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402
from bench.registry import Registry  # noqa: E402


def readings(workload: str, seeds, control_seeds, seconds: float,
             registry: Registry | None = None, devices=None) -> dict:
    """{"program": [...], "control": [...]}, one entry per seed: the seed,
    ``correct`` and every compared number."""
    import jax

    from repro.core import global_plan_cache

    reg = registry or Registry()
    if devices is None:
        devices = run.chip_devices(reg, int(reg.workload(workload)["chips"]))
    out = {"workload": workload, "program": [], "control": []}
    for label, seed_list, control in (("program", seeds, False),
                                      ("control", control_seeds, True)):
        # plans cache their jitted executors, traced at one precision
        jax.clear_caches()
        global_plan_cache().clear()
        for seed in seed_list:
            res = run.run_cell(workload, seed=seed, seconds=seconds,
                               trace=False, registry=reg, devices=devices,
                               control=control)
            entry = {"seed": seed, "correct": res["correct"]}
            entry.update({k: v["value"] for k, v in res["checks"].items()})
            out[label].append(entry)
            run.log(f"{label} {json.dumps(entry)}")
    for label in ("program", "control"):
        rows = out[label]
        if rows:
            names = [k for k in rows[0] if k not in ("seed", "correct")]
            out[f"{label}_max"] = {k: max(r[k] for r in rows) for k in names}
            out[f"{label}_min"] = {k: min(r[k] for r in rows) for k in names}
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    def parse(s):
        return [int(x) for x in s.split(",") if x]

    reg = Registry()
    devices = run.chip_devices(reg, int(reg.workload(args.workload)["chips"]))
    run.log(f"compile cache {run.enable_cache()}")
    out = readings(args.workload, parse(args.seeds),
                   parse(args.control_seeds), args.seconds, reg, devices)
    line = json.dumps(out)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
