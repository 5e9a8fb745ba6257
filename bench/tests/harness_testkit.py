"""A small copy of the benchmark for CPU tests: the same files, with the
configurations cut to a 16³ grid and the traffic to a few orbitals."""
from __future__ import annotations

import json
import os
import shutil

from bench.registry import BENCH_DIR, ROOT, Registry

TINY_CONFIG = {"n": 16, "diameter": 8}
#: bands a k-point by chip count, where a configuration states them so
TINY_NBANDS = {"1": 2, "4": 2}
TINY_TRAFFIC = {"cube-16": {"batch": 2}}


def tiny_registry(tmp_path) -> Registry:
    """A registry over a copy of the benchmark at CPU-test sizes."""
    root = str(tmp_path)
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for name in os.listdir(os.path.join(bench, "configs")):
        path = os.path.join(bench, "configs", name)
        with open(path) as fh:
            cfg = json.load(fh)
        cfg.update(TINY_CONFIG)
        if "L" in cfg:
            cfg["L"] = float(cfg["n"])
        if isinstance(cfg.get("nbands"), dict):
            cfg["nbands"] = dict(TINY_NBANDS)
        with open(path, "w") as fh:
            json.dump(cfg, fh)
    for name, change in TINY_TRAFFIC.items():
        path = os.path.join(bench, "traffic", f"{name}.json")
        with open(path) as fh:
            mix = json.load(fh)
        mix.update(change)
        with open(path, "w") as fh:
            json.dump(mix, fh)
    return Registry(root)


def run_child(script: str, n_devices: int, timeout: int = 600) -> str:
    """Run ``script`` in a child Python on ``n_devices`` host devices."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout,
                          check=False)
    if proc.returncode != 0:
        raise AssertionError(f"child failed:\n{proc.stdout[-3000:]}\n"
                             f"{proc.stderr[-3000:]}")
    return proc.stdout
