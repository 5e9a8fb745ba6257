#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python bench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
the mix names the kind of work (``bench/kinds/<kind>.py``), which builds
the program's own timed calls.  A run:

1. refuses any platform but ``tpu``, fewer chips than the cell asks for,
   and a ``device_kind`` missing from ``bench/peaks.json`` (exit 3);
2. turns on JAX's persistent compile cache at its fixed path
   (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``);
3. builds the cell — inputs made on the device from ``--seed`` — and
   warms every program it times (set-up, with compile time counted
   apart);
4. runs steps back to back until ``--seconds`` have passed, counting any
   compile inside the window; with ``--trace 1`` under the profiler;
5. reads the memory, frees the program's state, runs the plain reference
   and compares (``bench/limits/<workload>.json``);
6. prints every number compared beside its limit as the last lines of
   standard error, and one JSON line as the last line of standard output.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (``bench/metrics/<name>.py``), the
device's busy and window seconds and a breakdown of the trace.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if sys.path and os.path.abspath(sys.path[0]) == os.path.join(ROOT, "bench"):
    sys.path.pop(0)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.registry import Registry  # noqa: E402

#: JAX's event for one backend compile (a persistent-cache hit skips it)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Refused(SystemExit):
    """The run cannot give a result here (exit 3, no result line)."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(3)


@dataclasses.dataclass
class Context:
    """What a kind's ``build`` gets."""
    config: dict
    params: dict
    seed: int
    devices: list
    precision: str


class CompileLog:
    """Counts backend compiles and persistent-cache hits, by phase."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.compiles, self.compile_s, self.cache_hits


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def chip_devices(registry: Registry, chips: int):
    """The cell's devices; refuses anything but enough TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"cell asks for {chips} chips, found {len(devs)}")
    try:
        registry.peaks(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from e
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise Refused(f"no program under {os.path.join(ROOT, 'src')}")
    return devs[:chips]


def enable_cache(trace: bool = False) -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    # cache every program, however quick to compile, so that every run
    # after a checkout's first finds all of them (steady set-up)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if trace:
        # the cache key leaves the instructions' metadata out unless told
        # otherwise, and a hit then carries the scope names of whatever
        # revision compiled first; a traced run reads the scope map from
        # its programs, so it keys on them (untraced runs keep the key)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    return where


def _device_record(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             registry: Registry | None = None, devices=None,
             control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``devices=None`` looks for the cell's chips and refuses anything
    else.  ``control=True`` traces the program one precision below the
    configuration's (the control, never run by the benchmark itself).
    """
    import jax

    from bench import common
    from bench import scopes
    from bench import trace as btrace

    reg = registry or Registry()
    cell_entry = reg.workload(workload)
    config = reg.config(cell_entry["config"])
    params = reg.traffic(cell_entry["traffic"])
    kind = reg.kind(params["kind"])
    limits = reg.limits(workload)
    if devices is None:
        devices = chip_devices(reg, int(cell_entry["chips"]))
    peaks = (reg.peaks(devices[0].device_kind)
             if devices[0].platform == "tpu" else None)
    precision = config["precision"]
    if control:
        precision = common.LOWER[precision]
    compiles = CompileLog()

    ctx = Context(config, params, int(seed), list(devices), precision)
    cell = kind.build(ctx)
    t_step = cell.warm()
    setup_s = time.perf_counter() - T_START
    n0, c0, h0 = compiles.snapshot()
    log(f"setup_s={setup_s:.3f} backend_compiles={n0} compile_s={c0:.3f} "
        f"cache_hits={h0} warm_step_s={t_step:.4f}")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    steps = 0
    t0 = time.perf_counter()
    while True:
        cell.step(steps)
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    n1, c1, _ = compiles.snapshot()
    in_window = n1 - n0
    units = steps * cell.units_per_step
    log(f"window steps={steps} units={units} seconds={elapsed:.4f} "
        f"compiles_in_window={in_window} ({c1 - c0:.3f} s)")

    hbm = max(common.program_bytes(p) for p in cell.programs.values())
    for name, prog in cell.programs.items():
        log(f"program {name} bytes={common.program_bytes(prog)}")
    device = _device_record(devices)
    # hbm_gib is the compiler's static count, the peak is the runtime's
    # buffer high-water mark (blind to temporaries): kept side by side
    log(f"hbm_gib={hbm / 2 ** 30:.4f} (compile-time memory_analysis) "
        f"memory_peak_bytes={device['memory_peak_bytes']} "
        f"(runtime peak_bytes_in_use)")
    if trace:
        # read before release, which may drop the compiled programs
        t_map = time.perf_counter()
        scope_maps = {name: scopes.op_scopes(p)
                      for name, p in cell.programs.items()}
        log(f"scope maps of {len(scope_maps)} programs took "
            f"{time.perf_counter() - t_map:.3f} s")
    held = cell.release()

    values = {}
    if trace:
        tr = btrace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        info = {"programs": kind.PROGRAMS, "units_per_step":
                cell.units_per_step, "peaks": peaks, "chips": len(devices),
                "work": kind.required_work(config, params), "log": log,
                "scopes": scope_maps}
        if hasattr(kind, "scope_work"):
            info["scope_work"] = kind.scope_work(config, params)
        for m in reg.per_layer(workload):
            v = reg.metric_reader(m["name"]).read(tr, info)
            if v is not None:
                values[m["name"]] = v
        occ = btrace.occupancy(tr, kind.PROGRAMS)
        if occ is not None:
            device["busy_s"], device["window_s"] = occ
        metrics_def = reg.per_layer(workload)
    else:
        values.update(kind.window_metrics(units, steps, elapsed))
        values["hbm_gib"] = hbm / 2 ** 30
        values["setup_s"] = setup_s
        metrics_def = reg.end_to_end(workload)
        missing = [m["name"] for m in metrics_def if m["name"] not in values]
        if missing:
            raise KeyError(f"{workload}: no value for {missing}")

    t_check = time.perf_counter()
    readings = cell.readings(held)
    log(f"reference and comparison took "
        f"{time.perf_counter() - t_check:.3f} s")
    checks = {name: {"value": float(readings[name]),
                     "limit": float(limits[name])} for name in kind.CHECKS}
    failed = [name for name, c in checks.items()
              if not (math.isfinite(c["value"]) and c["value"] <= c["limit"])]
    result = {
        "correct": not failed,
        "attempted": units,
        "failed": len(failed),
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in metrics_def if m["name"] in values},
        "device": device,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": btrace.top_ops(tr, kind.PROGRAMS),
            "idle_gaps": btrace.idle_gaps(tr, kind.PROGRAMS)}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    reg = Registry()
    devices = chip_devices(reg, int(reg.workload(args.workload)["chips"]))
    log(f"compile cache {enable_cache(trace=bool(args.trace))}")
    emit(run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), registry=reg, devices=devices))
    return 0


def emit(result: dict) -> None:
    """Every compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} value={c['value']!r} limit={c['limit']!r} "
              f"{verdict}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
