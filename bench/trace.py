"""The device trace of a ``--trace 1`` run, and its reduction to numbers.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, with
``jax.profiler.ProfileData``, into a plain :class:`Trace`:

* per device plane (``/device:TPU:<i>``) the program executions of its
  ``XLA Modules`` line and the operations of its ``XLA Ops`` and
  ``Async XLA Ops`` lines;
* the harness's own host annotations (``bench.*``) from the host plane.

Host and device clocks are not the same clock.  Each device's offset is
taken from the runtime's ``CompleteCallbacks`` host events, which carry
the ``run_id`` of the program execution they complete: a completion
cannot come before the program ends on the device, so the smallest
``completion − device end`` over the runs is the offset (an upper bound,
within the callback latency of a fraction of a millisecond).

A :class:`Trace` round-trips through JSON (``to_json``/``from_json``), so
the reduction is tested on a small recorded trace without a chip.

Every reduction works inside the steady window of one device: from the
first to the last execution of the benchmark's own programs.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

#: the host annotations the harness writes around its own work
HOST_PREFIX = "bench."
OP_LINES = ("XLA Ops", "Async XLA Ops")


@dataclasses.dataclass
class Device:
    name: str
    #: ``[name, start_ns, dur_ns, run_id]`` per program execution
    modules: list
    #: ``[name, start_ns, dur_ns]`` per operation
    ops: list
    #: host clock − device clock, in ns
    offset_ns: float = 0.0


@dataclasses.dataclass
class Trace:
    devices: list
    #: ``[name, start_ns, dur_ns]`` per host annotation (host clock)
    host: list

    def to_json(self) -> dict:
        return {"devices": [dataclasses.asdict(d) for d in self.devices],
                "host": self.host}

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        return Trace([Device(**d) for d in obj["devices"]], obj["host"])


# ---------------------------------------------------------------- loading
def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def load(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(directory))
    devices, host, completions = [], [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = Device(plane.name, [], [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for e in line.events:
                        run = dict(e.stats).get("run_id")
                        dev.modules.append([e.name, float(e.start_ns),
                                            float(e.duration_ns), run])
                elif line.name in OP_LINES:
                    dev.ops.extend([e.name, float(e.start_ns),
                                    float(e.duration_ns)]
                                   for e in line.events)
            if dev.modules or dev.ops:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
                    elif e.name == "CompleteCallbacks":
                        st = dict(e.stats)
                        key = (st.get("device_ordinal"), st.get("run_id"))
                        completions[key] = float(e.start_ns)
    for dev in devices:
        dev.offset_ns = _clock_offset(dev, completions)
    devices.sort(key=lambda d: _ordinal(d.name))
    return Trace(devices, sorted(host, key=lambda h: h[1]))


def _ordinal(plane_name: str) -> int:
    m = re.search(r"(\d+)$", plane_name)
    return int(m.group(1)) if m else 0


def _clock_offset(dev: Device, completions: dict) -> float:
    ordinal = _ordinal(dev.name)
    gaps = [completions[(ordinal, run)] - (start + dur)
            for _, start, dur, run in dev.modules
            if (ordinal, run) in completions]
    return min(gaps) if gaps else 0.0


# -------------------------------------------------------------- reductions
def program_of(module_name: str) -> str:
    """``jit_bench_inverse(2758…)`` → ``bench_inverse``."""
    name = module_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def window(dev: Device, programs) -> tuple[float, float] | None:
    """First start to last end of the device's runs of ``programs``."""
    runs = [(s, s + d) for name, s, d, _ in dev.modules
            if program_of(name) in programs]
    if not runs:
        return None
    return min(r[0] for r in runs), max(r[1] for r in runs)


def merged(intervals, lo: float, hi: float) -> list:
    """Union of ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy(dev: Device, lo: float, hi: float) -> float:
    """ns in ``[lo, hi]`` in which some operation ran on the device."""
    return sum(e - s for s, e in merged(
        ((s, s + d) for _, s, d in dev.ops), lo, hi))


def occupancy(trace: Trace, programs) -> tuple[float, float] | None:
    """(busy_s, window_s), each the mean over the devices that ran
    ``programs``; None where none did."""
    pairs = []
    for dev in trace.devices:
        w = window(dev, programs)
        if w is not None:
            pairs.append((busy(dev, *w), w[1] - w[0]))
    if not pairs:
        return None
    n = len(pairs)
    return (sum(b for b, _ in pairs) / n / 1e9,
            sum(w for _, w in pairs) / n / 1e9)


def idle_share(trace: Trace, programs) -> float | None:
    """1 − busy/window in %, mean over the devices."""
    shares = []
    for dev in trace.devices:
        w = window(dev, programs)
        if w is not None and w[1] > w[0]:
            shares.append(1.0 - busy(dev, *w) / (w[1] - w[0]))
    return 100.0 * sum(shares) / len(shares) if shares else None


def calls(trace: Trace, program: str) -> list[list[float]]:
    """Per device, the device durations (ns) of each run of ``program``."""
    return [[d for name, _, d, _ in dev.modules
             if program_of(name) == program] for dev in trace.devices]


def mean_call_ns(trace: Trace, program: str) -> float | None:
    """Device time of one run of ``program``, mean over runs and devices."""
    durs = [d for per_dev in calls(trace, program) for d in per_dev]
    return sum(durs) / len(durs) if durs else None


def op_time_ns(trace: Trace, pattern: str, programs) -> float | None:
    """Device time of the operations whose name contains ``pattern``,
    inside the window; mean over the devices.  None where no operation
    matches on any device."""
    per_dev, seen = [], False
    for dev in trace.devices:
        w = window(dev, programs)
        if w is None:
            continue
        tot = 0.0
        for name, s, d in dev.ops:
            if pattern in name and s >= w[0] and s + d <= w[1]:
                tot += d
                seen = True
        per_dev.append(tot)
    if not seen:
        return None
    return sum(per_dev) / len(per_dev)


_OPCODE = re.compile(r"\)?\s([a-z][\w\-]*)\(")


def short_op(name: str) -> str:
    """``%fusion.2 = f32[…] fusion(…), kind=…`` → ``%fusion.2 fusion``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    m = _OPCODE.search(rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def top_ops(trace: Trace, programs, k: int = 10) -> list:
    """The ``k`` operations that took most device time in the window, as
    ``[program:op, seconds]`` (sum over runs, mean over devices)."""
    tot: dict[str, float] = {}
    ndev = 0
    for dev in trace.devices:
        w = window(dev, programs)
        if w is None:
            continue
        ndev += 1
        mods = sorted((s, s + d, program_of(n)) for n, s, d, _ in dev.modules)
        starts = [m[0] for m in mods]
        for name, s, d in dev.ops:
            if s < w[0] or s > w[1]:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = mods[i][2] if i >= 0 and s <= mods[i][1] else "?"
            key = f"{prog}:{short_op(name)}"
            tot[key] = tot.get(key, 0.0) + d
    if not ndev:
        return []
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / ndev / 1e9] for name, ns in ranked]


def host_label(trace: Trace, t_host: float) -> str:
    """The innermost ``bench.*`` annotation open at host time ``t_host``."""
    best = None
    for name, s, d in trace.host:
        if s <= t_host <= s + d and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "host:unannotated"


def idle_gaps(trace: Trace, programs, k: int = 10) -> list:
    """The ``k`` longest device-idle gaps inside the window, as
    ``[host annotation open at the gap's middle, seconds]``."""
    gaps = []
    for dev in trace.devices:
        w = window(dev, programs)
        if w is None:
            continue
        spans = merged(((s, s + d) for _, s, d in dev.ops), *w)
        edges = [w[0]] + [x for sp in spans for x in sp] + [w[1]]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b) + dev.offset_ns
                gaps.append((b - a, host_label(trace, mid)))
    gaps.sort(key=lambda g: -g[0])
    return [[label, ns / 1e9] for ns, label in gaps[:k]]


def save_json(trace: Trace, path: str) -> None:
    with open(path, "w") as fh:
        json.dump(trace.to_json(), fh)


def load_json(path: str) -> Trace:
    with open(path) as fh:
        return Trace.from_json(json.load(fh))
