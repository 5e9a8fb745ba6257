"""Everything is found by name, and a new cell needs new files only."""
from __future__ import annotations

import json
import os
import re

import jax
import pytest

from bench.registry import Registry
from bench.run import run_cell
from bench.tests.harness_testkit import tiny_registry

REG = Registry()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_cell_resolves():
    bench = REG.benchmark
    for cell in bench["workloads"]:
        cfg = REG.config(cell["config"])
        mix = REG.traffic(cell["traffic"])
        kind = REG.kind(mix["kind"])
        for attr in ("build", "required_work", "window_metrics", "PROGRAMS",
                     "CHECKS"):
            assert hasattr(kind, attr), (mix["kind"], attr)
        assert set(kind.CHECKS) <= set(REG.limits(cell["name"]))
        names = {m["name"] for m in REG.end_to_end(cell["name"])}
        own = set(kind.window_metrics(2, 1, 1.0))
        assert own | {"hbm_gib", "setup_s"} == names
        assert REG.per_layer(cell["name"]), cell["name"]
        assert cfg["precision"] in ("highest", "high", "default")


def test_every_per_layer_metric_has_a_reader():
    for m in REG.benchmark["per_layer"]:
        assert callable(REG.metric_reader(m["name"]).read), m["name"]


def test_every_layer_roofline_has_its_layers_work():
    """A ``<layer>_roofline`` metric reads the kind's ``scope_work``: every
    cell it lists runs a kind that counts its layers' work."""
    for m in REG.benchmark["per_layer"]:
        if "_roofline" not in m["name"]:
            continue
        for cell in m["workloads"]:
            mix = REG.traffic(REG.workload(cell)["traffic"])
            kind = REG.kind(mix["kind"])
            work = kind.scope_work(REG.config(REG.workload(cell)["config"]),
                                   mix)
            assert work and all(f >= 0 and b > 0 for f, b in work.values())


def test_benchmark_file_keeps_the_contract_shapes():
    bench = REG.benchmark
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in bench["configs"]:
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(REG.root, c["file"]))
        listed = json.load(open(os.path.join(REG.root, c["file"])))
        assert sorted(c["reduced"]) == sorted(listed["reduced"])
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_unknown_names_fail_loudly():
    with pytest.raises(KeyError):
        REG.workload("no-such-cell")
    with pytest.raises(KeyError):
        REG.config("no-such-config")
    with pytest.raises(FileNotFoundError):
        REG.kind("no_such_kind")
    with pytest.raises(KeyError, match="peaks.json"):
        REG.peaks("TPU v99 imaginary")
    assert REG.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_new_cell_is_new_files_only(tmp_path):
    """A cell of its own traffic mix and metric: files and one entry."""
    reg = tiny_registry(tmp_path)
    bench_dir = reg.bench_dir
    cfg = reg.config("fig9-planewave")
    cfg.update(name="tiny-planewave", nbands={"1": 1})
    with open(os.path.join(bench_dir, "configs", "tiny-planewave.json"),
              "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(bench_dir, "traffic", "sphere-2x1.json"),
              "w") as fh:
        json.dump({"kind": "sphere_roundtrip", "grid": [1],
                   "batch_axes": [], "fft_axes": [0]}, fh)
    with open(os.path.join(bench_dir, "limits", "tiny-sphere.json"),
              "w") as fh:
        json.dump({"limits": {"inverse_err": 1e-6,
                              "roundtrip_err": 1e-6}}, fh)
    with open(os.path.join(bench_dir, "metrics", "steps_seen.py"),
              "w") as fh:
        fh.write("def read(tr, info):\n    return 1.0\n")
    path = os.path.join(reg.root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-planewave",
                             "source": "https://arxiv.org/abs/2406.05577",
                             "file": "bench/configs/tiny-planewave.json",
                             "reduced": ["nbands"],
                             "why": "one band of each k-point"})
    bench["workloads"].append({"name": "tiny-sphere",
                               "config": "tiny-planewave",
                               "traffic": "sphere-2x1", "chips": 1,
                               "why": "one band of each k-point"})
    for m in bench["end_to_end"]:
        if m["name"] == "roundtrip_rate":
            m["workloads"].append("tiny-sphere")
    bench["per_layer"].append({"name": "steps_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "device", "moves": "roundtrip_rate",
                               "workloads": ["tiny-sphere"]})
    json.dump(bench, open(path, "w"))
    reg = Registry(reg.root)
    res = run_cell("tiny-sphere", seed=7, seconds=0.2, trace=False,
                   registry=reg, devices=jax.devices()[:1])
    assert res["correct"]
    assert set(res["metrics"]) == {"roundtrip_rate", "hbm_gib", "setup_s"}
    assert res["attempted"] % 2 == 0
    res = run_cell("tiny-sphere", seed=7, seconds=0.2, trace=True,
                   registry=reg, devices=jax.devices()[:1])
    assert res["metrics"]["steps_seen"] == {"value": 1.0, "unit": "1"}


@pytest.mark.parametrize("name", ["idle_share.transform", "idle_share.scf"])
def test_split_metric_shares_its_reader(name):
    """``<metric>.<split>`` falls back to ``metrics/<metric>.py``."""
    assert REG.metric_reader(name).__file__ == \
        REG.metric_reader("idle_share").__file__


PROBE_KIND = '''"""A kind whose one program names a layer no other kind has."""
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import common

PROGRAMS = ("bench_probe",)
CHECKS = ("probe_err",)


def window_metrics(units, steps, seconds):
    return {"roundtrip_rate": units / seconds}


def required_work(config, params):
    return 1.0, 1.0


def scope_work(config, params):
    return {"scf.probe": (0.0, 8.0 * config["n"] ** 2)}


class Cell:
    def __init__(self, ctx):
        n = ctx.config["n"]

        def bench_probe(x):
            with jax.named_scope("scf.probe"):
                return jnp.sin(x) * 2.0

        self.prog = common.compile_program(
            "bench_probe", bench_probe,
            jax.ShapeDtypeStruct((n, n), jnp.float32))
        self.programs = {"bench_probe": self.prog}
        self.units_per_step = 1
        self.x = jnp.ones((n, n), jnp.float32)
        self.y = None

    def step(self, i):
        self.y = self.prog(self.x)
        self.y.block_until_ready()

    def warm(self):
        t = time.perf_counter()
        self.step(-1)
        return time.perf_counter() - t

    def release(self):
        # as the SCF kind does: the compiled programs go with the state
        held = {"y": np.asarray(self.y), "x": np.asarray(self.x)}
        self.programs = {}
        return held

    def readings(self, held):
        want = 2.0 * np.sin(held["x"])
        return {"probe_err": float(np.abs(held["y"] - want).max())}


def build(ctx):
    return Cell(ctx)
'''


class SpyRegistry(Registry):
    """Keeps what the harness hands each per-layer reader."""

    def __init__(self, root):
        super().__init__(root)
        self.handed = {}

    def metric_reader(self, name):
        reader = super().metric_reader(name)
        handed = self.handed

        class Spy:
            @staticmethod
            def read(tr, info):
                handed[name] = info
                return reader.read(tr, info)
        return Spy


def test_a_new_layer_is_new_files_only(tmp_path):
    """A kind with a layer of its own, its time reader and its roofline
    share: files and entries only.  A traced run hands the readers the
    scope map of every program the kind runs and the kind's work per
    layer; the readers then read the layer from a trace."""
    from bench import scopes
    from bench.tests.test_harness_scopes import (SCF, _fixture, _has,
                                                 _plain_scope_ns, _steps)

    reg = tiny_registry(tmp_path)
    bench_dir = reg.bench_dir
    files = {"kinds/probe_layer.py": PROBE_KIND,
             "traffic/probe.json": json.dumps({"kind": "probe_layer"}),
             "limits/tiny-probe.json": json.dumps(
                 {"limits": {"probe_err": 1e-5}}),
             "metrics/probe_ms.py":
                 "from bench import scopes\n\n\ndef read(tr, info):\n"
                 "    return scopes.per_step_ms(tr, info, 'scf.probe')\n",
             "metrics/probe_roofline.py":
                 "from bench import scopes\n\n\ndef read(tr, info):\n"
                 "    return scopes.roofline_share(tr, info, 'scf.probe')\n"}
    for rel, text in files.items():
        with open(os.path.join(bench_dir, rel), "w") as fh:
            fh.write(text)
    path = os.path.join(reg.root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["workloads"].append({"name": "tiny-probe",
                               "config": "fig9-planewave",
                               "traffic": "probe", "chips": 1,
                               "why": "one program, one new layer"})
    for m in bench["end_to_end"]:
        if m["name"] == "roundtrip_rate":
            m["workloads"].append("tiny-probe")
    for name, unit, better in (("probe_ms", "ms/step", "lower"),
                               ("probe_roofline", "%", "higher")):
        bench["per_layer"].append({
            "name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": "probe",
            "moves": "roundtrip_rate", "workloads": ["tiny-probe"]})
    json.dump(bench, open(path, "w"))

    reg = SpyRegistry(reg.root)
    res = run_cell("tiny-probe", seed=5, seconds=0.2, trace=True,
                   registry=reg, devices=jax.devices()[:1])
    assert res["correct"]
    assert set(reg.handed) == {"probe_ms", "probe_roofline"}
    info = reg.handed["probe_roofline"]
    kind = reg.kind("probe_layer")
    assert set(info["scopes"]) == set(kind.PROGRAMS)
    assert any("scf.probe" in scopes.components(p)
               for p in info["scopes"]["bench_probe"].values())
    assert info["scope_work"] == {"scf.probe": (0.0, 8.0 * 16 ** 2)}
    # a CPU trace has no device plane: both are left out, not read as 0
    assert res["metrics"] == {}

    # on a trace from the chip the new readers read the layer: the
    # recorded SCF step's Hartree ops stand in for it, renamed
    tr, table = _fixture("trace_scoped_scf_1chip.json")
    renamed = {prog: {ins: path.replace("scf.hartree", "scf.probe")
                      for ins, path in m.items()}
               for prog, m in table.items()}
    info = dict(info, programs=SCF, scopes=renamed, units_per_step=1,
                peaks=REG.peaks("TPU v5 lite"), log=lambda msg: None)
    got_ms = reg.metric_reader("probe_ms").read(tr, info)
    want_ms = (_plain_scope_ns(tr, table, SCF, _has("scf.hartree"))
               / _steps(tr, SCF[0]) / 1e6)
    assert got_ms is not None and got_ms > 0
    assert got_ms == pytest.approx(want_ms, rel=1e-9)
    share = reg.metric_reader("probe_roofline").read(tr, info)
    least_ms = 8.0 * 16 ** 2 / info["peaks"]["hbm_bytes_per_s"] * 1e3
    assert share == pytest.approx(100.0 * least_ms / want_ms, rel=1e-9)
    assert 0 < share < 100
