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
