"""The shape of a run's last lines, on a CPU-sized copy of each cell."""
from __future__ import annotations

import json

import jax
import pytest

from bench import run
from bench.tests.harness_testkit import tiny_registry



@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tiny_registry(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", ["fig9-sphere", "fig9-cube",
                                      "fig9-scf"])
def test_end_to_end_line(reg, workload, capsys):
    res = run.run_cell(workload, seed=2 ** 33 + 5, seconds=0.2, trace=False,
                       registry=reg, devices=jax.devices()[:1])
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    want = {m["name"]: m["unit"] for m in reg.end_to_end(workload)}
    assert {k: v["unit"] for k, v in res["metrics"].items()} == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    dev = res["device"]
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert dev["count"] == 1
    run.emit(res)
    out, err = capsys.readouterr()
    last = json.loads(out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(res))
    tail = err.strip().splitlines()[-len(res["checks"]):]
    for line, (name, c) in zip(tail, res["checks"].items()):
        assert line.startswith(f"check {name} value=")
        assert f"limit={c['limit']!r}" in line and line.endswith(" ok")


def test_traced_line_carries_the_breakdown(reg):
    res = run.run_cell("fig9-sphere", seed=11, seconds=0.2, trace=True,
                       registry=reg, devices=jax.devices()[:1])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # a CPU trace has no device plane: the readers find nothing to read
    # and their metrics are left out, never reported as 0
    assert res["metrics"] == {}
    assert list(res)[-1] == "checks"
