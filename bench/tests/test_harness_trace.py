"""The trace reduction, on a small trace recorded on a TPU v5e.

``data/trace_1chip.json`` is a profiled window of three runs of a small
program named ``bench_inverse``; ``data/trace_4chip.json`` two steps of a
small ``bench_inverse``/``bench_forward`` pair with an all-to-all in
each, on a 2x2 host (``bench.trace.load`` of the ``.xplane.pb``, saved
with ``save_json``).  Every expected number below is worked out from the
files' own events by a second, plainer count.
"""
from __future__ import annotations

import os

import pytest

from bench import trace as bt
from bench.registry import Registry

DATA = os.path.join(os.path.dirname(__file__), "data")
PROGS = ("bench_inverse", "bench_forward")


@pytest.fixture(scope="module")
def tr():
    return bt.load_json(os.path.join(DATA, "trace_1chip.json"))


@pytest.fixture(scope="module")
def tr4():
    return bt.load_json(os.path.join(DATA, "trace_4chip.json"))


def _union_ns(intervals):
    """Busy ns by walking every ns-boundary: a second, slow count."""
    pts = sorted({p for s, e in intervals for p in (s, e)})
    busy = 0.0
    for a, b in zip(pts, pts[1:]):
        mid = 0.5 * (a + b)
        if any(s <= mid < e for s, e in intervals):
            busy += b - a
    return busy


def test_window_spans_the_program_runs(tr):
    dev = tr.devices[0]
    lo, hi = bt.window(dev, PROGS)
    starts = [m[1] for m in dev.modules]
    ends = [m[1] + m[2] for m in dev.modules]
    assert (lo, hi) == (min(starts), max(ends))
    assert bt.window(dev, ("no_such_program",)) is None


def test_idle_share_is_one_minus_the_union(tr):
    dev = tr.devices[0]
    lo, hi = bt.window(dev, PROGS)
    spans = [(max(s, lo), min(s + d, hi)) for _, s, d in dev.ops
             if min(s + d, hi) > max(s, lo)]
    want = 100.0 * (1.0 - _union_ns(spans) / (hi - lo))
    assert bt.idle_share(tr, PROGS) == pytest.approx(want, rel=1e-12)
    busy_s, window_s = bt.occupancy(tr, PROGS)
    assert window_s == pytest.approx((hi - lo) / 1e9)
    assert busy_s == pytest.approx(_union_ns(spans) / 1e9)
    assert 0 < busy_s <= window_s


def test_merged_overlaps_and_clips():
    got = bt.merged([(5, 9), (0, 2), (1, 4), (8, 12), (20, 30)], 1, 25)
    assert got == [[1, 4], [5, 12], [20, 25]]


def test_program_device_time_per_call(tr):
    durs = [m[2] for m in tr.devices[0].modules]
    assert len(durs) == 3
    assert bt.mean_call_ns(tr, "bench_inverse") == pytest.approx(
        sum(durs) / 3)
    assert bt.mean_call_ns(tr, "bench_forward") is None


def test_program_names_and_op_labels():
    assert bt.program_of("jit_bench_inverse(2758791090982678373)") == \
        "bench_inverse"
    name = ("%fusion.2 = f32[32,256,256,128]{3,2,1,0:T(8,128)} fusion("
            "f32[32,256,256,256]{3,2,1,0:T(8,128)} %custom-call), "
            "kind=kOutput")
    assert bt.short_op(name) == "%fusion.2 fusion"


def test_top_ops_sum_the_program_ops(tr):
    top = bt.top_ops(tr, PROGS, k=100)
    total = sum(d for _, d in top)
    dev = tr.devices[0]
    lo, hi = bt.window(dev, PROGS)
    want = sum(d for _, s, d in dev.ops if lo <= s <= hi) / 1e9
    assert total == pytest.approx(want)
    assert all(name.startswith("bench_inverse:") for name, _ in top)
    assert [d for _, d in top] == sorted((d for _, d in top), reverse=True)


def test_idle_gaps_are_labelled_by_host_annotations(tr):
    gaps = bt.idle_gaps(tr, PROGS, k=3)
    assert len(gaps) == 3
    assert all(label.startswith("bench.") for label, _ in gaps)
    busy_s, window_s = bt.occupancy(tr, PROGS)
    all_gaps = bt.idle_gaps(tr, PROGS, k=10 ** 6)
    assert sum(s for _, s in all_gaps) == pytest.approx(window_s - busy_s)


def test_collective_time_per_device():
    """Two devices, each running two steps with all-to-all ops inside."""
    def dev(name, a2a_durs):
        mods = [["jit_bench_inverse(1)", 0.0, 100.0, 1],
                ["jit_bench_forward(2)", 100.0, 100.0, 2],
                ["jit_bench_inverse(1)", 250.0, 100.0, 3],
                ["jit_bench_forward(2)", 350.0, 100.0, 4]]
        ops = [["%all-to-all.1 = c64[8] all-to-all(c64[8] %x)", 10.0,
                a2a_durs[0]],
               ["%fusion = f32[8] fusion(f32[8] %y)", 40.0, 50.0],
               ["%all-to-all.2 = c64[8] all-to-all(c64[8] %z)", 260.0,
                a2a_durs[1]],
               ["%copy = f32[8] copy(f32[8] %y)", 400.0, 40.0]]
        return bt.Device(name, mods, ops)
    tr = bt.Trace([dev("/device:TPU:0", (20.0, 30.0)),
                   dev("/device:TPU:1", (40.0, 10.0))], [])
    assert bt.op_time_ns(tr, "all-to-all", PROGS) == pytest.approx(50.0)
    assert bt.op_time_ns(tr, "all-gather", PROGS) is None
    # busy: [10,30] ∪ [40,90] ∪ [260,290] ∪ [400,440] on device 0
    assert bt.idle_share(tr, PROGS) == pytest.approx(
        100 * (1 - (0.5 * (140 + 130)) / 450))


def test_recorded_all_to_all_time_per_device(tr4):
    assert [d.name for d in tr4.devices] == [f"/device:TPU:{i}"
                                             for i in range(4)]
    per_dev = []
    for dev in tr4.devices:
        lo, hi = bt.window(dev, PROGS)
        a2a = [d for name, s, d in dev.ops
               if "all-to-all" in name and lo <= s and s + d <= hi]
        assert len(a2a) == 8            # 2 steps × 2 programs × 2 moves
        per_dev.append(sum(a2a))
    want = sum(per_dev) / 4
    assert bt.op_time_ns(tr4, "all-to-all", PROGS) == pytest.approx(want)
    runs = bt.calls(tr4, "bench_inverse")
    assert [len(r) for r in runs] == [2, 2, 2, 2]
    reader = Registry().metric_reader("a2a_ms")
    assert reader.read(tr4, {"programs": PROGS}) == pytest.approx(
        want / 2 / 1e6)                 # per step, in ms


def test_recorded_idle_share_is_the_mean_over_devices(tr4):
    shares = []
    for dev in tr4.devices:
        lo, hi = bt.window(dev, PROGS)
        spans = [(max(s, lo), min(s + d, hi)) for _, s, d in dev.ops
                 if min(s + d, hi) > max(s, lo)]
        shares.append(1.0 - _union_ns(spans) / (hi - lo))
    assert bt.idle_share(tr4, PROGS) == pytest.approx(
        100.0 * sum(shares) / 4)
    # every device's clock offset is set from the completion callbacks
    assert all(dev.offset_ns > 0 for dev in tr4.devices)


def test_json_round_trip(tr, tmp_path):
    path = str(tmp_path / "t.json")
    bt.save_json(tr, path)
    again = bt.load_json(path)
    assert again.to_json() == tr.to_json()
