"""Unified observability layer: tracer, metrics registry, instrumentation.

The contracts under test, in the order the layer makes them:

* disabled tracing is free — ``span()`` returns a shared no-op singleton
  (no allocation) and instrumented paths record nothing;
* enabled spans nest per thread with correct depth/parent, and the
  Chrome-trace export is valid, Perfetto-shaped JSON;
* ``timed_call`` blocks on the result before stopping the clock (the
  wall-clock honesty rule the benchmark audit enforces);
* percentile/reservoir math is safe on empty and single-sample windows,
  and ``ServiceMetrics`` storage is bounded;
* the registry's probes expose the legacy counters (FftPlan.executions,
  PlanCache.stats, PERK_LINALG_CALLS) without changing their APIs;
* traced plan execution returns the same values as untraced execution,
  with one ``plan:`` span around the same compiled program, whose stages
  carry their ``fftb.line_dft`` scopes; enabled spans reach a
  ``jax.profiler`` trace's host plane; and the instrumented SCF loop
  reports per-iteration records.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.obs.metrics import (MetricsRegistry, Reservoir, diff_snapshot,
                               global_metrics, percentile,
                               register_weak_probe)
from repro.obs.trace import NOOP_SPAN, Tracer, get_tracer, timed_call


@pytest.fixture(autouse=True)
def _quiet_global_tracer():
    """Tests drive the global tracer explicitly; leave it off afterwards."""
    yield
    get_tracer().disable()
    get_tracer().clear()


# ------------------------------------------------------------------ tracer
def test_disabled_span_is_shared_noop_singleton():
    tr = Tracer()
    assert not tr.enabled
    assert tr.span("a") is tr.span("b") is NOOP_SPAN
    with tr.span("outer", key=1) as sp:
        assert sp.sync(42) == 42         # passthrough, no recording
        sp.set(more=2)
    tr.event("e", 0.0, 1.0)
    tr.instant("i")
    assert tr.events() == []


def test_disabled_overhead_no_allocation():
    """The disabled fast path allocates no span objects at all."""
    tr = Tracer()
    spans = [tr.span(f"s{i}") for i in range(100)]
    assert all(s is NOOP_SPAN for s in spans)


def test_spans_nest_with_depth_and_parent():
    tr = Tracer().enable(sync=False)
    with tr.span("outer"):
        with tr.span("inner"):
            with tr.span("leaf", tag="x"):
                pass
    evs = {e["name"]: e for e in tr.events()}
    assert evs["outer"]["depth"] == 0 and evs["outer"]["parent"] is None
    assert evs["inner"]["depth"] == 1 and evs["inner"]["parent"] == "outer"
    assert evs["leaf"]["depth"] == 2 and evs["leaf"]["parent"] == "inner"
    assert evs["leaf"]["attrs"] == {"tag": "x"}
    # recorded leaf-first (exit order), every t1 >= t0
    assert all(e["t1"] >= e["t0"] for e in tr.events())


def test_threads_nest_independently():
    tr = Tracer().enable(sync=False)
    errs = []
    # all four threads hold their spans open together: a thread that ended
    # before the next one started would hand its id on
    alive = threading.Barrier(4)

    def work(i):
        try:
            with tr.span(f"outer{i}"):
                with tr.span(f"inner{i}"):
                    alive.wait(timeout=30)
        except Exception as e:            # pragma: no cover - diagnostics
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    evs = tr.events()
    assert len(evs) == 8
    for i in range(4):
        inner = next(e for e in evs if e["name"] == f"inner{i}")
        # each thread's inner span nests under ITS OWN outer, depth 1 —
        # cross-thread spans never pollute another thread's stack
        assert inner["depth"] == 1 and inner["parent"] == f"outer{i}"
    assert len({e["tid"] for e in evs}) == 4


def test_ring_buffer_bounds_and_dropped_counter():
    tr = Tracer(max_events=4).enable(sync=False)
    for i in range(10):
        tr.instant(f"m{i}")
    assert len(tr.events()) == 4
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["m6", "m7", "m8", "m9"]


def test_chrome_export_is_valid_perfetto_json(tmp_path):
    tr = Tracer().enable(sync=False)
    with tr.span("outer", bytes=8192):
        with tr.span("inner"):
            pass
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    with open(path) as f:
        d = json.load(f)                   # round-trips as strict JSON
    assert d["displayTimeUnit"] == "ms"
    evs = [e for e in d["traceEvents"] if e.get("ph") == "X"]
    meta = [e for e in d["traceEvents"] if e.get("ph") == "M"]
    assert meta and meta[0]["name"] == "thread_name"
    assert {e["name"] for e in evs} == {"outer", "inner"}
    for e in evs:
        assert e["ts"] >= 0 and e["dur"] >= 0          # µs, non-negative
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    inner = next(e for e in evs if e["name"] == "inner")
    outer = next(e for e in evs if e["name"] == "outer")
    assert inner["args"]["parent"] == "outer"
    assert outer["args"]["bytes"] == 8192
    # time containment: Perfetto nests inner under outer on the same tid
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert d["otherData"]["dropped_events"] == 0


def test_summary_rollup():
    tr = Tracer().enable(sync=False)
    for _ in range(3):
        with tr.span("a"):
            pass
    with tr.span("b"):
        pass
    s = tr.summary()
    assert s["a"]["count"] == 3 and s["b"]["count"] == 1
    assert s["a"]["total_ms"] >= 0.0


# ------------------------------------------------- wall-clock honesty audit
class _SlowResult:
    """Duck-typed device value whose drain takes a visible amount of time.

    ``jax.block_until_ready`` calls ``block_until_ready()`` on objects
    that expose it, so a naive timer (stop the clock at dispatch) reads
    ~0 while the honest one reads >= the sleep.
    """

    def __init__(self, delay):
        self.delay = delay

    def block_until_ready(self):
        time.sleep(self.delay)
        return self


def test_timed_call_blocks_before_stopping_clock():
    out, seconds = timed_call(lambda: _SlowResult(0.05))
    assert isinstance(out, _SlowResult)
    assert seconds >= 0.05, (
        f"timed_call stopped the clock after {seconds * 1e3:.1f} ms — it "
        "measured dispatch, not execution")


def test_span_sync_blocks_at_exit():
    tr = Tracer().enable(sync=True)
    with tr.span("work") as sp:
        sp.sync(_SlowResult(0.05))
    (ev,) = tr.events()
    assert ev["t1"] - ev["t0"] >= 0.05


# ----------------------------------------------------------------- metrics
def test_percentile_empty_and_single_sample():
    assert percentile([], 99) == 0.0
    assert percentile([7.0], 50) == 7.0
    assert percentile([7.0], 99) == 7.0
    assert percentile([1.0, 3.0], 50) == pytest.approx(2.0)
    xs = list(np.random.default_rng(0).standard_normal(101))
    assert percentile(xs, 50) == pytest.approx(
        float(np.percentile(np.asarray(xs), 50)))
    assert percentile(xs, 99) == pytest.approx(
        float(np.percentile(np.asarray(xs), 99)))


def test_reservoir_bounds_window_keeps_alltime_count():
    r = Reservoir(maxlen=4)
    for i in range(10):
        r.record(float(i))
    assert len(r) == 4
    assert r.count == 10                   # all-time, survives wraparound
    assert r.values() == [6.0, 7.0, 8.0, 9.0]


def test_registry_instruments_and_snapshot():
    m = MetricsRegistry()
    m.counter("c").inc()
    m.counter("c").inc(2)
    m.gauge("g").set(1.5)
    for v in (1.0, 2.0, 3.0):
        m.histogram("h").record(v)
    snap = m.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h"]["p50"] == pytest.approx(2.0)
    json.dumps(snap)                       # JSON-safe by construction


def test_probe_errors_are_contained():
    m = MetricsRegistry()

    def bad():
        raise RuntimeError("boom")

    m.register_probe("bad", bad)
    m.register_probe("good", lambda: {"x": 1})
    snap = m.snapshot()
    assert snap["good"] == {"x": 1}
    assert "error" in snap["bad"]          # never raises out of snapshot


def test_diff_snapshot_numeric_leaves():
    before = {"counters": {"c": 3}, "nested": {"a": 1.0, "s": "x"}}
    after = {"counters": {"c": 10}, "nested": {"a": 4.0, "s": "y"},
             "new": {"k": 2}}
    d = diff_snapshot(before, after)
    assert d["counters"]["c"] == 7
    assert d["nested"]["a"] == pytest.approx(3.0)
    assert d["nested"]["s"] == "y"         # non-numeric: keep after
    assert d["new"]["k"] == 2


def test_weak_probe_dies_with_object():
    m = MetricsRegistry()

    class Obj:
        def summary(self):
            return {"alive": True}

    o = Obj()
    register_weak_probe(m, "obj", o)
    assert m.snapshot()["obj"] == {"alive": True}
    del o
    import gc
    gc.collect()
    assert "obj" not in m.snapshot()       # dead probes drop out


# ------------------------------------------------- legacy counters as probes
def test_global_registry_carries_legacy_probes():
    # importing the instrumented layers registers their probes
    from repro.core import cache, plan  # noqa: F401
    from repro.dft import hamiltonian  # noqa: F401
    snap = global_metrics().snapshot()
    assert {"executions", "searches"} <= set(snap["fftb"])
    assert {"hits", "misses", "builds", "build_seconds"} <= \
        set(snap["plan_cache"])
    assert "per_k_linalg_calls" in snap["dft"]


def test_plan_cache_stats_gain_build_accounting():
    from repro.core import PlanCache
    c = PlanCache()
    c.get_or_build("k", lambda: object())
    s = c.stats
    assert s["builds"] == 1 and s["build_seconds"] >= 0.0
    c.clear()
    assert c.stats["builds"] == 0


# ------------------------------------------------------- traced == untraced
def test_traced_plan_execution_matches_untraced():
    import jax.numpy as jnp
    from repro.core import Domain, ProcGrid, fftb
    tr = get_tracer()
    g = ProcGrid.create([1])
    dom = Domain((0, 0, 0), (7, 7, 7))
    fx = fftb("x{0} y z -> X Y Z{0}", domains=dom, grid=g, sizes=(8, 8, 8))
    rng = np.random.default_rng(3)
    x = jnp.asarray((rng.standard_normal((8, 8, 8))
                     + 1j * rng.standard_normal((8, 8, 8))
                     ).astype(np.complex64))
    ref = np.asarray(fx(x))
    tr.enable(sync=True)
    traced = np.asarray(fx(x))
    tr.disable()
    np.testing.assert_array_equal(traced, ref)
    # one span around the plan's one compiled program: no per-stage path
    spans = [e for e in tr.events() if e["name"].startswith("plan:")]
    assert len(spans) == 1 and spans[0]["attrs"]["stages"] == len(fx.stages)
    # the line-DFT stages are named inside that program instead, one
    # scope each (the size-1 all-to-all compiles away here)
    hlo = fx._fn_for(fx.policy).lower(x).compile().as_text()
    dims = [st.dim for st in fx.stages if hasattr(st, "dim")]
    assert len(dims) == 3
    for dim in dims:
        assert f'op_name="jit(_raw_apply)/fftb.line_dft/{dim}/' in hlo


def test_enabled_span_reaches_the_profiler_host_plane(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = Tracer().enable(sync=False)
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("obs.one_clock"):
        time.sleep(0.001)
    with Tracer().span("obs.disabled"):     # a disabled tracer: no event
        pass
    jax.profiler.stop_trace()
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(pb[0])
    host = [e.name for p in data.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    assert host.count("obs.one_clock") == 1
    assert "obs.disabled" not in host
    assert tr.events()[0]["name"] == "obs.one_clock"


def test_scf_iteration_records():
    from repro.core import ProcGrid
    from repro.dft import SCFConfig, run_scf
    cfg = SCFConfig(n=8, nbands=2, kpts=((0, 0, 0),), max_iter=3,
                    e_tol=0.0, r_tol=0.0)     # run exactly max_iter sweeps
    res = run_scf(cfg, grid=ProcGrid.create([1]))
    recs = res.iteration_records
    assert len(recs) == res.iterations
    for i, r in enumerate(recs):
        assert r["iteration"] == i
        assert r["seconds"] >= 0.0 and r["transforms"] > 0
        assert np.isfinite(r["energy"]) and np.isfinite(r["residual"])
    assert sum(r["transforms"] for r in recs) == res.transforms


def test_service_metrics_bounded_storage():
    from repro.serve.metrics import ServiceMetrics
    m = ServiceMetrics(max_samples=8)
    for i in range(100):
        m.record_request("t", latency_s=i * 1e-3, nbands=1,
                         queue_wait_s=i * 1e-4)
    m.record_dispatch(2, 2, 0.25)
    m.record_dispatch(1, 1, 0.75)
    for _ in range(50):
        m.record_dispatch(1, 1, 0.0)       # wrap the padding window
    s = m.summary()
    assert s["requests"] == 100            # all-time count
    assert s["per_tenant"]["t"]["requests"] == 100
    assert len(m._lat["t"]) == 8           # storage stays bounded
    assert s["padding_fraction_max"] == 0.75   # max survives wraparound
    assert s["queue_wait_p99_ms"] > 0.0
    # empty + single-sample windows never divide by zero
    e = ServiceMetrics()
    se = e.summary()
    assert se["latency_p99_ms"] == 0.0 and se["padding_fraction_max"] == 0.0
    e.record_request("x", 0.002, 1)
    assert e.summary()["latency_p50_ms"] == pytest.approx(2.0)
