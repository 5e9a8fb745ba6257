"""Device time by the program's layer names (``bench/scopes.py``), and
each layer's share of its roofline.

The attribution is checked on hand-made HLO text and traces, where the
answer is known by construction, and on two small traces recorded on a
TPU v5e together with the scope maps of the same compiled programs:
``data/trace_scoped_1chip.json`` (a sphere pair at n=64, d=32, 2 k-points
× 4 bands, three steps) and ``data/trace_scoped_scf_1chip.json`` (the
jitted SCF step at n=32, d=16, 2 k-points × 2 bands, two iterations); op
names there are cut after the opcode, and scope paths end before the
primitive's name.  Every expected reading of the recorded traces is
worked out again by a plainer count.
"""
from __future__ import annotations

import json
import os

import pytest

from bench import scopes
from bench import trace as bt
from bench.registry import Registry
from bench.tests.harness_testkit import run_child

DATA = os.path.join(os.path.dirname(__file__), "data")
SPHERE = ("bench_inverse", "bench_forward")
SCF = ("bench_scf_step",)


def _fixture(name):
    with open(os.path.join(DATA, name)) as fh:
        obj = json.load(fh)
    return bt.Trace.from_json(obj["trace"]), obj["scopes"]


@pytest.fixture(scope="module")
def sphere():
    return _fixture("trace_scoped_1chip.json")


@pytest.fixture(scope="module")
def scf():
    return _fixture("trace_scoped_scf_1chip.json")


def _plain_union(intervals):
    """Covered ns by walking the sorted boundaries: a second, slow count."""
    pts = sorted({p for s, e in intervals for p in (s, e)})
    return sum(b - a for a, b in zip(pts, pts[1:])
               if any(s <= 0.5 * (a + b) < e for s, e in intervals))


def _plain_scope_ns(tr, table, programs, want):
    """Per device: the program each op starts inside, its path, a union
    by boundaries; mean over the devices that ran ``programs``."""
    per_dev = []
    for dev in tr.devices:
        runs = [(bt.program_of(n), s, s + d) for n, s, d, _ in dev.modules]
        mine = [r for r in runs if r[0] in programs]
        if not mine:
            continue
        lo, hi = min(r[1] for r in mine), max(r[2] for r in mine)
        spans = []
        for name, s, d in dev.ops:
            if not lo <= s <= hi:
                continue
            prog = next((p for p, a, b in runs if a <= s <= b), None)
            path = table.get(prog, {}).get(name.split(" = ")[0], "")
            if want(path):
                spans.append((s, min(s + d, hi)))
        per_dev.append(_plain_union(spans))
    return sum(per_dev) / len(per_dev)


# ------------------------------------------------------------ HLO names
def test_op_scopes_of_a_compiled_program_with_two_scopes():
    import jax
    import jax.numpy as jnp

    def two(x):
        with jax.named_scope("fftb.unpack"):
            y = jnp.sin(x) * 2.0
        with jax.named_scope("fftb.line_dft"), jax.named_scope("x"):
            return jnp.dot(y, y.T)

    compiled = jax.jit(two).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    table = scopes.op_scopes(compiled)
    text = compiled.as_text()
    # every instruction of the module is in the map
    for line in text.splitlines():
        if " = " in line and line.startswith("  "):
            name = line.split(" = ")[0].strip().removeprefix("ROOT ")
            assert name in table
    paths = set(table.values())
    assert any("fftb.unpack" in scopes.components(p) for p in paths)
    assert any(p.endswith("fftb.line_dft/x/dot_general") for p in paths)


HLO = """HloModule jit_demo

%fused (p0: f32[8], p1: s32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %tr = f32[8]{0} transpose(%p0), metadata={op_name="j/scf.subspace/tr"}
  ROOT %sc = f32[8]{0} scatter(%tr, %p1), metadata={op_name=""}
}

%inner (q0: f32[8]) -> f32[8] {
  %q0 = f32[8]{0} parameter(0)
  %rs = f32[8]{0} reshape(%q0), metadata={op_name="j/fftb.pack/reshape"}
  ROOT %ng = f32[8]{0} negate(%rs)
}

%body (b: f32[8]) -> f32[8] {
  %b = f32[8]{0} parameter(0)
  ROOT %rot = f32[8]{0} copy(%b)
}

%cond (c: f32[8]) -> pred[] {
  %c = f32[8]{0} parameter(0)
  ROOT %t = pred[] constant(true)
}

ENTRY %main (x: f32[8], i: s32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %i = s32[8]{0} parameter(1), metadata={op_name="i"}
  %clamp = s32[8]{0} copy(%i)
  %fusion = f32[8]{0} fusion(%x, %clamp), kind=kCustom, calls=%fused
  %gte = f32[8]{0} copy(%fusion)
  %slice = f32[8]{0} slice(%gte), metadata={op_name="j/fftb.unpack/slice"}
  %while = f32[8]{0} while(%slice), condition=%cond, body=%body,
  %fusion.1 = f32[8]{0} fusion(%while), kind=kLoop, calls=%inner
  ROOT %copy-done = f32[8]{0} copy(%fusion.1)
}
""".replace("body=%body,", 'body=%body, metadata={op_name="j/scf.subspace"}')


def test_unnamed_instructions_take_the_names_around_them():
    table = scopes.scopes_from_text(HLO)
    # an unnamed fusion takes its first named user's name, through
    # unnamed users: not the operand layout fused into it
    assert table["%fusion"] == "j/fftb.unpack/slice"
    assert table["%clamp"] == "j/fftb.unpack/slice"
    # with no named user, the most common name inside it
    assert table["%fusion.1"] == "j/fftb.pack/reshape"
    # a while body's operations: the name of the while that runs them
    assert table["%rot"] == "j/scf.subspace"
    assert table["%while"] == "j/scf.subspace"
    # nothing around it names it: no path, and an argument's own name
    # (``x``) is no layer
    assert table["%copy-done"] == ""
    assert not scopes.is_layer(table["%x"])
    assert scopes.is_layer(table["%fusion"])


# --------------------------------------------------------- attribution
def _toy_trace():
    """Two programs that share the instruction name ``%fusion``; in the
    first a ``while`` encloses its body's operations and an async copy
    overlaps a fusion."""
    mods = [["jit_bench_inverse(1)", 0.0, 100.0, 1],
            ["jit_bench_forward(2)", 100.0, 50.0, 2]]
    ops = [["%fusion = f32[8] fusion(…)", 0.0, 40.0],
           ["%copy-start = (f32[8]) copy-start(…)", 30.0, 30.0],
           ["%while = f32[8] while(…)", 50.0, 50.0],
           ["%rot = f32[8] copy(…)", 55.0, 10.0],
           ["%rot = f32[8] copy(…)", 70.0, 10.0],
           ["%fusion = f32[8] fusion(…)", 100.0, 30.0],
           ["%gather = f32[8] gather(…)", 130.0, 20.0]]
    return bt.Trace([bt.Device("/device:TPU:0", mods, ops)], [])


TOY = {"bench_inverse": {"%fusion": "jit/fftb.unpack/scatter",
                         "%copy-start": "",
                         "%while": "jit/scf.subspace/while",
                         "%rot": "jit/scf.subspace/while"},
       "bench_forward": {"%fusion": "jit/fftb.line_dft/Z/dot_general",
                         "%gather": "jit/fftb.pack/gather"}}


def test_a_repeated_instruction_name_takes_the_enclosing_program():
    tr = _toy_trace()
    assert scopes.scope_ns(tr, TOY, SPHERE, "fftb.unpack") == 40.0
    assert scopes.scope_ns(tr, TOY, SPHERE, "fftb.line_dft") == 30.0
    assert scopes.scope_ns(tr, TOY, SPHERE, "fftb.pack") == 20.0


def test_nested_and_overlapping_operations_count_once():
    tr = _toy_trace()
    # the while (50 ns) encloses both body copies: 50, not 70
    assert scopes.scope_ns(tr, TOY, SPHERE, "scf.subspace") == 50.0
    # the async copy overlaps the unpack fusion and the while for 10 ns
    # each: the unscoped union is its own 30 ns of the 150 busy
    assert scopes.unscoped_ns(tr, TOY, SPHERE) == 30.0
    info = {"programs": SPHERE, "scopes": TOY}
    busy = bt.busy(tr.devices[0], 0.0, 150.0)
    assert busy == 150.0
    assert scopes.unscoped_share(tr, info) == pytest.approx(20.0)
    assert scopes.scope_ns(tr, TOY, SPHERE, "no.such") is None


def test_a_shared_loop_body_takes_the_loop_that_ran_it():
    """One loop body serves two call sites, and its instruction's
    ``op_name`` joins both sites' paths: each run counts under the scope
    of the ``while`` that encloses it on the device, and once."""
    both = ("jit/scf.density/fftb.unpack/jit(run)/jit/scf.hamiltonian/"
            "fftb.unpack/jit(run)/while/body/gather")
    table = {"bench_scf_step": {
        "%while.1": "jit/scf.hamiltonian/fftb.unpack/jit(run)/while",
        "%while.2": "jit/scf.density/fftb.unpack/jit(run)/while",
        "%fusion.9": both}}
    mods = [["jit_bench_scf_step(1)", 0.0, 100.0, 1]]
    ops = [["%while.1 = (…) while(…)", 0.0, 40.0],
           ["%fusion.9 = c64[…] fusion(…)", 5.0, 30.0],
           ["%while.2 = (…) while(…)", 50.0, 20.0],
           ["%fusion.9 = c64[…] fusion(…)", 55.0, 10.0]]
    tr = bt.Trace([bt.Device("/device:TPU:0", mods, ops)], [])
    assert scopes.merged(both)
    assert not scopes.merged(table["bench_scf_step"]["%while.1"])
    paths = [p for p, _, _ in scopes.attributed_ops(tr.devices[0], table,
                                                    SCF)]
    assert paths.count(table["bench_scf_step"]["%while.1"]) == 2
    assert paths.count(table["bench_scf_step"]["%while.2"]) == 2
    assert scopes.scope_ns(tr, table, SCF, "scf.hamiltonian") == 40.0
    assert scopes.scope_ns(tr, table, SCF, "scf.density") == 20.0
    assert scopes.scope_ns(tr, table, SCF, "fftb.unpack") == 60.0


def test_a_program_without_layer_names_reads_none():
    tr = _toy_trace()
    bare = {p: {k: "" for k in m} for p, m in TOY.items()}
    info = {"programs": SPHERE, "scopes": bare}
    assert scopes.unscoped_ns(tr, bare, SPHERE) is None
    assert scopes.unscoped_share(tr, info) is None
    assert scopes.per_step_ms(tr, info, "fftb.unpack") is None
    # nor does a run that hands its readers no map
    assert scopes.per_step_ms(tr, {"programs": SPHERE}, "fftb.unpack") \
        is None


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def _share_info(**change):
    info = {"programs": SPHERE, "scopes": TOY, "peaks": PEAKS,
            "units_per_step": 2, "chips": 1, "log": lambda msg: None,
            "scope_work": {"fftb.unpack": (0.0, 10.0),
                           "fftb.line_dft": (15e3, 1.0),
                           "no.such": (1.0, 1.0)}}
    info.update(change)
    return info


def test_roofline_share_takes_the_larger_bound_over_the_scope_time():
    tr = _toy_trace()
    # bytes bound: 2 units × 10 B at 1 GB/s = 20 ns against the unpack's
    # 40 ns on the device
    assert scopes.roofline_share(tr, _share_info(), "fftb.unpack") == \
        pytest.approx(50.0)
    # flops bound: 2 × 15e3 at 1e12 = 30 ns against the line DFT's 30 ns
    logged = []
    info = _share_info(log=logged.append)
    assert scopes.roofline_share(tr, info, "fftb.line_dft") == \
        pytest.approx(100.0)
    assert "compute-bound" in logged[-1]
    # the chips share the work: half the least time
    assert scopes.roofline_share(tr, _share_info(chips=2),
                                 "fftb.unpack") == pytest.approx(25.0)


def test_roofline_share_reads_none_where_a_number_is_missing():
    tr = _toy_trace()
    # no peaks (not a chip), no work for the layer, no map, no device
    # time under the scope: nothing to read, never 0
    assert scopes.roofline_share(tr, _share_info(peaks=None),
                                 "fftb.unpack") is None
    assert scopes.roofline_share(tr, _share_info(), "fftb.pack") is None
    assert scopes.roofline_share(tr, _share_info(scope_work={}),
                                 "fftb.unpack") is None
    info = _share_info()
    del info["scope_work"]
    assert scopes.roofline_share(tr, info, "fftb.unpack") is None
    assert scopes.roofline_share(tr, _share_info(scopes={}),
                                 "fftb.unpack") is None
    assert scopes.roofline_share(tr, _share_info(), "no.such") is None


# ------------------------------------------------- recorded on the chip
def _steps(tr, program):
    return max(sum(bt.program_of(m[0]) == program for m in dev.modules)
               for dev in tr.devices)


def _has(scope):
    return lambda path: scope in path.split("/")


def _unnamed(path):
    return not any(c.startswith(("fftb.", "scf.")) for c in path.split("/"))


READERS = [("unpack_ms.transform", "sphere", "fftb.unpack"),
           ("pack_ms.transform", "sphere", "fftb.pack"),
           ("line_dft_ms.transform", "sphere", "fftb.line_dft"),
           ("unpack_ms.scf", "scf", "fftb.unpack"),
           ("pack_ms.scf", "scf", "fftb.pack"),
           ("line_dft_ms.scf", "scf", "fftb.line_dft"),
           ("hartree_ms", "scf", "scf.hartree"),
           ("density_ms", "scf", "scf.density"),
           ("subspace_ms", "scf", "scf.subspace")]


@pytest.mark.parametrize("name,cell,scope", READERS)
def test_reader_gives_scope_time_per_step(request, name, cell, scope):
    tr, table = request.getfixturevalue(cell)
    programs = SPHERE if cell == "sphere" else SCF
    info = {"programs": programs, "scopes": table}
    got = Registry().metric_reader(name).read(tr, info)
    want = (_plain_scope_ns(tr, table, programs, _has(scope))
            / _steps(tr, programs[0]) / 1e6)
    assert want > 0
    assert got == pytest.approx(want, rel=1e-9)
    # without the map (the harness as it stands) the reader is silent
    assert Registry().metric_reader(name).read(
        tr, {"programs": programs}) is None


#: the recorded cells' shapes (module docstring)
RECORDED = {"sphere": ("sphere_roundtrip", 8,
                       {"n": 64, "diameter": 32, "nbands": {"1": 4},
                        "kpts": [[0.0, 0.0, 0.0], [0.5, 0.5, 0.5]]}),
            "scf": ("scf_iterations", 1,
                    {"n": 32, "diameter": 16, "nbands": 2,
                     "inner_steps": 2,
                     "kpts": [[0.0, 0.0, 0.0], [0.5, 0.5, 0.5]]})}
V5E = Registry().peaks("TPU v5 lite")
SHARES = [("unpack_roofline.transform", "sphere", "fftb.unpack"),
          ("pack_roofline.transform", "sphere", "fftb.pack"),
          ("line_dft_roofline.transform", "sphere", "fftb.line_dft"),
          ("unpack_roofline.scf", "scf", "fftb.unpack"),
          ("pack_roofline.scf", "scf", "fftb.pack"),
          ("line_dft_roofline.scf", "scf", "fftb.line_dft")]


@pytest.mark.parametrize("name,cell,scope", SHARES)
def test_share_reader_gives_least_time_over_scope_time(request, name, cell,
                                                       scope):
    tr, table = request.getfixturevalue(cell)
    kind, units, cfg = RECORDED[cell]
    programs = SPHERE if cell == "sphere" else SCF
    work = Registry().kind(kind).scope_work(cfg, {})
    info = {"programs": programs, "scopes": table, "scope_work": work,
            "peaks": V5E, "units_per_step": units, "chips": 1,
            "log": lambda msg: None}
    got = Registry().metric_reader(name).read(tr, info)
    flops, nbytes = work[scope]
    least_s = units * max(flops / V5E["bf16_flops_per_s"],
                          nbytes / V5E["hbm_bytes_per_s"])
    step_s = (_plain_scope_ns(tr, table, programs, _has(scope))
              / _steps(tr, programs[0]) / 1e9)
    assert got == pytest.approx(100.0 * least_s / step_s, rel=1e-9)
    assert 0 < got < 100
    assert Registry().metric_reader(name).read(
        tr, dict(info, scope_work={})) is None


@pytest.mark.parametrize("name,cell", [("unscoped_share.transform", "sphere"),
                                       ("unscoped_share.scf", "scf")])
def test_unscoped_share_reader(request, name, cell):
    tr, table = request.getfixturevalue(cell)
    programs = SPHERE if cell == "sphere" else SCF
    info = {"programs": programs, "scopes": table}
    got = Registry().metric_reader(name).read(tr, info)
    busy = _plain_scope_ns(tr, table, programs, lambda p: True)
    want = 100.0 * _plain_scope_ns(tr, table, programs, _unnamed) / busy
    assert 0 < got < 100
    assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("cell,names", [
    ("sphere", ("fftb.unpack", "fftb.pack", "fftb.line_dft")),
    ("scf", ("scf.hamiltonian", "scf.subspace", "scf.density",
             "scf.hartree", "scf.energy", "scf.mixer", "fftb.unpack",
             "fftb.pack", "fftb.line_dft"))])
def test_every_layer_takes_device_time_on_the_chip(request, cell, names):
    tr, table = request.getfixturevalue(cell)
    programs = SPHERE if cell == "sphere" else SCF
    for scope in names:
        assert scopes.scope_ns(tr, table, programs, scope) > 0, scope


def test_the_unnamed_scatter_fusion_is_the_unpack(sphere):
    """The TPU compiler leaves the unpack's scatter fusion without an
    ``op_name``; it takes the name of the work fused into it."""
    tr, table = sphere
    runs = [m for m in tr.devices[0].modules
            if bt.program_of(m[0]) == "bench_inverse"]
    lo, hi = runs[0][1], runs[0][1] + runs[0][2]
    longest = max((op for op in tr.devices[0].ops if lo <= op[1] <= hi),
                  key=lambda op: op[2])
    name = scopes.instruction(longest[0])
    assert "scatter" in longest[0] or "fusion" in longest[0]
    assert "fftb.unpack" in scopes.components(table["bench_inverse"][name])


def test_recorded_programs_share_instruction_names(sphere):
    _, table = sphere
    shared = set(table["bench_inverse"]) & set(table["bench_forward"])
    assert any(table["bench_inverse"][n] != table["bench_forward"][n]
               for n in shared)


# ------------------------------------------------- the compile cache's key
CACHE_CHILD = """
import json, os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {cache!r}
import jax, jax.numpy as jnp
from bench import common, scopes
from bench.run import CompileLog, enable_cache

enable_cache(trace={trace!r})
log = CompileLog()
seen = []
for scope in {names!r}:
    def probe(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 2.0
    prog = common.compile_program(
        "bench_probe", probe, jax.ShapeDtypeStruct((8,), jnp.float32))
    paths = scopes.op_scopes(prog).values()
    seen.append(sorted({{c for p in paths for c in scopes.components(p)
                         if scopes.is_layer(c)}}))
print(json.dumps({{"seen": seen, "hits": log.snapshot()[2]}}))
"""


def _compile_probes(cache, trace, names):
    """Compile one program per scope name in a child process, each the
    same program but for the name, through ``run.py``'s cache set-up;
    returns the layer names each compiled program carries and the
    persistent-cache hits."""
    out = run_child(CACHE_CHILD.format(cache=str(cache), trace=trace,
                                       names=names), 1, timeout=300)
    return json.loads(out.strip().splitlines()[-1])


def test_a_traced_run_maps_the_names_of_the_code_it_compiled(tmp_path):
    """Two revisions' programs that differ only in a scope name share a
    cache: the traced set-up keys on the names, so the second maps its
    own, and a later traced run of either still finds its programs."""
    first = _compile_probes(tmp_path, True, ["fftb.alpha", "fftb.beta"])
    assert first == {"seen": [["fftb.alpha"], ["fftb.beta"]], "hits": 0}
    again = _compile_probes(tmp_path, True, ["fftb.beta", "fftb.alpha"])
    assert again == {"seen": [["fftb.beta"], ["fftb.alpha"]], "hits": 2}


def test_an_untraced_run_keeps_the_cache_key(tmp_path):
    """The untraced set-up keys as before: the second program is a hit,
    and carries the names of the program that filled the entry — why a
    traced run may not read its map so."""
    got = _compile_probes(tmp_path, False, ["fftb.alpha", "fftb.beta"])
    assert got == {"seen": [["fftb.alpha"], ["fftb.alpha"]], "hits": 1}
