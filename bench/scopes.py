"""Device time by the layer names the program gives its compiled work.

The program names its layers with ``jax.named_scope`` (``fftb.unpack``,
``fftb.line_dft/x``, ``scf.hartree``, …); the compiler keeps each name as
the ``op_name`` in an instruction's metadata, and the device trace names
each operation by its instruction (``%fusion.12 = …``, ``trace.short_op``).

* ``op_scopes(compiled)`` maps every instruction of one compiled program
  to its scope path, from ``compiled.as_text()``.  An instruction the
  compiler left unnamed (on one chip the TPU compiler names neither the
  unpack's scatter fusion nor most async copies) takes the name of the
  first named instruction that uses its result, through unnamed ones:
  the scatter fusion is read by the unpack's slice, while the operand
  layouts fused into it carry the producer's names.  With no named user it takes the most common
  name inside the computations it calls, and then the name of the
  instruction that calls its own computation (a ``while`` body's
  operations).
* Instruction names repeat across programs (``%fusion`` is in both
  ``bench_inverse`` and ``bench_forward``), so the maps are per program,
  and an operation takes the program whose run on the device encloses
  it, as ``trace.top_ops`` does.
* Where one jitted callee is called from several places (the unpack's
  loop, from each H apply and from the density), the compiler may keep
  one body for all of them and prefix each call site's path to its
  instructions' ``op_name``: the path then names the program's root more
  than once, and every call site's layers.  Such an operation takes the
  path of the innermost operation that encloses it in time on the device
  (the ``while`` of the call site that ran it).
* ``scope_ns`` is the union of the intervals of the operations whose
  path has the scope as one component, inside the window, mean over the
  devices: a ``while`` and the operations of its body, or an async copy
  that overlaps a fusion, count once.

Where no operation carries a layer name (a program without the scopes),
every reading is None.
"""
from __future__ import annotations

import bisect
import collections
import re

from bench import trace

#: the components that name a layer of the program
LAYER_PREFIXES = ("fftb.", "scf.")

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?(%[^\s=]+) = (.*)$")
_NAME = re.compile(r"%[\w.\-]+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition)=(%[\w.\-]+)")
_CALL_SETS = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")


def _parse_hlo(text: str) -> dict:
    """Per instruction of an HLO module's text: its own ``op_name``, the
    computation it sits in, the computations it calls and its operands."""
    instrs, comp = {}, None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY ")):
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0]
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        rest = m.group(2)
        own = _OP_NAME.search(rest)
        called = _CALLS.findall(rest)
        for group in _CALL_SETS.findall(rest):
            called.extend(c.strip() for c in group.split(","))
        operands = [n for n in _NAME.findall(rest.split(", metadata=")[0])
                    if n not in called]
        instrs[m.group(1)] = {"own": own.group(1) if own else "",
                              "comp": comp, "calls": called,
                              "operands": operands}
    return instrs


def scopes_from_text(text: str) -> dict[str, str]:
    """Instruction name → scope path (``""`` where nothing names it)."""
    instrs = _parse_hlo(text)
    members = collections.defaultdict(list)
    users = collections.defaultdict(list)
    callers = {}
    for name, ins in instrs.items():
        members[ins["comp"]].append(name)
        for c in ins["calls"]:
            callers.setdefault(c, name)
        for op in ins["operands"]:
            users[op].append(name)
    inside: dict[str, collections.Counter] = {}

    def names_in(comp: str) -> collections.Counter:
        """Own names of every instruction a computation runs, nested
        calls included (the call graph is acyclic)."""
        if comp not in inside:
            inside[comp] = collections.Counter()
            count = collections.Counter()
            for name in members.get(comp, ()):
                if instrs[name]["own"]:
                    count[instrs[name]["own"]] += 1
                for c in instrs[name]["calls"]:
                    count.update(names_in(c))
            inside[comp] = count
        return inside[comp]

    def used_by(name: str) -> str:
        """The name of the first named user, breadth first."""
        queue, seen = collections.deque(users[name]), {name}
        while queue:
            user = queue.popleft()
            if user in seen or user not in instrs:
                continue
            seen.add(user)
            if instrs[user]["own"]:
                return instrs[user]["own"]
            queue.extend(users[user])
        return ""

    def named(name: str) -> str:
        ins = instrs[name]
        if ins["own"]:
            return ins["own"]
        path = used_by(name)
        if path:
            return path
        count = collections.Counter()
        for c in ins["calls"]:
            count.update(names_in(c))
        return count.most_common(1)[0][0] if count else ""

    out = {}
    for name, ins in instrs.items():
        path, seen = named(name), {name}
        caller = callers.get(ins["comp"])
        while not path and caller is not None and caller not in seen:
            seen.add(caller)
            path = named(caller)
            caller = callers.get(instrs[caller]["comp"])
        out[name] = path
    return out


def op_scopes(compiled) -> dict[str, str]:
    """Instruction name → scope path of one compiled program."""
    return scopes_from_text(compiled.as_text())


def components(path: str) -> list[str]:
    return path.split("/") if path else []


def is_layer(path: str) -> bool:
    """Whether some component of ``path`` names a layer of the program."""
    return any(c.startswith(LAYER_PREFIXES) for c in components(path))


def instruction(op_name: str) -> str:
    """``%fusion.2 = f32[…] fusion(…)`` → ``%fusion.2``."""
    return op_name.split(" = ", 1)[0].strip()


def merged(path: str) -> bool:
    """Whether ``path`` joins several call sites' paths: its first
    component, the program's root, comes again later."""
    parts = components(path)
    return bool(parts) and parts[0] in parts[1:]


def attributed_ops(dev, scopes: dict, programs):
    """``(path or None, start, end)`` of each operation in the device's
    window, its path looked up in the map of the program whose run
    encloses it (None where that program has no map or the instruction
    is missing); a merged path gives way to that of the innermost
    enclosing operation whose path is not merged.  Nothing where the
    device ran none of ``programs``."""
    w = trace.window(dev, programs)
    if w is None:
        return
    mods = sorted((s, s + d, trace.program_of(n))
                  for n, s, d, _ in dev.modules)
    starts = [m[0] for m in mods]
    open_ops: list = []            # (end, path) of the enclosing operations
    for name, s, d in sorted(dev.ops, key=lambda op: (op[1], -op[2])):
        if s < w[0] or s > w[1]:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s <= mods[i][1] else None
        path = scopes.get(prog, {}).get(instruction(name))
        while open_ops and open_ops[-1][0] < s + d:
            open_ops.pop()
        if path is not None and merged(path):
            path = next((p for _, p in reversed(open_ops)
                         if p is not None and not merged(p)), path)
        open_ops.append((s + d, path))
        yield path, s, s + d


def _per_device(tr, scopes, programs, keep):
    """Union of the intervals of the operations whose path ``keep``
    accepts, per device with a window; and whether any operation of any
    device carries a layer name."""
    per_dev, named = [], False
    for dev in tr.devices:
        w = trace.window(dev, programs)
        if w is None:
            continue
        spans = []
        for path, s, e in attributed_ops(dev, scopes, programs):
            named = named or is_layer(path or "")
            if keep(path or ""):
                spans.append((s, e))
        per_dev.append(sum(e - s for s, e in trace.merged(spans, *w)))
    return per_dev, named


def scope_ns(tr, scopes: dict, programs, scope: str) -> float | None:
    """Device ns under ``scope`` (one component of the path), mean over
    the devices; None where no operation runs under it."""
    per_dev, _ = _per_device(tr, scopes, programs,
                             lambda p: scope in components(p))
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev)


def unscoped_ns(tr, scopes: dict, programs) -> float | None:
    """Busy ns of the operations whose path names no layer, mean over the
    devices; None where no operation names one."""
    per_dev, named = _per_device(tr, scopes, programs,
                                 lambda p: not is_layer(p))
    if not named:
        return None
    return sum(per_dev) / len(per_dev)


def steps(tr, info) -> int:
    """Runs of the cell's first program on the device that ran most."""
    return max((len(r) for r in trace.calls(tr, info["programs"][0])),
               default=0)


def per_step_ms(tr, info, scope: str) -> float | None:
    """Device ms under ``scope`` per step (per SCF iteration in the SCF
    cell); None without a scope map or a run."""
    table = info.get("scopes")
    if not table:
        return None
    ns = scope_ns(tr, table, info["programs"], scope)
    n = steps(tr, info)
    if ns is None or not n:
        return None
    return ns / n / 1e6


def unscoped_share(tr, info) -> float | None:
    """Busy time of the operations that name no layer, as % of the busy
    time (means over the devices); None without a scope map or a name."""
    table = info.get("scopes")
    if not table:
        return None
    ns = unscoped_ns(tr, table, info["programs"])
    occ = trace.occupancy(tr, info["programs"])
    if ns is None or occ is None or not occ[0]:
        return None
    return 100.0 * ns / (occ[0] * 1e9)


def roofline_share(tr, info, scope: str) -> float | None:
    """The layer's share of its roofline, in %: the least time the chips
    could take for one step's work under ``scope`` — the larger of flops
    over the bf16 peak and bytes over the HBM bandwidth, both from
    ``bench/peaks.json`` times the chips, the work from the kind's
    ``scope_work`` (per unit, times ``units_per_step``) — over the
    scope's device time per step (``per_step_ms``).  None where the
    peaks, the layer's work or its device time are missing."""
    work = info.get("scope_work", {}).get(scope)
    peaks = info.get("peaks")
    ms = per_step_ms(tr, info, scope)
    if work is None or peaks is None or ms is None:
        return None
    flops, nbytes = work
    units, chips = info["units_per_step"], info["chips"]
    t_flops = units * flops / (chips * peaks["bf16_flops_per_s"])
    t_bytes = units * nbytes / (chips * peaks["hbm_bytes_per_s"])
    bound = "memory" if t_bytes >= t_flops else "compute"
    info["log"](f"{scope} roofline: {bound}-bound, least "
                f"{max(t_flops, t_bytes) * 1e3:.4f} ms/step against "
                f"{ms:.4f} ms on the device")
    return 100.0 * max(t_flops, t_bytes) / (ms / 1e3)
