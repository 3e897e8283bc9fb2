"""Per-layer reduction of a profiler trace of the stage path, beside the
reduction in ``trace.py`` (whose ``ops``, annotations, busy time, top
operations and idle gaps it keeps exactly as they are).

It also keeps three things from the ``.xplane.pb``:

- the program's own spans: host annotations named ``repro.<span>``, which
  ``repro.telemetry`` opens while a recording tracer is installed;
- the device plane's ``XLA Modules`` intervals, so the idle time outside the
  stage program's executions (``jit_stage_program``) can be told from the
  idle time between its operations;
- each device operation's module, so its scope path, its HLO ``op_name``
  metadata, where ``jax.named_scope`` names the layer (``fl.local_train``,
  ``fl.aggregate``, ``coding.encode``), can be found in the module's
  compiled HLO text (``add_hlo``): on a TPU v5e the operations' events carry
  only their timing stats, no ``op_name``.

``layer_summary`` reduces a trace to the numbers that ``METRICS`` read.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from bench.harness.trace import (PREFIX, _CONTAINERS, _DEVICE_PLANE, _OP_LINES,
                                 Interval, Trace, merge, op_name, overlap)

SPAN_PREFIX = "repro."
PROGRAM = "jit_stage_program"
SCOPES = ("fl.local_train", "fl.aggregate", "coding.encode")
UNSCOPED = "unscoped"
IN_PROGRAM = "in_stage_program"
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s*=")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HLO_RUNS = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")


def module_name(event_name: str) -> str:
    """``jit_stage_program(12)`` -> ``jit_stage_program``."""
    return event_name.split("(", 1)[0].strip()


def subtract(xs: Sequence[Interval], ys: Sequence[Interval]) -> List[Interval]:
    """``xs`` minus ``ys``, both merged."""
    out: List[Interval] = []
    j = 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, lo = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > lo:
                out.append((lo, ys[k][0]))
            lo = max(lo, ys[k][1])
            k += 1
        if lo < b:
            out.append((lo, b))
    return out


def hlo_paths(text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` from a module's HLO text.  A fusion
    whose instruction carries none takes its fused computation's (the
    root's, else the first found there, nested fusions followed); an
    instruction still without one, such as a copy that XLA put in a loop,
    takes that of the instruction that runs its computation (the loop)."""
    comps: Dict[str, list] = {}         # computation -> [(name, is root)]
    own: Dict[str, tuple] = {}          # name -> (op_name, fused computation)
    home: Dict[str, str] = {}           # name -> its computation
    runner: Dict[str, str] = {}         # computation -> the name that runs it
    comp = None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            comps[comp] = []
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(2)
        path = _HLO_OP_NAME.search(line)
        fused = _HLO_CALLS.search(line)
        own[name] = (path and path.group(1), fused and fused.group(1))
        home[name] = comp
        comps[comp].append((name, bool(m.group(1))))
        for called in _HLO_RUNS.findall(line):
            runner.setdefault(called, name)

    inner: Dict[str, Optional[str]] = {}

    def of(name: str) -> Optional[str]:
        path, fused = own[name]
        if path or fused not in comps:
            return path
        if fused not in inner:
            names = sorted(comps[fused], key=lambda n: not n[1])
            inner[fused] = next((p for p in (of(n) for n, _ in names) if p),
                                None)
        return inner[fused]

    out: Dict[str, Optional[str]] = {}

    def found(name: str) -> Optional[str]:
        if name not in out:
            up = runner.get(home[name])
            out[name] = of(name) or (found(up) if up else None)
        return out[name]

    return {n: p for n in own if (p := found(n))}


def scope_of(path: Optional[str]) -> str:
    """The first of ``SCOPES`` that the path holds, else ``unscoped``."""
    if path:
        for s in SCOPES:
            if s in path:
                return s
    return UNSCOPED


@dataclass
class LayerTrace(Trace):
    """A ``Trace`` plus the program's spans (``spans``, as ``(name, start,
    end)``), each device plane's module executions (``modules``), each
    operation's module (``op_modules``, parallel to ``ops``; ``None``
    outside every module) and the scope paths that ``add_hlo`` found
    (``paths``, keyed by (module, operation))."""
    spans: List[tuple] = field(default_factory=list)
    modules: Dict[str, List[tuple]] = field(default_factory=dict)
    op_modules: Dict[str, list] = field(default_factory=dict)
    paths: Dict[tuple, str] = field(default_factory=dict)

    @classmethod
    def from_file(cls, path: str) -> "LayerTrace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        tr = cls()
        for plane in data.planes:
            if _DEVICE_PLANE.match(plane.name):
                tr._read_device(plane)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(PREFIX):
                            tr.annotations.append(
                                (e.name, e.start_ns, e.start_ns + e.duration_ns))
                        elif e.name.startswith(SPAN_PREFIX):
                            tr.spans.append(
                                (e.name, e.start_ns, e.start_ns + e.duration_ns))
        tr.annotations.sort(key=lambda n: n[1])
        tr.spans.sort(key=lambda n: n[1])
        return tr

    def _read_device(self, plane) -> None:
        lines = {line.name: line for line in plane.lines}
        line = next((lines[n] for n in _OP_LINES if n in lines), None)
        if line is None:
            return
        mods = sorted(((module_name(e.name), e.start_ns,
                        e.start_ns + e.duration_ns)
                       for e in lines["XLA Modules"].events),
                      key=lambda m: m[1]) if "XLA Modules" in lines else []
        starts = [a for _, a, _ in mods]
        ops, where = [], []
        for e in line.events:
            name, a = op_name(e.name), e.start_ns
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][0] if i >= 0 and a < mods[i][2] else None
            ops.append((name, a, a + e.duration_ns))
            where.append(mod)
        self.ops[plane.name] = ops
        self.modules[plane.name] = mods
        self.op_modules[plane.name] = where

    def add_hlo(self, text: str) -> None:
        """The scope paths of a module's operations, from its compiled HLO
        text."""
        mod = text.split(None, 2)[1].rstrip(",")     # "HloModule <name>, ..."
        self.paths.update({(mod, name): path
                           for name, path in hlo_paths(text).items()})

    # ----------------------------------------------------------------- reads
    def module_intervals(self, plane: str, name: str = PROGRAM) -> List[Interval]:
        return merge([(a, b) for m, a, b in self.modules.get(plane, ())
                      if m == name])

    def idle_by_span(self, within: Interval,
                     program: str = PROGRAM) -> Optional[Dict[str, float]]:
        """Idle nanoseconds inside ``within``, averaged over the device
        planes: outside ``program``'s executions summed by the innermost
        annotation (``repro.*`` or ``bench.*``; ``none`` outside them) open
        at each gap's midpoint, and inside them under ``in_stage_program``.
        None without a device operation or an execution of ``program``."""
        if not self.ops or not any(self.module_intervals(p, program)
                                   for p in self.ops):
            return None
        notes = [(b - a, n, a, b) for n, a, b in self.annotations + self.spans]
        out: Dict[str, float] = defaultdict(float)
        for plane, evs in self.ops.items():
            lo, hi = within
            busy = merge([(a, b) for _, a, b in evs])
            idle = subtract([(lo, hi)], busy)
            runs = merge([(max(a, lo), min(b, hi))
                          for a, b in self.module_intervals(plane, program)
                          if b > lo and a < hi])
            out[IN_PROGRAM] += overlap(idle, runs)
            for a, b in subtract(idle, runs):
                mid = (a + b) / 2
                open_ = [(d, n) for d, n, s, e in notes if s <= mid <= e]
                out[min(open_)[1] if open_ else "none"] += b - a
        n = len(self.ops)
        return {k: v / n for k, v in out.items()}

    def scope_time(self, within: Interval) -> Optional[Dict[str, float]]:
        """Device nanoseconds inside ``within`` by scope (``SCOPES``),
        averaged over the device planes, and the rest of the busy time as
        ``unscoped``.  Each operation counts its self time: its interval
        less those of the operations nested in it, so a loop's body counts
        in the body's operations and the loop's own time (between them) in
        the loop's scope.  Scopes that no operation holds are left out.
        None without a device operation."""
        busy = self.busy([within])
        if busy is None:
            return None
        lo, hi = within
        scopes = {k: scope_of(p) for k, p in self.paths.items()}
        out: Dict[str, float] = defaultdict(float)
        for plane, evs in self.ops.items():
            items = sorted(((max(a, lo), min(b, hi),
                             scopes.get((mod, name), UNSCOPED))
                            for (name, a, b), mod in zip(evs, self.op_modules[plane])
                            if b > lo and a < hi), key=lambda t: (t[0], -t[1]))
            own: Dict[str, list] = defaultdict(list)
            stack: list = []             # [start, end, scope, nested]

            def close(op):
                own[op[2]].extend(subtract([(op[0], op[1])], merge(op[3])))
            for a, b, scope in items:
                while stack and stack[-1][1] <= a:
                    close(stack.pop())
                if stack:
                    stack[-1][3].append((a, b))
                stack.append([a, b, scope, []])
            while stack:
                close(stack.pop())
            for scope, ivs in own.items():
                if scope != UNSCOPED:
                    out[scope] += sum(b - a for a, b in merge(ivs)) / len(self.ops)
        out[UNSCOPED] = busy - sum(out.values())
        return out

    def loop_time(self, within: Interval) -> Optional[float]:
        """Busy nanoseconds inside ``within`` in which only loops,
        conditionals or calls ran, none of their bodies' operations (the
        device's time in the control flow between them), averaged over the
        device planes.  None without a device operation."""
        busy = self.busy([within])
        if busy is None:
            return None
        leaves = Trace({p: [e for e in evs if e[0].split(".")[0] not in _CONTAINERS]
                        for p, evs in self.ops.items()})
        return busy - (leaves.busy([within]) or 0.0)


def layer_summary(tr: LayerTrace, stages: int) -> dict:
    """What ``METRICS`` read: the traced window, the device's busy time,
    ``idle_by_span`` and ``scope_s`` (seconds), over ``stages`` traced
    stages; and ``loops_s``, the busy time of control flow alone."""
    window = tr.window()
    if window is None:
        return {}
    lo, hi = window
    busy = tr.busy([window])
    idle = tr.idle_by_span(window)
    scopes = tr.scope_time(window)
    loops = tr.loop_time(window)
    return {"stages": stages, "window_s": (hi - lo) / 1e9,
            "busy_s": None if busy is None else busy / 1e9,
            "idle_by_span": None if idle is None
            else {k: v / 1e9 for k, v in idle.items()},
            "scope_s": None if scopes is None
            else {k: v / 1e9 for k, v in scopes.items()},
            "loops_s": None if loops is None else loops / 1e9}


# ------------------------------------------------------------------ readers
def idle_host(d: dict) -> Optional[float]:
    """idle_host.stage: % of the traced window with the device idle and no
    stage program executing."""
    idle = d.get("idle_by_span")
    if idle is None or not d.get("window_s"):
        return None
    return 100.0 * sum(v for k, v in idle.items() if k != IN_PROGRAM) / d["window_s"]


def idle_program(d: dict) -> Optional[float]:
    """idle_program.stage: % of the traced window with the device idle inside
    the stage program's executions."""
    idle = d.get("idle_by_span")
    if idle is None or not d.get("window_s"):
        return None
    return 100.0 * idle.get(IN_PROGRAM, 0.0) / d["window_s"]


def _scope_ms(scope: str):
    """The reader of ``scope``'s device ms per traced stage."""
    def read(d: dict) -> Optional[float]:
        t = (d.get("scope_s") or {}).get(scope)
        if t is None or not d.get("stages"):
            return None
        return 1e3 * t / d["stages"]
    return read


METRICS = {"idle_host.stage": idle_host,
           "idle_program.stage": idle_program,
           "train_ms.stage": _scope_ms("fl.local_train"),
           "aggregate_ms.stage": _scope_ms("fl.aggregate"),
           "encode_ms.stage": _scope_ms("coding.encode")}
