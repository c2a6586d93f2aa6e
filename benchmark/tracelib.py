"""From the profiler's trace to numbers: the one reduction every PR shares.

``load_xplane`` turns an ``.xplane.pb`` into a small neutral ``Trace``
(device operations, executed modules, the runner's host spans), and the
functions below reduce a ``Trace``. The tests run them on a recorded
``Trace`` checked in as JSON, so the arithmetic is checked without a chip.

On a TPU the profiler names a device operation by its whole HLO
instruction (``%fusion.7 = f32[22528,1024]{...} fusion(...), calls=...``).
A fusion does not say what it fuses, so ``hlo_kinds`` reads the compiled
module's text and gives every instruction the set of opcodes it runs
(its own and those of the computations it calls). The reducers classify
by those opcodes and by shapes, never by a name the program chose.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Set, Tuple

Interval = Tuple[float, float]

#: Host spans the runner writes with ``jax.profiler.TraceAnnotation``.
HOST_SPANS = ("feed_wait", "h2d", "dispatch", "loss_fetch")

_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][a-z0-9\-]*)\(")
_CALLED = re.compile(r"(?:calls|body|condition|branch_computations)=\{?%([\w.\-]+)")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


@dataclass
class Op:
    name: str  # the HLO instruction's name, without the leading %
    shape: str  # its (first) output shape, such as f32[22528,1024]
    start_ns: float
    dur_ns: float
    kinds: List[str] = field(default_factory=list)  # opcodes it runs

    @property
    def label(self) -> str:
        inner = [k for k in self.kinds if k in INTERESTING]
        return " ".join([self.name, self.shape] + inner)


#: Opcodes worth showing in a breakdown label.
INTERESTING = ("convolution", "dot", "gather", "scatter", "reduce", "reduce-window", "select-and-scatter", "sort", "copy", "transpose", "dynamic-update-slice", "custom-call")


@dataclass
class Trace:
    ops: List[Op]  # device operations of one device, in time order
    modules: List[Tuple[str, float, float]]  # executed programs: name, start, duration
    host_spans: List[Tuple[str, float, float]]  # the runner's spans: name, start, duration

    def to_json(self) -> Dict[str, object]:
        return {
            "ops": [[o.name, o.shape, o.start_ns, o.dur_ns, o.kinds] for o in self.ops],
            "modules": [list(m) for m in self.modules],
            "host_spans": [list(s) for s in self.host_spans],
        }

    @staticmethod
    def from_json(data: Dict[str, object]) -> "Trace":
        return Trace(
            ops=[Op(n, s, t, d, list(k)) for n, s, t, d, k in data["ops"]],
            modules=[(n, t, d) for n, t, d in data["modules"]],
            host_spans=[(n, t, d) for n, t, d in data["host_spans"]],
        )


def hlo_kinds(hlo_text: str) -> Dict[str, Set[str]]:
    """Instruction name -> every opcode it runs, from a compiled module's text."""
    own: Dict[str, Set[str]] = {}  # computation -> opcodes written in it
    calls: Dict[str, Set[str]] = {}  # computation -> computations it calls
    instrs: Dict[str, Tuple[str, Set[str]]] = {}  # instruction -> (opcode, called computations)
    current = None
    for line in hlo_text.splitlines():
        header = _HEADER.match(line)
        if header:
            current = header.group(1)
            own[current], calls[current] = set(), set()
            continue
        if current is None:
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        opcode = _OPCODE.search(m.group(2))
        if not opcode:
            continue
        called = set(_CALLED.findall(m.group(2)))
        own[current].add(opcode.group(1))
        calls[current] |= called
        instrs[m.group(1)] = (opcode.group(1), called)

    resolved: Dict[str, Set[str]] = {}

    def resolve(comp: str, seen: Tuple[str, ...] = ()) -> Set[str]:
        if comp in resolved:
            return resolved[comp]
        kinds = set(own.get(comp, ()))
        for callee in calls.get(comp, ()):
            if callee not in seen:
                kinds |= resolve(callee, seen + (comp,))
        resolved[comp] = kinds
        return kinds

    out: Dict[str, Set[str]] = {}
    for name, (opcode, called) in instrs.items():
        kinds = {opcode}
        for comp in called:
            kinds |= resolve(comp)
        out[name] = kinds
    return out


def _split_event_name(text: str) -> Tuple[str, str]:
    """``%fusion.7 = f32[2,3]{...} fusion(...)`` -> (``fusion.7``, ``f32[2,3]``)."""
    name, _, rest = text.partition(" = ")
    shape = _SHAPE.match(rest)
    return name.lstrip("%"), shape.group(1) if shape else ""


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str, kinds: Dict[str, Set[str]], device: int = 0) -> Trace:
    """The trace of device ``device`` and of the runner's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Op] = []
    modules: List[Tuple[str, float, float]] = []
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name, shape = _split_event_name(ev.name)
                        ops.append(Op(name, shape, ev.start_ns, ev.duration_ns, sorted(kinds.get(name, ()))))
                elif line.name == "XLA Modules":
                    modules.extend((ev.name, ev.start_ns, ev.duration_ns) for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(
                    (ev.name, ev.start_ns, ev.duration_ns) for ev in line.events if ev.name in HOST_SPANS
                )
    ops.sort(key=lambda o: o.start_ns)
    modules.sort(key=lambda m: m[1])
    spans.sort(key=lambda s: s[1])
    return Trace(ops, modules, spans)


# -- reductions ---------------------------------------------------------------


def union_ns(intervals: Iterable[Interval]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_start, cur_end = 0.0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def step_modules(trace: Trace) -> List[Tuple[str, float, float]]:
    """The executions of the program that took most device time: the step."""
    totals: Dict[str, float] = {}
    for name, _start, dur in trace.modules:
        totals[name] = totals.get(name, 0.0) + dur
    if not totals:
        return []
    step = max(totals, key=totals.get)
    return [m for m in trace.modules if m[0] == step]


def window(trace: Trace) -> Interval:
    """From the first traced step's start to the last one's end, device clock."""
    steps = step_modules(trace)
    if not steps:
        raise ValueError("the trace holds no executed module")
    return steps[0][1], max(start + dur for _n, start, dur in steps)


def ops_in(trace: Trace, span: Interval) -> List[Op]:
    lo, hi = span
    return [o for o in trace.ops if o.start_ns >= lo and o.start_ns + o.dur_ns <= hi]


def busy_ns(trace: Trace, span: Interval) -> float:
    return union_ns((o.start_ns, o.start_ns + o.dur_ns) for o in ops_in(trace, span))


def kind_time_ns(trace: Trace, kinds: Sequence[str]) -> float:
    """Summed device time, inside the window, of the operations that run
    any opcode of ``kinds``."""
    return sum(o.dur_ns for o in ops_in(trace, window(trace)) if set(kinds) & set(o.kinds))


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The operations that took most device time in the window: label, seconds."""
    totals: Dict[str, float] = {}
    for o in ops_in(trace, window(trace)):
        totals[o.label] = totals.get(o.label, 0.0) + o.dur_ns
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(label, ns / 1e9) for label, ns in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds of the device in the window, by the host span open then.

    A gap between two device operations goes to the runner's span that
    overlaps it most (``feed_wait``, ``h2d``, ``dispatch``, ``loss_fetch``),
    or to ``between_spans`` where none does."""
    lo, hi = window(trace)
    gaps: List[Interval] = []
    cursor = lo
    for o in ops_in(trace, (lo, hi)):
        if o.start_ns > cursor:
            gaps.append((cursor, o.start_ns))
        cursor = max(cursor, o.start_ns + o.dur_ns)
    if hi > cursor:
        gaps.append((cursor, hi))
    totals: Dict[str, float] = {}
    for g_lo, g_hi in gaps:
        best, best_overlap = "between_spans", 0.0
        for name, start, dur in trace.host_spans:
            overlap = min(g_hi, start + dur) - max(g_lo, start)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        totals[best] = totals.get(best, 0.0) + (g_hi - g_lo)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile by linear interpolation (``q`` in 0..100)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
