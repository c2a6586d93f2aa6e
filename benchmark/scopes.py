"""Which phase of the step each traced device operation belongs to.

The program names its own work (``doc/observability.md`` "Training and
compilation"): both trainers run their step under ``jax.named_scope``s
``forward``, ``loss`` and ``optimizer``, and the models add scopes of
their own below ``forward`` (``block07``, ``ft_gather``). JAX writes the
scopes into every instruction's ``op_name``
(``jit(_step)/transpose(jvp(forward))/ft_gather/jit(_take)/scatter-add``),
the backward pass as ``transpose(...)`` round the outermost scope. This
module reads the compiled step's text, gives every instruction a phase
by its own ``op_name`` (a fusion by its fusion instruction's, never by a
prefix match), joins the profiler's operations to it by name AND output
shape, and splits a traced step's device time by phase and by scope.

``step_forward_ms``, ``step_backward_ms``, ``step_optimizer_ms`` and
``step_unscoped_ms`` are one-line reducers over ``phase_ms``. Adding a
phase: a name in ``PHASES``, its rule in ``_phase_of_one``, a reducer
file and a ``per_layer`` entry. Adding a metric of one scope (say
``az_block_ms``): a reducer that sums ``split(ctx).by_path`` over the
paths it wants. The ``train_step`` runner does not put the compiled
text into the reducers' ``ctx``: the step is lowered once more here,
from the family adapter's own calls, and loads from the compile cache
(``split`` is kept in ``ctx``, so the reducers of one run share it). A
runner that puts the text under ``ctx["step_hlo_text"]`` saves that.

A join that fails in any operation gives no number at all: the reducers
print why and return None.
"""

from __future__ import annotations

import re
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from benchmark import tracelib

PHASES = ("forward", "backward", "optimizer", "unscoped")
#: The trainers' scopes that count as the forward pass (and, transposed, the backward).
_FORWARD_SCOPES = ("forward", "loss")

_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")
_STACK_TABLES = re.compile(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*\n?", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HEADER = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_CALLED = re.compile(r"(?:calls|body|condition|branch_computations)=\{?%([\w.\-]+)")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")
_WRAPPED = re.compile(r"^([A-Za-z_][\w.]*)\((.*)\)$")


def without_metadata(hlo_text: str) -> str:
    """The module's text less everything that only describes where it
    came from: each ``metadata={...}`` and the stack-frame tables of the
    header. Two programs that differ in scopes alone agree in this."""
    return _STACK_TABLES.sub("", _METADATA.sub("", hlo_text))


def _parts(name: str) -> List[str]:
    """``a/t(j(b))/c`` -> its parts, split at ``/`` outside parentheses."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(name[start:i])
            start = i + 1
    parts.append(name[start:])
    return parts


def _unwrap(part: str) -> Tuple[Tuple[str, ...], str]:
    """``transpose(jvp(forward))`` -> ((``transpose``, ``jvp``), ``forward``)."""
    wrappers: List[str] = []
    while True:
        m = _WRAPPED.match(part)
        if not m:
            return tuple(wrappers), part
        wrappers.append(m.group(1))
        part = m.group(2)


def _phase_of_one(name: str) -> Tuple[str, str, bool]:
    """(phase, scope path, shared) of one name. ``shared``: the name
    passes through a jit of JAX's own (``jit(relu)``). JAX lowers such a
    function once for all the places that call it with one shape, so its
    instructions carry the scopes of the FIRST call (every relu of the AZ
    tower reads ``stem/jit(relu)/max``): right in phase, not in path."""
    parts = [(part, *_unwrap(part)) for part in _parts(name)]
    phase = "unscoped"
    for _part, wrappers, scope in parts:
        if scope == "optimizer":
            phase = "optimizer"
            break
        if scope in _FORWARD_SCOPES and phase == "unscoped":
            phase = "backward" if "transpose" in wrappers else "forward"
    # The scope path: what lies between the program (the first part) and
    # the primitive (the last), less the jits, two levels deep.
    between = [(part, wrappers[:1] == ("jit",)) for part, wrappers, _scope in parts[1:-1]]
    scopes = [part for part, jitted in between if not jitted]
    return phase, "/".join(scopes[:2]) or "(no scope)", any(jitted for _part, jitted in between)


def _best(found: List[Tuple[str, str, bool]]) -> Tuple[str, str, bool]:
    """Of several names' readings the first that has a scope, one that is
    not ``shared`` before one that is."""
    scoped = [f for f in found if f[0] != "unscoped"]
    unshared = [f for f in scoped if not f[2]]
    return (unshared or scoped or found or [("unscoped", "(no scope)", False)])[0]


def phase_of(op_name: str) -> Tuple[str, str]:
    """(phase, scope path) of one instruction's ``op_name``.

    A name XLA joined from several (``a;b``) takes the first of them
    that has a scope; ``phases_of`` says whether they disagree."""
    return _best([_phase_of_one(name) for name in op_name.split(";") if name])[:2]


def phases_of(op_name: str) -> Set[str]:
    """The scoped phases an ``op_name`` holds (more than one: mixed)."""
    return {_phase_of_one(name)[0] for name in op_name.split(";") if name} - {"unscoped"}


@dataclass
class Instruction:
    shape: str  # first output shape, as tracelib reads it off a traced operation
    phase: str
    path: str
    mixed: str  # ``backward+optimizer`` where it and what it calls hold more than one phase, else empty


def instructions(hlo_text: str) -> Dict[str, Instruction]:
    """Every instruction of a compiled module's text by name.

    An instruction takes the phase of its own ``op_name``. One that has
    no scope of its own (XLA rewrites a scatter into a fusion and drops
    its metadata) takes the phase of the instructions it calls where
    those hold exactly one; what then still has none is ``unscoped``."""
    own: Dict[str, Dict[str, List[Tuple[str, str, bool]]]] = {}  # computation -> scoped phase -> readings in it
    calls: Dict[str, Set[str]] = {}  # computation -> computations it calls
    found: Dict[str, Tuple[str, str, Set[str]]] = {}  # instruction -> shape, op_name, called
    current = None
    for line in hlo_text.splitlines():
        header = _HEADER.match(line)
        if header:
            current = header.group(1)
            own[current], calls[current] = {}, set()
            continue
        if current is None:
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        shape = _SHAPE.match(m.group(2))
        op_name = _OP_NAME.search(m.group(2))
        op_name = op_name.group(1) if op_name else ""
        called = set(_CALLED.findall(m.group(2)))
        for name in op_name.split(";"):
            reading = _phase_of_one(name)
            if reading[0] != "unscoped":
                own[current].setdefault(reading[0], []).append(reading)
        calls[current] |= called
        found[m.group(1)] = (shape.group(1) if shape else "", op_name, called)

    def held(comp: str, seen: Tuple[str, ...] = ()) -> Dict[str, List[Tuple[str, str, bool]]]:
        phases = {phase: list(readings) for phase, readings in own.get(comp, {}).items()}
        for callee in calls.get(comp, ()):
            if callee not in seen:
                for phase, readings in held(callee, seen + (comp,)).items():
                    phases.setdefault(phase, []).extend(readings)
        return phases

    out: Dict[str, Instruction] = {}
    for name, (shape, op_name, called) in found.items():
        phase, path = phase_of(op_name)
        inside: Dict[str, List[Tuple[str, str, bool]]] = {}
        for comp in called:
            for held_phase, readings in held(comp).items():
                inside.setdefault(held_phase, []).extend(readings)
        if phase == "unscoped" and len(inside) == 1:
            phase, path, _shared = _best(next(iter(inside.values())))
        holds = phases_of(op_name) | set(inside)
        out[name] = Instruction(shape, phase, path, "+".join(sorted(holds)) if len(holds) > 1 else "")
    return out


@dataclass
class Split:
    """Device ms a traced step, by phase and by scope path."""

    steps: int
    by_phase: Dict[str, float] = field(default_factory=lambda: dict.fromkeys(PHASES, 0.0))
    by_path: Dict[str, float] = field(default_factory=dict)
    mixed_ms: Dict[str, float] = field(default_factory=dict)  # by the phases held: ``backward+optimizer``


def _self_ns(ops: List[tracelib.Op]) -> List[float]:
    """Each operation's device time less that of the operations that ran
    wholly inside it (a loop's body inside the loop), so that the times
    add up to the busy time. ``ops`` are in order of their start."""
    own = [o.dur_ns for o in ops]
    open_ops: List[int] = []
    for i, o in enumerate(ops):
        while open_ops and ops[open_ops[-1]].start_ns + ops[open_ops[-1]].dur_ns <= o.start_ns:
            open_ops.pop()
        if open_ops and o.start_ns + o.dur_ns <= ops[open_ops[-1]].start_ns + ops[open_ops[-1]].dur_ns:
            own[open_ops[-1]] -= o.dur_ns
        open_ops.append(i)
    return own


def split_trace(trace: tracelib.Trace, hlo_text: str) -> Optional[Split]:
    """The traced steps' device time by phase and scope, or None (and a
    line saying why) where an operation does not join the text."""
    steps = tracelib.step_modules(trace)
    if not steps:
        return None
    known = instructions(hlo_text)
    out = Split(len(steps))
    for _name, start, dur in steps:
        ops = tracelib.ops_in(trace, (start, start + dur))
        for op, self_ns in zip(ops, _self_ns(ops)):
            instr = known.get(op.name)
            if instr is None or instr.shape != op.shape:
                print(f"scopes: traced operation {op.name} {op.shape or '(no shape)'} "
                      + ("is not in the compiled step's text" if instr is None
                         else f"is {instr.shape or '(no shape)'} in the compiled step's text")
                      + ": the text is of another program than the one traced; no phase metric is reported")
                return None
            ms = self_ns / 1e6 / len(steps)
            out.by_phase[instr.phase] += ms
            out.by_path[instr.path] = out.by_path.get(instr.path, 0.0) + ms
            if instr.mixed:
                out.mixed_ms[instr.mixed] = out.mixed_ms.get(instr.mixed, 0.0) + ms
    return out


def step_text(ctx: Dict[str, Any]) -> str:
    """The compiled step program's text, from the family adapter's own
    calls on an abstract state: one more trace and lowering, and the
    program itself comes from the compile cache."""
    import jax
    import numpy as np

    config = ctx["config"]
    family = ctx["registry"].module("families", config["family"])
    trainer = family.make_trainer(config)
    state = jax.eval_shape(trainer._init, jax.random.PRNGKey(0))
    n_pool = len(next(iter(ctx["pool"].values())))
    batch = family.build_batch(ctx["pool"], np.arange(ctx["batch"]) % n_pool)
    return family.step_hlo_text(trainer, state, batch)


def split(ctx: Dict[str, Any]) -> Optional[Split]:
    """``split_trace`` of this run, computed once and kept in ``ctx``."""
    if "scopes_split" not in ctx:
        ctx["scopes_split"] = None
        if ctx["trace"] is not None:
            text = ctx.get("step_hlo_text")
            if text is None:
                started = time.monotonic()
                text = step_text(ctx)
                print(f"scopes: lowered the step again in {time.monotonic() - started:.2f} s")
            ctx["scopes_split"] = found = split_trace(ctx["trace"], text)
            if found is not None:
                _print(found)
    return ctx["scopes_split"]


def _print(found: Split) -> None:
    total = sum(found.by_phase.values())
    print(f"scopes: {total:.3f} ms a step over {found.steps} traced steps: "
          + ", ".join(f"{phase} {found.by_phase[phase]:.3f}" for phase in PHASES))
    if total and found.by_phase["unscoped"] >= 0.99 * total:
        print("scopes: the compiled program carries no scope (a program cached before the scopes "
              "were added has the same cache key and comes back with its old names)")
    print("scopes: of that in operations that hold more than one phase (counted under their own): "
          + (", ".join(f"{held} {ms:.3f}" for held, ms in sorted(found.mixed_ms.items())) or "0") + " ms")
    for path, ms in sorted(found.by_path.items(), key=lambda kv: -kv[1]):
        print(f"scopes:   {ms:9.3f} ms  {path}")


def phase_ms(ctx: Dict[str, Any], phase: str) -> Optional[float]:
    found = split(ctx)
    return None if found is None else found.by_phase[phase]
