"""Where compiled XLA programs are kept between runs.

One function, called before the first jit by every entry point that
compiles (the serving services, the evaluator host, the trainers). A
cold start compiles every (bucket x row tier) eval program; with the
persistent cache a restart on the same checkout reloads them instead.

Placement rule: ``JAX_COMPILATION_CACHE_DIR`` belongs to whoever runs
the program. When it is set JAX reads it itself and this module names no
directory at all; otherwise the cache lives in ONE fixed directory
inside the checkout. The directory is part of JAX's cache key, so it is
never derived from a tempdir, a pid or the clock — a path that moves
never hits.

``configure()`` also installs the process's compile recorder (once,
however often it is called): ``jax.monitoring`` listeners that feed
``fishnet_compile_seconds_total{phase}`` and
``fishnet_compiles_total{cache}`` and record every program brought up as
one ``program_up`` span of the span flight recorder, with its name, its
four phases and the functions whose traces cost most
(doc/observability.md "Training and compilation"). The listeners run
only when something traces or compiles: a window with no compilation
pays nothing.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

from fishnet_tpu.telemetry import spans, tracing
from fishnet_tpu.telemetry.registry import REGISTRY, MetricsRegistry

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: ``jax.monitoring`` events with a time span or a duration -> the ``phase`` label.
PHASE_OF_EVENT = {
    _TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    _LOAD_EVENT: "cache_load",
}
#: ``jax.monitoring`` plain events -> the ``cache`` label.
CACHE_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

#: A program that hit the cache or asked none, and whose four phases took
#: less than this together, is counted in ``small`` and is no span.
SMALL_PROGRAM_S = 0.010
#: Rows of a ``program_up`` span's ``traced``: the functions traced inside
#: the program that cost most self time.
TRACED_KEPT = 8
#: Finished traces a thread holds for a lowering before it forgets them
#: (a thread that only ever traces: ``jax.eval_shape`` in a loop).
TRACES_HELD = 1 << 15


#: A finished trace no finished trace holds yet, a plain tuple (a step program sends thousands): its
#: interval on JAX's clock (``time.time()``), its name, and its rows of ``_Held.rows``, its own last.
_Trace = Tuple[float, float, str, int, int]
_START, _END, _NAME, _FIRST_ROW, _LAST_ROW = range(5)


class _Program(NamedTuple):
    """A lowered program waiting for its compilation."""

    name: str
    start: float  # its trace's, or its lowering's where it had none
    lowered: float  # its lowering's end
    trace_s: float
    lower_s: float
    rows: List[Tuple[str, float]]


class _Held(threading.local):
    """What one thread has of the program it is bringing up."""

    def __init__(self) -> None:
        self.rows: List[Tuple[str, float]] = []  # (function, self seconds) of every finished trace, as they finished
        self.outermost: List[_Trace] = []
        self.program: Optional[_Program] = None
        self.cache, self.cache_load_s = "none", 0.0  # of the program being compiled
        self.totals = {"compile_s": 0.0, "cache_load_s": 0.0, "trace_lower_s": 0.0, "cache_misses": 0}
        self.parent: Optional[tracing.TraceContext] = None  # the start-up span open on this thread


class CompileRecorder:
    """Counts what JAX reports of tracing and compiling, by phase, and
    folds the events of each program into one ``program_up`` span.

    JAX 0.9.0 sends, on the thread that brings a program up and in this
    order: the time span of every function traced (the nested first, the
    program's own last), of its lowering (``jit(<name>)``; a kernel's body
    is traced inside it), then, inside the time span of
    ``backend_compile``, ``cache_hits`` and the duration of the retrieval,
    or ``cache_misses`` once it is compiled and written; no cache event
    where no cache is asked. The span is recorded when ``backend_compile``
    ends. The phases are disjoint, so their seconds add up to the time
    spent:

    * JAX times every traced function, those traced inside another too
      (one trainer step: ~1,500 events, nearly all nested in the step's
      own). A trace is held back until a program is lowered on the same
      thread: the last outermost trace of the program's name (of another
      name only where none has it) is the program's and is counted then,
      once, with what it traced inside it; what else was held led to no
      program (``jax.eval_shape``) and is forgotten. A trace's **self**
      time is its interval less the intervals of the traces directly
      inside it; same-named traces add up.
    * ``backend_compile`` covers a program loaded from the persistent
      cache as well, and then holds the load. ``compile_s`` is counted
      less the ``cache_load`` that came inside it: it is real compilation
      only.
    """

    def __init__(self, registry: MetricsRegistry = REGISTRY, span_recorder: Optional[spans.SpanRecorder] = None) -> None:
        self._seconds = registry.counter(
            "fishnet_compile_seconds_total",
            "Seconds JAX spent bringing programs up, by disjoint phase",
            labelnames=("phase",),
        )
        self._compiles = registry.counter(
            "fishnet_compiles_total",
            "Programs asked of the persistent compile cache, by outcome",
            labelnames=("cache",),
        )
        self._spans = span_recorder or spans.RECORDER
        self._held = _Held()
        self._lock = threading.Lock()  # ``small`` alone: programs are rare and slow, not a hot path
        self._small = [0, 0.0]

    # -- the listeners ------------------------------------------------------

    def on_span(self, event: str, start: float, end: float, fun_name: str = "", **_kw) -> None:
        if event == _TRACE_EVENT:  # thousands a step program: one interval pushed, those it holds popped
            held = self._held
            rows, outermost = held.rows, held.outermost
            if len(rows) >= TRACES_HELD:
                rows.clear()
                outermost.clear()
            self_s, first_row = end - start, len(rows)
            while outermost and outermost[-1][_START] >= start:
                nested = outermost.pop()
                self_s -= nested[_END] - nested[_START]
                first_row = nested[_FIRST_ROW]
            outermost.append((start, end, fun_name, first_row, len(rows)))
            rows.append((fun_name, self_s))
            return
        phase = PHASE_OF_EVENT.get(event)
        if phase in ("lower", "backend"):
            name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
            (self._lowered if phase == "lower" else self._compiled)(name, start, end)

    def on_duration(self, event: str, seconds: float, **_kw) -> None:
        """The one phase JAX sends no time span of (the three others' durations come with their spans)."""
        if event == _LOAD_EVENT:
            self._held.cache_load_s += seconds
            self._count("cache_load", "cache_load_s", seconds)

    def on_event(self, event: str, **_kw) -> None:
        cache = CACHE_OF_EVENT.get(event)
        if cache is None:
            return
        held = self._held
        held.cache = cache
        held.totals["cache_misses"] += cache == "miss"
        self._compiles.inc(cache=cache)

    # -- one program ----------------------------------------------------------

    def _lowered(self, name: str, start: float, end: float) -> None:
        held = self._held
        if held.program is not None:  # lowered and never compiled (``.lower()`` alone)
            self._record(held.program, held.program.lowered, 0.0)
        before = [trace for trace in held.outermost if trace[_START] < start]
        own = next((trace for trace in reversed(before) if trace[_NAME] == name), before[-1] if before else None)
        rows: List[Tuple[str, float]] = []
        for trace in ([own] if own else []) + held.outermost[len(before):]:  # its own, and what its lowering traced
            rows += held.rows[trace[_FIRST_ROW]:trace[_LAST_ROW] + 1]
        held.rows.clear()
        held.outermost.clear()
        trace_s = own[_END] - own[_START] if own else 0.0
        held.program = _Program(name, own[_START] if own else start, end, trace_s, end - start, rows)
        self._count("trace", "trace_lower_s", trace_s)
        self._count("lower", "trace_lower_s", end - start)

    def _compiled(self, name: str, start: float, end: float) -> None:
        held = self._held
        program = held.program or _Program(name, start, start, 0.0, 0.0, [])  # lowered on another thread, or long ago
        compile_s = max(0.0, end - start - held.cache_load_s)
        self._count("backend", "compile_s", compile_s)
        self._record(program, end, compile_s)
        held.program, held.cache, held.cache_load_s = None, "none", 0.0

    def _count(self, phase: str, total: str, seconds: float) -> None:
        self._held.totals[total] += seconds
        self._seconds.inc(seconds, phase=phase)

    def _record(self, program: _Program, end: float, compile_s: float) -> None:
        held = self._held
        seconds = program.trace_s + program.lower_s + held.cache_load_s + compile_s
        if held.cache != "miss" and seconds < SMALL_PROGRAM_S:
            with self._lock:
                self._small[0] += 1
                self._small[1] += seconds
            return
        traced: Dict[str, List[float]] = {}
        for name, self_s in program.rows:
            row = traced.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += self_s
        costliest = sorted(traced.items(), key=lambda item: -item[1][1])[:TRACED_KEPT]
        to_monotonic = self._spans.epoch_offset  # JAX's clock is time.time(), the span recorder's time.monotonic()
        self._spans.record(
            "program_up", program.start - to_monotonic, ended=end - to_monotonic,
            trace=held.parent and held.parent.child(),
            name=program.name, trace_s=round(program.trace_s, 6), lower_s=round(program.lower_s, 6),
            cache_load_s=round(held.cache_load_s, 6), compile_s=round(compile_s, 6), cache=held.cache,
            traced=[[name, calls, round(self_s, 6)] for name, (calls, self_s) in costliest], small=self.small(),
        )

    # -- for a start-up span ----------------------------------------------------

    def small(self) -> List[float]:
        """``[count, seconds]`` of the programs brought up so far that were too small to be spans."""
        with self._lock:
            return [self._small[0], round(self._small[1], 6)]

    def mark(self) -> Dict[str, float]:
        """This thread's running totals now, for ``totals_since``."""
        return dict(self._held.totals)

    def totals_since(self, mark: Dict[str, float]) -> Dict[str, float]:
        """What this thread has brought up since ``mark()``, under the
        names of a start-up span's fields."""
        return {key: round(value - mark[key], 6) for key, value in self._held.totals.items()}

    @contextmanager
    def parent_of_programs(self, context: tracing.TraceContext) -> Iterator[None]:
        """While open, a ``program_up`` of this thread is a child of ``context``'s span."""
        self._held.parent = context
        try:
            yield
        finally:
            self._held.parent = None


#: The process's recorder, installed by the first ``configure()``.
RECORDER: Optional[CompileRecorder] = None
_install_lock = threading.Lock()


def configure_recorder() -> CompileRecorder:
    """The process's compile recorder, installed on first use."""
    global RECORDER
    with _install_lock:
        if RECORDER is None:
            import jax

            recorder = CompileRecorder()
            jax.monitoring.register_event_time_span_listener(recorder.on_span)
            jax.monitoring.register_event_duration_secs_listener(recorder.on_duration)
            jax.monitoring.register_event_listener(recorder.on_event)
            RECORDER = recorder
        return RECORDER


def cache_dir() -> str:
    """The directory the persistent cache uses under the placement rule."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def configure() -> Optional[str]:
    """Turn the persistent compilation cache on. Returns the directory
    this call set in code, or None when ``JAX_COMPILATION_CACHE_DIR`` is
    set (JAX already points there; no directory is set here).

    JAX decides once, at its first compile, whether a cache is in use —
    call this before that. The default thresholds would skip programs
    that compile in under a second, which is most of the small-bucket
    eval programs, so every program is kept."""
    import jax

    configure_recorder()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
