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
``fishnet_compiles_total{cache}`` (doc/observability.md "Training and
compilation") and keep the last compile events, so that a start-up span
can total what fell inside it. The listeners run only when something
traces or compiles: a window with no compilation pays nothing.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from pathlib import Path
from typing import Deque, Dict, Optional, Tuple

from fishnet_tpu.telemetry.registry import REGISTRY, MetricsRegistry

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The in-checkout default (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


#: ``jax.monitoring`` duration events -> the ``phase`` label.
PHASE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
#: ``jax.monitoring`` plain events -> the ``cache`` label.
CACHE_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

#: One kept compile event: monotonic time it ended, ``phase`` (or
#: ``hit``/``miss``, with 0 seconds), seconds, thread that compiled.
Event = Tuple[float, str, float, int]

EVENTS_KEPT = 256


class CompileRecorder:
    """Counts what JAX reports of tracing and compiling, by phase.

    The phases are disjoint, so their seconds add up to the time spent:

    * JAX times every traced function, those traced inside another too
      (one trainer step: ~1,500 events, nearly all nested in the step's
      own). A trace is held back until a program of its name is lowered
      on the same thread and is counted then, once, with what it traced
      inside it; traces that lead to no program are not counted.
    * ``backend_compile_duration`` fires for a program loaded from the
      persistent cache as well, and then holds the load. ``backend`` is
      counted less the ``cache_load`` that came just before it on the
      same thread: it is real compilation only.
    """

    def __init__(self, registry: MetricsRegistry = REGISTRY) -> None:
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
        self._events: Deque[Event] = deque(maxlen=EVENTS_KEPT)
        self._lock = threading.Lock()  # compiles are rare and slow: not a hot path
        self._thread = threading.local()  # .traced: function name -> seconds, since the last lowering

    def on_duration(self, event: str, seconds: float, fun_name: str = "", **_kw) -> None:
        phase = PHASE_OF_EVENT.get(event)
        if phase is None:
            return
        if phase == "trace":
            self._traced()[fun_name] = seconds
            return
        if phase == "lower":
            traced = self._traced()
            name = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
            # the program's own trace; by another name (pmap), the longest held
            self._count("trace", traced.get(name) or max(traced.values(), default=0.0))
            traced.clear()
        elif phase == "backend":
            seconds = max(0.0, seconds - self._load_just_before())
        self._count(phase, seconds)

    def on_event(self, event: str, **_kw) -> None:
        cache = CACHE_OF_EVENT.get(event)
        if cache is None:
            return
        with self._lock:
            self._events.append((time.monotonic(), cache, 0.0, threading.get_ident()))
        self._compiles.inc(cache=cache)

    def _traced(self) -> Dict[str, float]:
        try:
            return self._thread.traced
        except AttributeError:
            self._thread.traced = {}
            return self._thread.traced

    def _count(self, phase: str, seconds: float) -> None:
        with self._lock:
            self._events.append((time.monotonic(), phase, seconds, threading.get_ident()))
        self._seconds.inc(seconds, phase=phase)

    def _load_just_before(self) -> float:
        thread = threading.get_ident()
        for _ended, phase, seconds, who in reversed(self.events()):
            if who == thread:
                return seconds if phase == "cache_load" else 0.0
        return 0.0

    def events(self) -> Tuple[Event, ...]:
        """The last ``EVENTS_KEPT`` compile events, oldest first."""
        with self._lock:
            return tuple(self._events)

    def totals_since(self, started: float) -> Dict[str, float]:
        """What this thread's kept events since monotonic ``started`` add
        up to, under the names of a start-up span's fields."""
        thread = threading.get_ident()
        mine = [(phase, seconds) for ended, phase, seconds, who in self.events() if who == thread and ended >= started]

        def total(*phases: str) -> float:
            return round(sum(seconds for phase, seconds in mine if phase in phases), 6)

        return {
            "compile_s": total("backend"),
            "cache_load_s": total("cache_load"),
            "trace_lower_s": total("trace", "lower"),
            "cache_misses": sum(phase == "miss" for phase, _seconds in mine),
        }


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
