"""The start-up spans: what a process spends before its learner's first window.

Four stages of the span flight recorder, recorded whether or not
``telemetry.enabled()`` (they are over before anything could enable it,
and nothing on a step's path after the first pays for them):

* ``process_boot`` and ``program_import``, once a process, when its
  first trainer is made (``step_metrics.STEPS.attach``): from the
  process's start to the package's first import (the interpreter, the
  caller's and JAX's imports, the device client's start), and from there
  to that trainer's construction (the program's own imports and whatever
  the caller did between). Both are recorded after the fact.
* ``train_init`` and ``train_first_step``, once a trainer instance: its
  first ``init`` and first ``step`` are where its programs are traced,
  lowered and compiled or loaded from the persistent cache. Each carries
  the compile seconds that fell inside it (``utils/compile_cache``'s
  recorder), is the parent of the ``program_up`` spans of the programs
  brought up inside it, and is written as a
  ``jax.profiler.TraceAnnotation`` so that a profile of start-up shows it
  on the profiler's clock beside the device.

What lies between them is the caller's: ``train_init``'s end to
``train_first_step``'s start (a learner's data; the benchmark's pool and
settle), and ``train_first_step``'s end to the first step that counts.
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Optional

import jax

import fishnet_tpu
from fishnet_tpu.telemetry import tracing
from fishnet_tpu.telemetry.spans import RECORDER
from fishnet_tpu.utils import compile_cache

_process_spans_pending = True
_process_lock = threading.Lock()


def process_started() -> Optional[float]:
    """``time.monotonic()`` of this process's start, to a clock tick: its
    start time since boot (``/proc/self/stat`` field 22) against
    ``CLOCK_BOOTTIME`` now. None where either cannot be read."""
    try:
        with open("/proc/self/stat") as stat:
            ticks = int(stat.read().rpartition(")")[2].split()[19])  # field 3 follows the name's bracket
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.monotonic() - age if age >= 0 else None


def record_process_spans() -> None:
    """``process_boot`` and ``program_import``, the first time it is called
    in a process; ``process_boot`` only where the process's start can be
    read and lies before the package's import."""
    global _process_spans_pending
    with _process_lock:
        if not _process_spans_pending:
            return
        _process_spans_pending = False
    imported, now = fishnet_tpu.FIRST_IMPORT, time.monotonic()
    started = process_started()
    if started is not None and started <= imported:
        RECORDER.record("process_boot", started, ended=imported)
    RECORDER.record("program_import", imported, ended=now)


@contextmanager
def _bringing_up(stage: str) -> Iterator[Dict[str, Any]]:
    """Round a start-up stage: yields what its span is recorded with, whole once the stage has ended."""
    recorder = compile_cache.configure_recorder()
    context = tracing.new_trace()
    span: Dict[str, Any] = {"started": time.monotonic(), "trace": context, "small_at_start": recorder.small()}
    mark = recorder.mark()
    with jax.profiler.TraceAnnotation(stage), recorder.parent_of_programs(context):
        yield span
    span.update(recorder.totals_since(mark), small_at_end=recorder.small())


@contextmanager
def init_span(trainer: str, **fields: float) -> Iterator[None]:
    """Round ``Trainer.init`` / ``AzTrainer.init``; ``trainer`` is ``nnue`` or ``az``,
    ``fields`` what the trainer knows of the state it makes (``AzTrainer``: the leaves
    the client holds off row-major, and their bytes; of a trunk also
    ``attention_heads_paired``, the share of its attention layers' query heads whose
    scores the kernel pair makes two a product, ``loop_steps``, the times its plan is
    walked a forward pass, and ``layer_passes``, that times its layers)."""
    with _bringing_up("train_init") as span:
        yield
    RECORDER.record("train_init", trainer=trainer, **fields, **span)


@contextmanager
def first_step_span(trainer: str) -> Iterator[None]:
    """Round the first ``.step`` of a trainer instance: trace, lower,
    compile or cache load, and the dispatch (not the step's execution)."""
    with _bringing_up("train_first_step") as span:
        yield
    RECORDER.record("train_first_step", trainer=trainer, **span)
