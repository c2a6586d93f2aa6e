"""The trainers' two start-up spans: ``train_init`` and ``train_first_step``.

A trainer's first ``init`` and first ``step`` are where its programs are
traced, lowered and compiled or loaded from the persistent cache. Each
is recorded once per trainer instance in the span flight recorder, with
the compile seconds that fell inside it (``utils/compile_cache``'s
recorder), and written as a ``jax.profiler.TraceAnnotation`` so that a
profile of start-up shows it on the profiler's clock beside the device.

These are start-up events, two per trainer, and are recorded whether or
not ``telemetry.enabled()``: they are over before anything could enable
it, and nothing on a step's path after the first pays for them.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Iterator

import jax

from fishnet_tpu.telemetry.spans import RECORDER
from fishnet_tpu.utils import compile_cache


@contextmanager
def _annotated(stage: str) -> Iterator[float]:
    started = time.monotonic()
    with jax.profiler.TraceAnnotation(stage):
        yield started


@contextmanager
def init_span(trainer: str, **fields: int) -> Iterator[None]:
    """Round ``Trainer.init`` / ``AzTrainer.init``; ``trainer`` is ``nnue`` or ``az``,
    ``fields`` what the trainer knows of the state it makes (``AzTrainer``: the leaves
    the client holds off row-major, and their bytes)."""
    with _annotated("train_init") as started:
        yield
    RECORDER.record("train_init", started, trainer=trainer, **fields, **compile_cache.configure_recorder().totals_since(started))


@contextmanager
def first_step_span(trainer: str) -> Iterator[None]:
    """Round the first ``.step`` of a trainer instance: trace, lower,
    compile or cache load, and the dispatch (not the step's execution)."""
    with _annotated("train_first_step") as started:
        yield
    RECORDER.record("train_first_step", started, trainer=trainer, **compile_cache.configure_recorder().totals_since(started))
