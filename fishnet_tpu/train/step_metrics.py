"""The trainers' step recorder: what a step already counts, kept for a reader.

Both trainers' ``.step`` return a ``metrics`` dict of device scalars
(``loss`` and the counters of doc/observability.md "Training and
compilation"). Each trainer instance owns a ``StepRecord`` that keeps the
last ``RING_STEPS`` of them **as the device arrays they are**, and the
process's ``StepRecorder`` lists the records weakly, in the order their
trainers were made: a trainer that is collected takes its record with it.

Pull-only. On the step's path a record stores one reference and adds one
to a host count: no ``device_get``, no ``float()``, no
``block_until_ready``, no lock. A reader pays for the transfer
(``StepRecord.read``: one ``jax.device_get`` over what the ring holds);
the exporter's series are a collector that fetches the latest step's
metrics when scraped and costs nothing when never scraped. Like the
start-up spans it is always on: the benchmark never enables telemetry, and
a step's metrics are outputs of the step program, never donated, a few
dozen scalars a step.
"""

from __future__ import annotations

import threading
import weakref
from collections import deque
from typing import Any, Callable, Deque, Dict, List, NamedTuple, Optional, Tuple

import jax
import numpy as np

from fishnet_tpu.telemetry.registry import REGISTRY, MetricFamily, MetricsRegistry, Sample
from fishnet_tpu.train import startup

#: Steps a record keeps: the window's tail and what follows it, a few KiB.
RING_STEPS = 64


class Reading(NamedTuple):
    """What ``StepRecord.read`` resolved: ``metrics[i]`` is the step
    numbered ``first + i`` of the trainer ``trainer``, oldest first, up to
    the newest it had made when read."""

    trainer: str
    first: int
    metrics: List[Dict[str, float]]

    @property
    def steps(self) -> int:
        """Steps the trainer had made when read."""
        return self.first + len(self.metrics)


def _scalars(metrics: Dict[str, Any]) -> Dict[str, float]:
    """The scalar keys of one step's fetched metrics, as floats."""
    return {key: float(value) for key, value in metrics.items() if np.ndim(value) == 0}


class StepRecord:
    """One trainer instance's steps. ``kind`` is ``az`` or ``nnue``,
    ``order`` the trainer's place among those the process has made."""

    def __init__(self, kind: str, order: int) -> None:
        self.kind, self.order = kind, order
        self.steps = 0  # written by the stepping thread alone
        self._ring: Deque[Tuple[int, Dict[str, Any]]] = deque(maxlen=RING_STEPS)
        self._first_step_pending = True

    @property
    def trainer(self) -> str:
        """The ``trainer`` label of this record's series: kind and order."""
        return f"{self.kind}-{self.order}"

    def run(self, step: Callable[[Any, Any], Tuple[Any, Dict[str, Any]]], state: Any, batch: Any):
        """One ``.step`` of the trainer: ``step(state, batch)``, the first
        inside the ``train_first_step`` span, its metrics kept as returned."""
        if self._first_step_pending:
            self._first_step_pending = False
            with startup.first_step_span(self.kind):
                out = step(state, batch)
        else:
            out = step(state, batch)
        self._ring.append((self.steps, out[1]))
        self.steps += 1
        return out

    def _held(self) -> List[Tuple[int, Dict[str, Any]]]:
        while True:
            try:
                return list(self._ring)
            except RuntimeError:  # the stepping thread appended mid-copy: the writer takes no lock, so copy again
                continue

    def read(self, last: Optional[int] = None) -> Reading:
        """Fetch the ``last`` newest steps the ring holds (all of them by
        default) in one transfer. The reader waits for those steps to end
        on the device; the stepping thread does not wait for the reader."""
        held = self._held()
        if last is not None:
            held = held[-last:]
        fetched = jax.device_get([metrics for _step, metrics in held])
        first = held[0][0] if held else self.steps
        return Reading(self.trainer, first, [_scalars(metrics) for metrics in fetched])


class StepRecorder:
    """The process's trainers' records, held weakly in order of construction."""

    def __init__(self, registry: MetricsRegistry = REGISTRY) -> None:
        self._registry = registry
        self._lock = threading.Lock()  # a trainer's construction and a reader's listing: never on a step's path
        self._records: List["weakref.ref[StepRecord]"] = []
        self._made = 0
        self._collector: Optional[int] = None

    def attach(self, kind: str) -> StepRecord:
        """A new trainer's record; the trainer holds the one strong reference.
        The process's first is where its ``program_import`` span ends."""
        startup.record_process_spans()
        with self._lock:
            record = StepRecord(kind, self._made)
            self._made += 1
            self._records = [ref for ref in self._records if ref() is not None] + [weakref.ref(record)]
            if self._collector is None:
                self._collector = self._registry.register_collector(self.collect, name="train-steps")
        return record

    def records(self) -> List[StepRecord]:
        """The records of the trainers still alive, oldest trainer first."""
        with self._lock:
            return [record for record in (ref() for ref in self._records) if record is not None]

    def first_stepped(self) -> Optional[StepRecord]:
        """The oldest live trainer that has stepped, or None."""
        return next((record for record in self.records() if record.steps), None)

    def collect(self) -> List[MetricFamily]:
        """The exporter's series, resolved at scrape: the latest step of
        every live trainer that has stepped, one transfer a trainer."""
        latest = MetricFamily(
            "fishnet_train_step", "gauge", "Each scalar of the trainer's latest step metrics, by key")
        total = MetricFamily("fishnet_train_steps_total", "counter", "Steps the trainer has dispatched")
        for record in self.records():
            reading = record.read(last=1)
            total.samples.append(Sample("fishnet_train_steps_total", float(reading.steps), {"trainer": reading.trainer}))
            for metrics in reading.metrics:
                for key, value in metrics.items():
                    latest.samples.append(Sample("fishnet_train_step", value, {"trainer": reading.trainer, "key": key}))
        return [latest, total]


#: The process's recorder: every trainer attaches to it when made.
STEPS = StepRecorder()
