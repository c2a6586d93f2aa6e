"""Runner ``train_step``: one learner stepping a trainer on fed batches.

A feed thread samples the position pool uniformly with replacement,
builds the dense arrays and keeps ``prefetch_batches`` ahead; the loop is
closed (one learner). The step loop ships a batch, dispatches step k and
only then fetches step k-1's loss, so the measurement never drains the
device. A step counts when its loss has reached the host; a step time is
the interval between two consecutive fetches. The window runs from the
fetch that ends warm-up to the first fetch at or after ``--seconds``:
every step completed in it and all of its time, feed included.
"""

from __future__ import annotations

import math
import queue
import sys
import tempfile
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmark import correctness, device, positions, tracelib


class CompileCounter:
    """Counts backend compilations (a program loaded from the persistent
    cache counts too: inside the window neither may happen)."""

    def __init__(self) -> None:
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw: Any) -> None:
        if name.endswith("backend_compile_duration"):
            self.count += 1


class Feed(threading.Thread):
    """Builds batches ahead of the step loop. An error in it reaches the
    loop through the queue instead of dying with the thread."""

    def __init__(self, family: Any, pool: Dict[str, np.ndarray], batch: int, seed: int, depth: int) -> None:
        super().__init__(name="feed", daemon=True)
        self.family, self.pool, self.batch = family, pool, batch
        self.rng = np.random.default_rng([int(seed), 0x66656564])
        self.queue: "queue.Queue[Any]" = queue.Queue(maxsize=depth)
        self.stopping = threading.Event()

    def run(self) -> None:
        n_pool = len(next(iter(self.pool.values())))
        while not self.stopping.is_set():
            try:
                item = self.family.build_batch(self.pool, self.rng.integers(0, n_pool, self.batch))
            except Exception as err:  # handed to the loop, which raises it
                item = err
            while not self.stopping.is_set():
                try:
                    self.queue.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def stop(self) -> None:
        self.stopping.set()
        self.join(timeout=30)
        if self.is_alive():
            raise RuntimeError("the feed thread did not stop")


class StepLoop:
    def __init__(self, trainer: Any, state: Any, feed: Feed) -> None:
        self.trainer, self.state, self.feed = trainer, state, feed
        self.pending: Optional[Dict[str, Any]] = None
        self.spans: Dict[str, List[float]] = {name: [] for name in tracelib.HOST_SPANS}
        self.last_batch: Any = None

    @contextmanager
    def span(self, name: str):
        start = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans[name].append(time.monotonic() - start)

    def step(self) -> Optional[float]:
        """Ship and dispatch one step; fetch the loss of the one before it."""
        with self.span("feed_wait"):
            batch = self.feed.queue.get()
        if isinstance(batch, Exception):
            raise batch
        with self.span("h2d"):
            self.last_batch = jax.device_put(batch)
        with self.span("dispatch"):
            self.state, metrics = self.trainer.step(self.state, self.last_batch)
        previous, self.pending = self.pending, metrics
        if previous is None:
            return None
        with self.span("loss_fetch"):
            return float(previous["loss"])

    def drain(self) -> Optional[float]:
        previous, self.pending = self.pending, None
        return None if previous is None else float(previous["loss"])


def seed31(seed: int) -> int:
    """Any whole ``--seed`` folded into what a PRNG key takes everywhere."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


def run(registry: Any, cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t0: float, devices: List[Any]) -> Dict[str, Any]:
    config = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    family = registry.module("families", config["family"])
    reference = registry.module("reference", config["family"])
    batch = int(config["train"]["batch"])
    compiles = CompileCounter()

    trainer = family.make_trainer(config)
    state = trainer.init(seed31(seed))
    started = time.monotonic()
    pool = positions.playout_pool(traffic, seed, family)
    print(f"pool: {len(next(iter(pool.values())))} positions in {time.monotonic() - started:.2f} s")

    feed = Feed(family, pool, batch, seed, int(traffic["prefetch_batches"]))
    feed.start()
    failed, losses, fetch_times = 0, [], []
    trace_data = None
    try:
        loop = StepLoop(trainer, state, feed)
        warm = 0
        while warm < int(cell["warmup_steps"]):
            if loop.step() is not None:
                warm += 1
        t_start = time.monotonic()
        setup_s = t_start - t0
        compiles_before = compiles.count
        for values in loop.spans.values():
            values.clear()
        fetch_times.append(t_start)
        try:
            while fetch_times[-1] - t_start < seconds:
                losses.append(loop.step())
                fetch_times.append(time.monotonic())
        except Exception as err:  # a step that raised is a failed operation
            failed += 1
            sys.stderr.write(f"benchmark: a step raised inside the window: {err!r}\n")
        compiles_in_window = compiles.count - compiles_before
        window_spans = {name: list(values) for name, values in loop.spans.items()}
        if not failed:
            loop.drain()
        peak_bytes = device.memory_peak_bytes(devices)
        print(f"memory per device: {device.memory_lines(devices)}")
        if trace and not failed:
            trace_data = _traced_steps(loop, family, int(cell["trace_steps"]))
    finally:
        feed.stop()

    window_s = fetch_times[-1] - t_start
    steps = len(fetch_times) - 1
    intervals = [b - a for a, b in zip(fetch_times, fetch_times[1:])]
    failed += sum(1 for x in losses if x is None or not math.isfinite(x))
    print(
        f"window: {steps} steps of {batch} in {window_s:.4f} s; step time samples {len(intervals)}; "
        f"compilations inside the window {compiles_in_window}; "
        f"feed lag mean {1e3 * float(np.mean(window_spans['feed_wait'] or [0.0])):.3f} ms "
        f"max {1e3 * float(np.max(window_spans['feed_wait'] or [0.0])):.3f} ms; "
        f"last loss {losses[-1] if losses else None}"
    )

    numbers = correctness.Checker(family, reference, config).compare(pool, seed)
    agrees, line = correctness.judge(numbers, config)
    print(f"correct: {line}")
    correct = bool(agrees and compiles_in_window == 0 and failed == 0 and steps > 0)

    context = {
        "registry": registry, "cell": cell, "config": config, "traffic": traffic, "pool": pool,
        "batch": batch, "steps": steps, "window_s": window_s, "setup_s": setup_s,
        "step_intervals_s": intervals, "spans_s": window_spans, "trace": trace_data,
        "device_kind": devices[0].device_kind, "memory_peak_bytes": peak_bytes,
    }
    report = device.report(devices, peak_bytes)
    result: Dict[str, Any] = {"correct": correct, "attempted": steps + failed, "failed": failed}
    if trace:
        result["metrics"] = _reduce(registry, cell["name"], "per_layer", context)
        if trace_data is not None:
            lo, hi = tracelib.window(trace_data)
            report["busy_s"] = tracelib.busy_ns(trace_data, (lo, hi)) / 1e9
            report["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = {
                "device_ops": [list(x) for x in tracelib.top_ops(trace_data)],
                "idle_gaps": [list(x) for x in tracelib.idle_gaps(trace_data)],
            }
    else:
        result["metrics"] = _reduce(registry, cell["name"], "end_to_end", context)
    result["device"] = report
    return result


def _traced_steps(loop: StepLoop, family: Any, n_steps: int) -> Optional[tracelib.Trace]:
    """A few steps under the profiler, after the window, reduced to a ``Trace``."""
    with tempfile.TemporaryDirectory(prefix="benchmark-trace-") as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for i in range(n_steps):
                with jax.profiler.StepTraceAnnotation("train_step", step_num=i):
                    loop.step()
            loop.drain()
        finally:
            jax.profiler.stop_trace()
        kinds = tracelib.hlo_kinds(family.step_hlo_text(loop.trainer, loop.state, loop.last_batch))
        data = tracelib.load_xplane(tracelib.find_xplane(trace_dir), kinds)
    if not data.modules:
        print("trace: no program ran on a traced device; the device metrics are left out")
        return None
    return data


def _reduce(registry: Any, cell: str, section: str, context: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each metric of the section through its own reducer; one that finds
    nothing to read returns None and is left out."""
    metrics: Dict[str, Dict[str, Any]] = {}
    for entry in registry.metrics(section, cell):
        value = registry.module("reducers", entry["name"]).reduce(context)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return metrics
