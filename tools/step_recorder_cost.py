"""What the trainers' step recorder costs when it is read, on the chip.

    python3 tools/step_recorder_cost.py --config <benchmark configuration> [--scrape-seconds S]

Builds the configuration's trainer through the benchmark's own family
adapter at its full widths and batch, steps it on one fixed batch, and
prints:

* the sha256 of the compiled step program's text without metadata
  (``benchmark/scopes.py without_metadata``), lowered before anything
  steps: equal on two trees **run from the same path** means the device
  runs the same program (a Pallas kernel's module keeps the absolute file
  names of its trace's call stack; this line needs no recorder, so the
  parent of the PR that added it prints it too);
* 70 steps under ``jax.transfer_guard_device_to_host("disallow_explicit")``:
  the step's path fetches nothing;
* the time of ``StepRecord.read()`` over a full ring, five times;
* with ``--scrape-seconds``: step times (between consecutive loss
  fetches, as the benchmark's runner takes them) over alternating spans
  with and without a ``GET /metrics`` once a second from another thread,
  and the time of each scrape.

Times are a host clock's on a TPU; without one this exits 4 like the
benchmark. doc/observability.md "Training and compilation" is the contract.
"""

from __future__ import annotations

import argparse
import hashlib
import statistics
import sys
import threading
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
POOL = 4096  # positions the one batch is drawn from
sys.path.insert(0, str(REPO))


def stepped(trainer, state, batch, seconds):
    """Step for ``seconds``; the intervals between consecutive loss fetches."""
    fetched, pending = [time.monotonic()], None
    while fetched[-1] - fetched[0] < seconds:
        state, metrics = trainer.step(state, batch)
        previous, pending = pending, metrics
        if previous is not None:
            float(previous["loss"])
            fetched.append(time.monotonic())
    float(pending["loss"])
    return state, [b - a for a, b in zip(fetched[1:], fetched[2:])]


def p90(values):
    return statistics.quantiles(values, n=10)[-1]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, default=3700000001)
    parser.add_argument("--scrape-seconds", type=float, default=0.0)
    parser.add_argument("--text-out", help="write the step program's text without metadata here, to diff two trees' by hand")
    args = parser.parse_args()

    import jax
    import numpy as np

    from benchmark import device, positions, scopes
    from benchmark.registry import Registry
    from benchmark.runners.train_step import seed31

    device.require_tpu(1)
    registry = Registry(REPO)
    config = registry.config(args.config)
    family = registry.module("families", config["family"])
    trainer = family.make_trainer(config)
    state = trainer.init(seed31(args.seed))
    pool = positions.playout_pool(registry.traffic("playout_pool"), args.seed, family, POOL)
    batch = jax.device_put(family.build_batch(pool, np.arange(int(config["train"]["batch"])) % POOL))
    # lowered before the first ``.step``: a Pallas kernel's module keeps the Python call stack of its trace as debug
    # locations, outside ``metadata={...}``, and a trace made inside ``.step`` would hold the frames of ``.step``'s own path
    text = scopes.without_metadata(family.step_hlo_text(trainer, state, batch))
    print(f"{args.config}: step program sha256 {hashlib.sha256(text.encode()).hexdigest()} ({len(text)} bytes without metadata)")
    if args.text_out:
        Path(args.text_out).write_text(text)
    state, metrics = trainer.step(state, batch)
    record = getattr(trainer, "_record", None)
    if record is None:
        print("no step recorder in this tree")
        return 0

    with jax.transfer_guard_device_to_host("disallow_explicit"):
        for _ in range(70):
            state, metrics = trainer.step(state, batch)
    jax.block_until_ready(metrics)
    reads = []
    for _ in range(5):
        started = time.monotonic()
        reading = record.read()
        reads.append(1e3 * (time.monotonic() - started))
    print(f"read(): {len(reading.metrics)} steps of {len(reading.metrics[-1])} keys, trainer {reading.trainer}; "
          f"ms {' '.join(f'{ms:.3f}' for ms in reads)}; latest {reading.metrics[-1]}")

    if args.scrape_seconds:
        from fishnet_tpu import telemetry

        exporter = telemetry.start_exporter(0)
        scrapes, scraping, stop = [], threading.Event(), threading.Event()

        def scrape_each_second():
            while not stop.wait(1.0):
                if scraping.is_set():
                    started = time.monotonic()
                    with urllib.request.urlopen(exporter.url + "/metrics", timeout=30) as reply:
                        body = reply.read().decode()
                    scrapes.append((1e3 * (time.monotonic() - started), body.count("fishnet_train_step{")))

        scraper = threading.Thread(target=scrape_each_second, daemon=True)
        scraper.start()
        try:
            for span in range(4):
                scraped = bool(span % 2)
                (scraping.set if scraped else scraping.clear)()
                state, intervals = stepped(trainer, state, batch, args.scrape_seconds)
                print(f"{'scraped each second' if scraped else 'not scraped':>19}: {len(intervals)} steps, step ms median "
                      f"{1e3 * statistics.median(intervals):.3f} p90 {1e3 * p90(intervals):.3f} max {1e3 * max(intervals):.3f}")
        finally:
            stop.set()
            scraper.join(timeout=60)
            exporter.close()
        print(f"scrapes: {len(scrapes)}, ms {' '.join(f'{ms:.2f}' for ms, _n in scrapes)}; "
              f"fishnet_train_step series a scrape {sorted({n for _ms, n in scrapes})}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
