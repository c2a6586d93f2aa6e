"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell on the machine it is started on, which has to hold the
TPU chips the cell asks for, and prints one JSON object as the last line
of its output. See benchmark/README.md.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python can say

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmark import device
    from benchmark.registry import BenchmarkError, Registry

    try:
        registry = Registry(REPO)
        cell = registry.workload(args.workload)
        devices = device.require_tpu(int(cell["chips"]))
        registry.peaks(devices[0].device_kind)  # an unknown device is an error, not a default
        runner = registry.module("runners", cell["runner"])
        result = runner.run(registry, cell, args.seed, args.seconds, bool(args.trace), T0, devices)
    except BenchmarkError as err:
        sys.stderr.write(f"benchmark: {err}\n")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
