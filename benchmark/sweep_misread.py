"""The program against a reference that MISREADS the layer (run by hand on the chip).

    python3 benchmark/sweep_misread.py --config kimi-linear-trunk-train --misread decay_after,unit_beta --seeds 2 --pool 65536

``sweep_correct.py --mutate`` scales one gradient of the program; what it
cannot do is change the mathematics the program is compared WITH. A
reference that takes a ``model["misread"]`` (``reference/kda_trunk.py``:
the decay applied after the rank-one correction, beta fixed at 1, the head
norm over the gated head) computes a plausible other layer; the program,
which computes the published one, then has to come out NOT correct by the
configuration's limits, at the configuration's full widths. One line a
comparison; exit 1 if a misread reference agrees with the program. The
benchmark's own runs never run this file.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--config", required=True)
    parser.add_argument("--misread", required=True, help="comma-separated values of model['misread'] that the configuration's reference knows")
    parser.add_argument("--seeds", type=int, default=1)
    parser.add_argument("--first-seed", type=int, default=2_400_000_007)
    parser.add_argument("--pool", type=int, default=2048, help="pool positions the comparison samples from (the cells' own: 65536)")
    args = parser.parse_args(argv)

    from benchmark import correctness, device, positions
    from benchmark.registry import Registry

    registry = Registry(REPO)
    device.require_tpu(1)
    config = registry.config(args.config)
    family = registry.module("families", config["family"])
    reference = registry.module("reference", config["family"])
    traffic = registry.traffic("playout_pool")
    agreed = []
    for misread in args.misread.split(","):
        misreading = {**config, "model": {**config["model"], "misread": misread}}
        checker = correctness.Checker(family, reference, misreading)
        for seed in (args.first_seed + 7919 * i for i in range(args.seeds)):
            numbers = checker.compare(positions.playout_pool(traffic, seed, family, args.pool), seed)
            agrees, line = correctness.judge(numbers, misreading)
            print(json.dumps({"config": args.config, "seed": seed, "pool": args.pool, "misread": misread, "correct": agrees}), flush=True)
            print(f"seed {seed} misread {misread} correct: {line}", flush=True)
            if agrees:
                agreed.append((misread, seed))
        del checker
    print(f"{args.config}: the program against {args.misread}: {'every one NOT correct, as expected' if not agreed else f'CORRECT on {agreed}'}")
    return 1 if agreed else 0


if __name__ == "__main__":
    sys.exit(main())
