"""Read the two numbers every limit is set from (run by hand on the chip).

    python3 benchmark/sweep_correct.py --config <name> --seeds 24 --control-seeds 8 --pool 65536

For each seed: the program against the reference, and for the control
seeds the reference in the configuration's ``control_precision`` against
the reference, at the configuration's full widths, in one process. Prints
one line a comparison and the largest sound reading beside the smallest
control reading for each number. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=4)
    parser.add_argument("--first-seed", type=int, default=2_200_000_001)
    parser.add_argument("--pool", type=int, default=2048, help="pool positions the comparison samples from")
    parser.add_argument("--batch", type=int, default=0, help="positions compared (default: the configuration's)")
    parser.add_argument("--matmul-precision", default="", help="diagnosis: the program's gradients under this "
                        "jax.default_matmul_precision (e.g. highest), to see what its default costs")
    args = parser.parse_args(argv)

    from benchmark import correctness, device, positions
    from benchmark.registry import Registry

    registry = Registry(REPO)
    device.require_tpu(1)
    config = registry.config(args.config)
    if args.batch:
        config["correct"]["batch"] = args.batch
        config["correct"]["chunk"] = min(args.batch, config["correct"].get("chunk", args.batch))
    family = registry.module("families", config["family"])
    reference = registry.module("reference", config["family"])
    traffic = registry.traffic("playout_pool")
    checker = correctness.Checker(family, reference, config)
    if args.matmul_precision:
        import jax

        program_grad = checker._program_grad

        def with_precision(params, batch):
            with jax.default_matmul_precision(args.matmul_precision):
                return program_grad(params, batch)

        checker._program_grad = with_precision
    readings = {"program": [], "control": []}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        pool = positions.playout_pool(traffic, seed, family, args.pool)
        for who in ("program", "control")[: 2 if i < args.control_seeds else 1]:
            numbers = checker.compare(pool, seed, control=(who == "control"))
            readings[who].append(numbers)
            print(json.dumps({"config": args.config, "seed": seed, "who": who, **{k: v for k, v in numbers.items() if not k.startswith("grad_rel_l2.")}}), flush=True)
    for name in correctness.COMPARED:
        sound = max(r[name] for r in readings["program"])
        line = f"{args.config} {name}: largest sound {sound:.6g}"
        if readings["control"]:
            control = min(r[name] for r in readings["control"])
            line += f", smallest control {control:.6g}, ratio {control / max(sound, 1e-300):.3g}"
        print(line)
    tensors = readings["program"][0]["_per_tensor"]
    for tensor in tensors:
        sound = max(r["_per_tensor"][tensor] for r in readings["program"])
        control = min((r["_per_tensor"][tensor] for r in readings["control"]), default=float("nan"))
        print(f"  tensor {tensor}: largest sound {sound:.4g}, smallest control {control:.4g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
