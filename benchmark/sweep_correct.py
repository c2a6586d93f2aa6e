"""Read the numbers every limit is set from, and judge them (run by hand on the chip).

    python3 benchmark/sweep_correct.py --config <name> --seeds 40 --control-seeds 8 --pool 65536
    python3 benchmark/sweep_correct.py --config <name> --seed-list 2700800011,2700034567 --pool 65536
    python3 benchmark/sweep_correct.py --config <name> --seeds 2 --mutate value_w:0

For each seed: the program against the reference, and for the first
``--control-seeds`` of them the reference in the configuration's
``control_precision`` against the reference, at the configuration's full
widths, in one process. ``--pool 65536`` is the cell's own pool: the
comparison then draws the positions a run of the cell draws for that seed.
Prints one line a comparison, then a table: for every number compared and
every tensor the median, second largest and largest sound reading, largest
over median (a reading several times the median measures how a seed's sum
cancels: a defect of the reference's conditioning to repair, never a reason
for a wider limit), the limit, and the smallest control reading.

Every reading is judged against the configuration's limits. Exit 1 if a
program seed is not ``correct`` or a control seed is. With ``--mutate
<tensor>:<factor>`` the program's gradient of that tensor is scaled by the
factor (``optimizer:<factor>`` scales the trainer's update instead; 0 is
an optimizer that does not update): the mathematics limits exist to catch
that, so then exit 1 if a mutated seed IS ``correct``. The benchmark's own
runs never run this file.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path
from typing import Any, Dict, List, Sequence

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def mutate(checker: Any, tensor: str, factor: float) -> None:
    """Break the program under the comparison: one gradient scaled, or the optimizer's update."""
    import jax
    import jax.numpy as jnp

    if tensor == "optimizer":
        step = checker.trainer.step

        def scaled_step(state, batch):
            before = jax.tree_util.tree_map(jnp.copy, state.params)  # the step donates its state
            new, metrics = step(state, batch)
            return new._replace(params=jax.tree_util.tree_map(lambda a, b: a + factor * (b - a), before, new.params)), metrics

        checker.trainer.step = scaled_step
        return
    program_grad = checker._program_grad

    def scaled_grad(params, batch):
        loss, grads = program_grad(params, batch)
        return loss, {**grads, tensor: factor * grads[tensor]}

    checker._program_grad = scaled_grad


def table(config: Dict[str, Any], compared: Sequence[str], program: List[Dict[str, Any]], control: List[Dict[str, Any]]) -> List[str]:
    """One line a number compared, then one a tensor: what a limit is set from."""
    limits = config["correct"]["limits"]
    names = list(dict.fromkeys([*limits, *compared]))
    names += [f"grad_rel_l2.{t}" for t in program[0]["_per_tensor"] if f"grad_rel_l2.{t}" not in names]
    lines = [f"{'number':<34} {'n':>3} {'median':>10} {'2nd':>10} {'largest':>10} {'lg/med':>7} {'limit':>8} {'lim/lg':>7} {'control>=':>10} {'ctl/lg':>7}"]
    for name in names:
        sound = sorted(r[name] for r in program)
        median, largest = statistics.median(sound), sound[-1]
        second = sound[-2] if len(sound) > 1 else float("nan")
        limit = limits.get(name)
        least = min((r[name] for r in control), default=float("nan"))
        lines.append(
            f"{name:<34} {len(sound):>3} {median:>10.4g} {second:>10.4g} {largest:>10.4g} {largest / max(median, 1e-300):>7.2f} "
            f"{limit if limit is not None else '-':>8} {(limit / max(largest, 1e-300)) if limit else float('nan'):>7.2f} "
            f"{least:>10.4g} {least / max(largest, 1e-300):>7.2f}")
    return lines


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--config", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--control-seeds", type=int, default=4)
    parser.add_argument("--first-seed", type=int, default=2_200_000_001)
    parser.add_argument("--seed-list", default="", help="these seeds, comma-separated, in place of --seeds from --first-seed")
    parser.add_argument("--pool", type=int, default=2048, help="pool positions the comparison samples from (the cells' own: 65536)")
    parser.add_argument("--batch", type=int, default=0, help="positions compared (default: the configuration's)")
    parser.add_argument("--matmul-precision", default="", help="diagnosis: the program's gradients under this "
                        "jax.default_matmul_precision (e.g. highest), to see what its default costs")
    parser.add_argument("--mutate", default="", metavar="TENSOR:FACTOR", help="the program's gradient of the tensor "
                        "(or the update of the 'optimizer') scaled by the factor: every seed then has to read not correct")
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
    if args.mutate:
        tensor, factor = args.mutate.rsplit(":", 1)
        mutate(checker, tensor, float(factor))

    seeds = [int(s) for s in args.seed_list.split(",")] if args.seed_list else [args.first_seed + 7919 * i for i in range(args.seeds)]
    readings: Dict[str, List[Dict[str, Any]]] = {"program": [], "control": []}
    mutated = f" mutated {args.mutate}" if args.mutate else ""
    wrong = []
    for i, seed in enumerate(seeds):
        pool = positions.playout_pool(traffic, seed, family, args.pool)
        for who in ("program", "control")[: 2 if i < args.control_seeds else 1]:
            numbers = checker.compare(pool, seed, control=(who == "control"))
            readings[who].append(numbers)
            agrees, line = correctness.judge(numbers, config)
            if agrees != (who == "program" and not args.mutate):
                wrong.append(f"seed {seed} {who}{mutated if who == 'program' else ''}: {'correct' if agrees else 'NOT correct'}: {line}")
            print(json.dumps({"config": args.config, "seed": seed, "pool": args.pool, "who": who, "mutate": args.mutate, "correct": agrees,
                              **{k: v for k, v in numbers.items() if not k.startswith("grad_rel_l2.")}}), flush=True)
            print(f"seed {seed} {who} correct: {line}", flush=True)
    print("\n".join(table(config, correctness.COMPARED, readings["program"], readings["control"])))
    print(f"{args.config}: {len(readings['program'])} program seeds{mutated}, "
          f"{len(readings['control'])} control seeds, pool {args.pool}; judged against the configuration's limits: "
          f"{'as expected' if not wrong else str(len(wrong)) + ' NOT as expected'}")
    for line in wrong:
        print(line)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
