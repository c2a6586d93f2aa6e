"""A cell's traced steps BY OPERATION: every device operation of the step joined to its scope by name, as the benchmark's
``scopes:`` lines are (``benchmark/scopes.py``), but kept apart, one line an operation.

    python3 tools/scope_ops.py --workload gdn_trunk_train_b128 --match 'layer00\\.(gdn|delta)|unscoped' [--seed 1] [--steps 4] [--least 0.01]

runs the cell as its runner does (the family's trainer, the seeded pool, the feed; the warm-up steps, then ``--steps`` steps
under the profiler) WITHOUT the window and without ``correct`` (minutes on the delta cells), and prints, for every operation
whose line ``<phase> <scope path> <name> <shape> <opcodes> <primitive>`` matches ``--match`` and that takes ``--least`` ms a step
or more, its device ms a step (self time: a loop's body is not counted twice), largest first; then one JSON line: the device,
the step's device ms, and the matched operations' ms summed by phase and scope path. ``<primitive>`` is the last part of the
instruction's ``op_name`` (``reduce_sum``, ``mul``, ``pallas_call``), ``-`` where it has none. Chip only: a time is a device
time or nothing. ``rows`` is the reduction alone, which ``tests/test_mamba_mix.py`` runs on a recorded trace of two operations.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import tempfile
from pathlib import Path
from typing import Dict, List, Tuple

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

_PRIMITIVE = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = .*op_name=\"[^\"]*?([\w.\-]+)\"")


def rows(trace, hlo_text: str, match: str) -> Tuple[float, List[Tuple[float, str]]]:
    """The traced steps' device ms a step, and ``(ms a step, line)`` of every operation whose line matches, largest first."""
    from benchmark import scopes, tracelib

    known = scopes.instructions(hlo_text)
    primitive = dict(m.groups() for m in map(_PRIMITIVE.match, hlo_text.splitlines()) if m)
    steps = tracelib.step_modules(trace)
    wanted, taken, total = re.compile(match), {}, 0.0
    for _name, start, dur in steps:
        ops = tracelib.ops_in(trace, (start, start + dur))
        for op, self_ns in zip(ops, scopes._self_ns(ops)):
            instr = known.get(op.name) or scopes.Instruction(op.shape, "unscoped", "(not in the step's text)", "")
            line = f"{instr.phase} {instr.path.replace(' ', '_')} {op.name} {op.shape} {','.join(k for k in op.kinds if k in tracelib.INTERESTING) or '-'} {primitive.get(op.name, '-')}"
            total += self_ns
            if wanted.search(line):
                taken[line] = taken.get(line, 0.0) + self_ns
    per_step = 1e6 * max(len(steps), 1)
    return total / per_step, sorted(((ns / per_step, line) for line, ns in taken.items()), reverse=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--match", required=True, help="a regular expression over '<phase> <scope path> <name> <shape> <opcodes> <primitive>'")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--steps", type=int, default=4)
    parser.add_argument("--least", type=float, default=0.01, help="ms a step under which an operation is summed but not printed")
    args = parser.parse_args(argv)

    import jax

    from benchmark import device, positions, tracelib
    from benchmark.registry import Registry
    from benchmark.runners.train_step import Feed, StepLoop, seed31

    registry = Registry(REPO)
    cell = registry.workload(args.workload)
    devices = device.require_tpu(int(cell["chips"]))
    config, traffic = registry.config(cell["config"]), registry.traffic(cell["traffic"])
    family = registry.module("families", config["family"])
    trainer = family.make_trainer(config)
    state = trainer.init(seed31(args.seed))
    pool = positions.playout_pool(traffic, args.seed, family)
    feed = Feed(family, pool, int(config["train"]["batch"]), args.seed, int(traffic["prefetch_batches"]))
    feed.start()
    try:
        loop = StepLoop(trainer, state, feed)
        for _ in range(int(cell["warmup_steps"]) + 1):
            loop.step()
        with tempfile.TemporaryDirectory(prefix="scope-ops-") as trace_dir:  # as the runner's ``_traced_steps``, but the step's text is lowered ONCE (a minute on a delta cell)
            jax.profiler.start_trace(trace_dir)
            try:
                for _ in range(args.steps):
                    loop.step()
                loop.drain()
            finally:
                jax.profiler.stop_trace()
            text = family.step_hlo_text(loop.trainer, loop.state, loop.last_batch)
            trace = tracelib.load_xplane(tracelib.find_xplane(trace_dir), tracelib.hlo_kinds(text))
    finally:
        feed.stop()
    step_ms, found = rows(trace, text, args.match)
    sums: Dict[str, float] = {}
    for ms, line in found:
        if ms >= args.least:
            print(f"{ms:9.4f} ms  {line}")
        key = " ".join(line.split(" ")[:2])
        sums[key] = sums.get(key, 0.0) + ms
    print(json.dumps({"device": devices[0].device_kind, "workload": args.workload, "seed": args.seed, "steps": args.steps, "match": args.match,
                      "step_device_ms": step_ms, "matched_ms": sum(ms for ms, _ in found), "by_scope_ms": dict(sorted(sums.items()))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
