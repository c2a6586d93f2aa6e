"""The block-masked attention core's share of its roofline: the least time
the chip could take for a step's scores, softmax and mix of every board,
query head and ALLOWED (query, key) pair of both copies, forward and gradient
(roofline/bd_core.py, from shapes and the configuration's stated precision
alone, whatever implements the core) over the summed device time a traced
step of the masked kernel pair's operations, found as ``gqa_core_roofline``
finds the plain pair's, by the names their ``pallas_call``s give them in the
compiled step: ``board_attention_blocks``, ``board_attention_blocks_grad``
(``doc/observability.md`` "Training and compilation"). None without a trace,
for another family's configuration, or where no operation of that name ran
(a program whose core is not that kernel pair)."""

import re

from benchmark import tracelib

_KERNEL = re.compile(r"^board_attention_blocks(_grad)?(\.\d+)?$")


def reduce(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if trace is None or config["family"] != "sdar_trunk":
        return None
    steps = len(tracelib.step_modules(trace))
    core_s = sum(o.dur_ns for o in tracelib.ops_in(trace, tracelib.window(trace)) if _KERNEL.match(o.name)) / 1e9 / max(steps, 1)
    if not core_s:
        return None
    roofline = ctx["registry"].module("roofline", "bd_core")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"bd_core_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}; {roofline.allowed_pairs(config['model'])} allowed pairs a board and head) "
          f"over {1e3 * core_s:.3f} ms of board_attention_blocks and board_attention_blocks_grad calls a step")
    return 100.0 * least["least_s"] / core_s
