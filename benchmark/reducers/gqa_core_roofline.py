"""The grouped-query attention core's share of its roofline: the least time
the chip could take for a step's scores, softmax and mix of every board and
query head, forward and gradient (roofline/gqa_core.py, from shapes and the
configuration's stated precision alone, whatever implements the core) over
the summed device time a traced step of the kernel pair's operations, found
as ``mla_core_roofline`` finds them, by the names their ``pallas_call``s give
them in the compiled step: ``board_attention``, ``board_attention_grad``
(``doc/observability.md`` "Training and compilation"). None without a trace,
for another family's configuration, or where no operation of that name ran
(a program whose core is not the kernel pair)."""

import re

from benchmark import tracelib

_KERNEL = re.compile(r"^board_attention(_grad)?(\.\d+)?$")


def reduce(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if trace is None or config["family"] != "mellum_trunk":
        return None
    steps = len(tracelib.step_modules(trace))
    core_s = sum(o.dur_ns for o in tracelib.ops_in(trace, tracelib.window(trace)) if _KERNEL.match(o.name)) / 1e9 / max(steps, 1)
    if not core_s:
        return None
    roofline = ctx["registry"].module("roofline", "gqa_core")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"gqa_core_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) "
          f"over {1e3 * core_s:.3f} ms of board_attention and board_attention_grad calls a step")
    return 100.0 * least["least_s"] / core_s
