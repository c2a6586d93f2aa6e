"""The one-head attention core's share of its roofline in a looped trunk: the
least time the chip could take for a step's scores, softmax and mix of every
board, head and PASS, forward and gradient, over the summed device time a
traced step of the kernel pair's operations, found as ``gqa_core_roofline``
finds them, by the names their ``pallas_call``s give them in the compiled
step: ``board_attention``, ``board_attention_grad``.

The count is ``roofline/gqa_core.py``'s own, a layer's (``layer_flops``,
``layer_bytes``: the seven products a board and query head, every operand's
bytes once), which takes the heads as arguments: at ``num_key_value_heads`` =
``num_attention_heads`` it is the count of a group of ONE (no key shared, k
and v as wide as q), and the absence of a qk-norm changes nothing in it (the
norm is elementwise over operands counted once). What this reducer adds is
the calls: ``num_hidden_layers x total_ut_steps`` of them a step, where
``gqa_core``'s ``least_seconds`` counts a call a kept layer. None without a
trace, for another family's configuration, or where no operation of that
name ran."""

import re

from benchmark import tracelib

_KERNEL = re.compile(r"^board_attention(_grad)?(\.\d+)?$")


def reduce(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if trace is None or config["family"] != "ouro_trunk":
        return None
    steps = len(tracelib.step_modules(trace))
    core_s = sum(o.dur_ns for o in tracelib.ops_in(trace, tracelib.window(trace)) if _KERNEL.match(o.name)) / 1e9 / max(steps, 1)
    if not core_s:
        return None
    model, peaks = config["model"], ctx["registry"].peaks(ctx["device_kind"])
    count = ctx["registry"].module("roofline", "gqa_core")
    calls = model["num_hidden_layers"] * model["total_ut_steps"]
    compute = calls * count.layer_flops(model, ctx["batch"]) / peaks["bf16_flops_per_s"]
    memory = calls * count.layer_bytes(model, ctx["batch"]) / peaks["hbm_bytes_per_s"]
    least = max(compute, memory)
    print(f"mha_core_roofline: {'compute' if compute >= memory else 'memory'}-bound, least {1e3 * least:.3f} ms (compute {1e3 * compute:.3f}, memory {1e3 * memory:.3f}) "
          f"for {calls} calls each way over {1e3 * core_s:.3f} ms of board_attention and board_attention_grad calls a step")
    return 100.0 * least / core_s
