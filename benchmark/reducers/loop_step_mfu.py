"""A looped trunk's step as a share of the chip's bfloat16 peak: the step's
matrix work from shapes alone (``roofline/loop_step.py``: three products a
weight and use over ``total_ut_steps`` passes, the attention cores' seven
products a board, head and pass, the heads' products a pass, the embedding's;
nothing remade is counted, so recomputation lowers the share) over the median
device-busy time of a traced step (what ``step_device_ms`` reports) times the
device's bf16 peak of ``peaks.json``, in percent. None without a trace or for
a configuration that is no looped trunk's."""

from benchmark import tracelib


def reduce(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if trace is None or config["family"] != "ouro_trunk":
        return None
    busy = [tracelib.busy_ns(trace, (start, start + dur)) / 1e9 for _name, start, dur in tracelib.step_modules(trace)]
    if not busy:
        return None
    step_s = tracelib.percentile(busy, 50)
    roofline = ctx["registry"].module("roofline", "loop_step")
    flops = roofline.step_flops(config["model"], ctx["batch"])
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"loop_step_mfu: {flops['all'] / 1e12:.3f} TFLOP a step (layers {flops['layers'] / 1e12:.3f}, cores {flops['cores'] / 1e12:.3f}, "
          f"heads {flops['heads'] / 1e12:.3f}, embed {flops['embed'] / 1e12:.4f}), least {1e3 * least:.3f} ms over {1e3 * step_s:.3f} ms of device time a step")
    return 100.0 * least / step_s
