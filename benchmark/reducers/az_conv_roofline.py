"""The convolution tower's share of its roofline: the least time the
chip could take for the step's matrix work (roofline/az_conv.py) over
the summed device time of the operations that run a convolution or a
matrix product, per traced step."""

from benchmark import tracelib


def reduce(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if trace is None or config["family"] != "az":
        return None
    steps = len(tracelib.step_modules(trace))
    conv_s = tracelib.kind_time_ns(trace, ("convolution", "dot")) / 1e9 / max(steps, 1)
    if not conv_s:
        return None
    roofline = ctx["registry"].module("roofline", "az_conv")
    least = roofline.least_seconds(config["model"], ctx["batch"], ctx["registry"].peaks(ctx["device_kind"]))
    print(f"az_conv_roofline: {least['bound']}-bound, least {1e3 * least['least_s']:.3f} ms "
          f"(compute {1e3 * least['compute_s']:.3f}, memory {1e3 * least['memory_s']:.3f}) "
          f"over {1e3 * conv_s:.3f} ms of convolution operations a step")
    return 100.0 * least["least_s"] / conv_s
