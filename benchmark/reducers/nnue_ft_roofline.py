"""The feature transformer's share of its roofline: the least time the
chip could take to move the ACTIVE rows (roofline/nnue_ft.py) over the
summed device time of the operations that gather from or scatter into a
table of ``num_features`` rows, per traced step."""

import numpy as np

from benchmark import tracelib


def reduce(ctx):
    trace, config = ctx["trace"], ctx["config"]
    if trace is None or config["family"] != "nnue":
        return None
    model = config["model"]
    steps = len(tracelib.step_modules(trace))
    table = f"[{model['num_features']},"
    rows = f"[{ctx['batch'] * 2 * model['max_active']},"
    ft_ns = sum(
        o.dur_ns for o in tracelib.ops_in(trace, tracelib.window(trace))
        if {"gather", "scatter"} & set(o.kinds) and (table in o.shape or rows in o.shape)
    )
    ft_s = ft_ns / 1e9 / max(steps, 1)
    if not ft_s:
        return None
    active_per_position = float(np.mean(np.sum(ctx["pool"]["indices"] < model["num_features"], axis=(1, 2))))
    roofline = ctx["registry"].module("roofline", "nnue_ft")
    least = roofline.least_seconds(model, ctx["batch"], active_per_position * ctx["batch"],
                                   ctx["registry"].peaks(ctx["device_kind"]))
    print(f"nnue_ft_roofline: {least['bound']}-bound, {active_per_position:.2f} active rows a position "
          f"of {2 * model['max_active']} slots, least {1e3 * least['least_s']:.3f} ms over "
          f"{1e3 * ft_s:.3f} ms of gather and scatter operations a step")
    return 100.0 * least["least_s"] / ft_s
