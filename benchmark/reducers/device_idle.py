"""Share of the traced steps in which no operation ran on the device."""

from benchmark import tracelib


def reduce(ctx):
    trace = ctx["trace"]
    if trace is None or not trace.modules:
        return None
    lo, hi = tracelib.window(trace)
    return 100.0 * (1.0 - tracelib.busy_ns(trace, (lo, hi)) / (hi - lo))
