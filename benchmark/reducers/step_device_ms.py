"""Median device-busy time inside one execution of the step program (profiler trace)."""

from benchmark import tracelib


def reduce(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    busy = [
        tracelib.busy_ns(trace, (start, start + dur)) / 1e6
        for _name, start, dur in tracelib.step_modules(trace)
    ]
    return tracelib.percentile(busy, 50) if busy else None
