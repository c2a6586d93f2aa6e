"""90th percentile of the time between consecutive loss fetches in the window."""

from benchmark import tracelib


def reduce(ctx):
    intervals = ctx["step_intervals_s"]
    if not intervals:
        return None
    print(f"step_ms_p90: {len(intervals)} samples, median {1e3 * tracelib.percentile(intervals, 50):.4f} ms")
    return 1e3 * tracelib.percentile(intervals, 90)
