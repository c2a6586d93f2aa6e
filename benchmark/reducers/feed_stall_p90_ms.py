"""90th percentile of the time a step waited on the feed queue in the window."""

from benchmark import tracelib


def reduce(ctx):
    waits = ctx["spans_s"]["feed_wait"]
    return 1e3 * tracelib.percentile(waits, 90) if waits else None
