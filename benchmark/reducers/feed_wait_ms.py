"""Mean time a step waited on the feed queue in the window (harness span)."""


def reduce(ctx):
    waits = ctx["spans_s"]["feed_wait"]
    return 1e3 * sum(waits) / len(waits) if waits else None
