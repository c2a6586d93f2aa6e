"""Positions trained per second: batch x steps completed in the window
over the window's seconds, feed included."""


def reduce(ctx):
    if not ctx["steps"]:
        return None
    return ctx["batch"] * ctx["steps"] / ctx["window_s"]
