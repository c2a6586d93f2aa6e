"""Process start to the first step of the window: imports, the position
pool, parameter initialisation, compilation or its cache, warm-up steps."""


def reduce(ctx):
    return ctx["setup_s"]
