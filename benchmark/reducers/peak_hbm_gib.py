"""Peak memory taken on the fullest device after the window, from
``memory_stats()`` (arrays in use plus the programs' reserved scratch)."""


def reduce(ctx):
    return ctx["memory_peak_bytes"] / 2**30 if ctx["memory_peak_bytes"] else None
