"""Device ms a traced step in the operations of phase ``optimizer``: those
whose ``op_name`` holds the scope ``optimizer`` (benchmark/scopes.py):
the part of the update that XLA left in operations of its own."""

from benchmark import scopes


def reduce(ctx):
    return scopes.phase_ms(ctx, "optimizer")
