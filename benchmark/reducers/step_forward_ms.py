"""Device ms a traced step in the operations of phase ``forward``: those
whose ``op_name`` holds the scope ``forward`` or ``loss`` and no
``transpose(...)`` round it (benchmark/scopes.py)."""

from benchmark import scopes


def reduce(ctx):
    return scopes.phase_ms(ctx, "forward")
