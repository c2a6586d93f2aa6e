"""Device ms a traced step in the operations of phase ``backward``: those
whose ``op_name`` holds ``transpose(...)`` round the scope ``forward`` or
``loss`` (benchmark/scopes.py). An optimizer update that XLA fused into a
gradient kernel is in here; the run prints how many ms such fusions take."""

from benchmark import scopes


def reduce(ctx):
    return scopes.phase_ms(ctx, "backward")
