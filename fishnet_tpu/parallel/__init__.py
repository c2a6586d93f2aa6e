"""Multi-chip parallelism: the trainers' device meshes and the serving
shards' placement. See mesh.py for the design rationale."""

from fishnet_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    factor_mesh,
    make_mesh,
)

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "factor_mesh",
    "make_mesh",
]
