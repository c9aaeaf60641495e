"""Multi-chip scale-out: device meshes and sharded merge entry points."""
from .mesh import (DOCS_AXIS, OPS_AXIS, batched_materialize, make_mesh,
                   stack_packed)

__all__ = [
    "DOCS_AXIS", "OPS_AXIS", "batched_materialize", "make_mesh",
    "stack_packed",
]
from . import distributed  # noqa: E402  (multi-host helpers)

__all__.append("distributed")
