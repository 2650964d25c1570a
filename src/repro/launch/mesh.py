"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).

Single pod : (16, 16)      ("data", "model")       = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   ("pod", "data", "model") = 512 chips

The "pod" axis carries only data-parallel gradient reductions (hierarchical:
reduce-scatter intra-pod on "data", all-reduce inter-pod on "pod") — the one
traffic class that tolerates the slower inter-pod links.  FSDP parameter
sharding stays on "data" (intra-pod) by design; see launch/sharding.py.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Small mesh over the real host devices (tests / CPU smoke)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that carry the batch: ("pod","data") on multi-pod else ("data",)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_size(mesh) -> int:
    return mesh.shape["model"]
