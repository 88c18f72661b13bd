"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run driver must set
XLA_FLAGS before any JAX initialization.
"""
from __future__ import annotations

import jax


def build_mesh(shape, axes):
    """The one mesh-construction path (every builder here and
    ``repro.pipeline.spmd.stage_mesh`` routes through it — construct
    meshes nowhere else).  Every axis is Auto: the compiler
    propagates shardings, as the pipeline and dry-run code expect."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16x16 = 256 chips per pod; 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return build_mesh(shape, axes)


def make_stage_mesh(num_stages: int, *, model_parallel: int = 1):
    """Serving-pipeline mesh: ``stage`` = execution places (paper EPs),
    ``model`` = operator parallelism within an EP."""
    if model_parallel > 1:
        return build_mesh((num_stages, model_parallel), ("stage", "model"))
    return build_mesh((num_stages,), ("stage",))


def data_axes(mesh) -> tuple:
    """Axes that shard the batch (pod composes with data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
