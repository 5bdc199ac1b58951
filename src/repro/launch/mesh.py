"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import AxisPlan


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules place
    arrays with ``with_sharding_constraint``/``NamedSharding``, which only
    address Auto axes (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_pipeline_mesh(*, pp: int = 4, data: int = 8, model: int = 16):
    """3D mesh with a pipeline axis (pp × data × model)."""
    return make_mesh((pp, data, model), ("pp", "data", "model"))


def make_serving_mesh(*, data: int = 1, model: Optional[int] = None):
    """A (data, model) mesh over however many devices the host exposes.

    ``model=None`` uses every device not consumed by ``data``. The
    single-device default collapses to a 1×1 mesh, for which
    :func:`make_plan` yields a no-op plan (every axis has size 1, so every
    sharding constraint resolves to replication).
    """
    n = jax.device_count()
    if model is None:
        if n % max(1, data):
            raise ValueError(f"data={data} does not divide device count {n}")
        model = n // max(1, data)
    if data * model != n:
        raise ValueError(
            f"mesh {data}x{model} needs {data * model} devices, have {n}")
    return make_mesh((data, model), ("data", "model"))


def make_plan(mesh, *, fsdp: bool = True, seq_parallel: bool = False) -> AxisPlan:
    multi_pod = "pod" in mesh.axis_names
    return AxisPlan(
        mesh=mesh,
        batch=("pod", "data") if multi_pod else ("data",),
        model="model",
        expert="model",
        fsdp="data" if fsdp else None,
        seq="data" if seq_parallel else None,
        stage="pp" if "pp" in mesh.axis_names else None,
    )
