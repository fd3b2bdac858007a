"""Production meshes (TPU v5e target).

single pod : (16, 16)      axes ("data", "model")          = 256 chips
multi pod  : (2, 16, 16)   axes ("pod", "data", "model")   = 512 chips

FAVAS clients live on the ("pod", "data") product axis — one resident client
per data-parallel coordinate; "model" is tensor parallelism. Defined as a
FUNCTION so importing this module never touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes: the engine places its
flat buffers with ``NamedSharding`` + ``with_sharding_constraint`` and
lets GSPMD propagate through the flatten/unflatten reshapes. JAX 0.9's
``jax.make_mesh`` defaults to ``Explicit`` axes, under which those
reshapes raise ``ShardingTypeError``.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_model_mesh(n_model: int | None = None):
    """1-D ("model",) mesh — the tensor-parallel slice of the production
    meshes, and what the forced-8-CPU-device sharded tests / benchmarks run
    on. ``n_model=None`` uses every visible device."""
    n = n_model or len(jax.devices())
    return _auto_mesh((n,), ("model",))


def mesh_from_arg(arg: str | None):
    """Parse a ``--mesh`` CLI value into a mesh (or None).

    "none"/"" -> None (single-device engine, the CPU default);
    "model"   -> all visible devices on a 1-D ("model",) mesh;
    "model=K" -> K devices on a 1-D ("model",) mesh;
    "single"  -> the 256-chip (16, 16) ("data", "model") production mesh;
    "multi"   -> the 512-chip (2, 16, 16) ("pod", "data", "model") mesh."""
    if arg in (None, "none", ""):
        return None
    if arg == "single":
        return make_production_mesh()
    if arg == "multi":
        return make_production_mesh(multi_pod=True)
    if arg == "model":
        return make_model_mesh()
    if arg.startswith("model="):
        return make_model_mesh(int(arg.split("=", 1)[1]))
    raise ValueError(f"unknown --mesh value: {arg!r}")


def model_axis_size(mesh) -> int:
    if mesh is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)


def data_axes(mesh) -> tuple:
    """The mesh axes that carry clients/batch (everything but "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def n_client_slots(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = 1
    for a in data_axes(mesh):
        out *= sizes[a]
    return out
