"""Production mesh builders (functions, never module-level constants — the
module must be importable without touching jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["auto_mesh", "make_production_mesh", "make_local_mesh", "mesh_name"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis.  Requires 256/512 (placeholder) devices — see launch/dryrun.py."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings and
    `with_sharding_constraint` steers it, as the logical-axis rules in
    `parallel.sharding` assume (``jax.make_mesh`` defaults to ``Explicit``
    axes, under which those constraints are refused)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return auto_mesh((data, model), ("data", "model"))


def mesh_name(mesh) -> str:
    return "x".join(f"{k}{v}" for k, v in mesh.shape.items())
