"""Production mesh definitions (functions, never module-level constants —
importing this module must not touch jax device state)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_local_mesh(n_data: int | None = None, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    n_data = n_data if n_data is not None else n // n_model
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int) -> tuple:
    # Auto axes: the model code steers placement with
    # with_sharding_constraint, which Explicit axes (jax.make_mesh's
    # default) refuse
    return (jax.sharding.AxisType.Auto,) * n
