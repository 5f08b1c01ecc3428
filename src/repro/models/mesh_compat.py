"""Access to the active abstract mesh.

Model code asks one question — "is a mesh context active, and which?" —
so expose exactly that.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["active_abstract_mesh"]


def active_abstract_mesh() -> Optional["jax.sharding.AbstractMesh"]:
    """The active abstract mesh, or None when no mesh context is set."""
    am = jax.sharding.get_abstract_mesh()
    return None if am is None or am.empty else am
