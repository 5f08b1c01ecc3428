"""Consistency dimension: schedule freedom of the update stream (DESIGN.md §2).

Given a per-chunk reduction ``chunk_reduce(chunk_idx) -> [V'] partial``:

- **DRF0**  — one monolithic reduction; a hard phase boundary (the GPU's
  full L1 invalidate/flush at every synchronization).
- **DRF1**  — ordered chunk pipeline via ``lax.scan``: chunk *k*'s gather/
  compute overlaps chunk *k-1*'s accumulate, but partial accumulation is
  ordered with respect to itself (data may reorder w.r.t. unpaired sync,
  sync stays ordered w.r.t. sync).
- **DRFrlx** — independent partial reductions (vmapped) followed by a
  commutative tree-combine: the chunks may complete in any order, the MLP
  the paper gets from relaxed atomics.

All three are mathematically identical because the monoid is commutative-
associative — exactly the property that makes relaxed atomics legal for
these workloads.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.core.config_space import Consistency
from repro.core.vertex_program import Monoid

__all__ = ["scheduled_reduce"]


def scheduled_reduce(chunk_reduce: Callable[[int], jnp.ndarray],
                     n_chunks: int, consistency: Consistency,
                     monoid: Monoid) -> jnp.ndarray:
    """Combine ``n_chunks`` partial reductions under a consistency model."""
    if consistency is Consistency.DRF0 or n_chunks == 1:
        # chunk_reduce must have been built with a single chunk.
        return chunk_reduce(0)

    if consistency is Consistency.DRF1:
        def body(carry, idx):
            part = chunk_reduce(idx)
            with jax.named_scope(spans.SCHEDULE):
                return monoid.combine(carry, part), None
        first = chunk_reduce(0)
        out, _ = jax.lax.scan(body, first, jnp.arange(1, n_chunks))
        return out

    # DRFrlx: all partials independent, then reorderable combine.
    partials = jax.vmap(chunk_reduce)(jnp.arange(n_chunks))  # [C, V']
    with jax.named_scope(spans.SCHEDULE):
        if monoid.name == "sum":
            return jnp.sum(partials, axis=0)
        if monoid.name == "min":
            return jnp.min(partials, axis=0)
        return jnp.max(partials, axis=0)
