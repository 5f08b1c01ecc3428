"""Vertex-centric program abstraction (paper Fig. 1, typed).

A ``VertexProgram`` is written against the :class:`EdgeContext` API
(``ctx.propagate``) which hides the system configuration: update direction
(push/pull), coherence (LLC vs owned accumulation) and consistency schedule
(DRF0/DRF1/DRFrlx).  This is the paper's contract: the *algorithm* supplies
``spred``/``tpred`` (algorithmic control), ``vprop`` (algorithmic
information) and the reduction monoid ``op``; the *system* decides how
edge-propagated updates execute.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Hashable, Optional

import jax.numpy as jnp

from repro.core.properties import TABLE_III, AlgorithmicProperties

__all__ = ["Monoid", "SUM", "MIN", "MAX", "EdgePhase", "VertexProgram",
           "FRONTIER_DIR_KEY", "FRONTIER_OCC_KEY", "DENSE_OCC",
           "dense_occupancy"]

State = dict  # str -> jnp.ndarray pytree

#: State key under which frontier-aware programs record the direction
#: their step chose (bool scalar, True=pull).  ``run`` reads it back per
#: iteration to build :attr:`RunResult.direction_trace`.
FRONTIER_DIR_KEY = "pull"

#: State key under which frontier-aware programs record this iteration's
#: sparse-gather occupancy (float scalar): ``m_f / sparse_edge_capacity``
#: when :meth:`~repro.core.executor.EdgeContext.propagate_sparse` took
#: the gathered O(m_f) path, -1.0 when the iteration ran the dense O(E)
#: scan (pull direction, capacity overflow, or a static config).  ``run``
#: reads it back per iteration into :attr:`RunResult.occupancy_trace`.
FRONTIER_OCC_KEY = "sparse_occ"

#: Occupancy value marking a dense O(E) iteration in the
#: :data:`FRONTIER_OCC_KEY` trace.  Every producer — the executor's
#: ``propagate_sparse`` branches and the frontier-aware programs' init
#: states — must construct it through :func:`dense_occupancy` so the
#: sentinel is one ``jnp.float32`` scalar everywhere (a dtype or
#: weak-type asymmetry between branches would fail ``lax.cond``/
#: ``lax.while_loop`` carry matching).
DENSE_OCC = -1.0


def dense_occupancy() -> jnp.ndarray:
    """The dense-iteration occupancy sentinel as a jnp.float32 scalar."""
    return jnp.asarray(DENSE_OCC, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Monoid:
    """Commutative-associative reduction: the paper's ``op``.

    Commutativity+associativity is what lets DRFrlx reorder the update
    stream (relaxed atomics) — and what lets us legally re-schedule the
    reduction on TPU.
    """
    name: str  # 'sum' | 'min' | 'max'

    def identity(self, dtype) -> Any:
        dtype = jnp.dtype(dtype)
        if self.name == "sum":
            return jnp.zeros((), dtype)
        big = (jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
               else jnp.array(jnp.inf, dtype))
        small = (jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                 else jnp.array(-jnp.inf, dtype))
        return big if self.name == "min" else small

    def combine(self, a, b):
        if self.name == "sum":
            return a + b
        return jnp.minimum(a, b) if self.name == "min" else jnp.maximum(a, b)


SUM = Monoid("sum")
MIN = Monoid("min")
MAX = Monoid("max")


@dataclasses.dataclass(frozen=True)
class EdgePhase:
    """One edge-propagated reduction (one kernel of Fig. 1).

    ``vprop(state, src_ids, edge_weight) -> [E] values`` — algorithmic
    information, reads *source-side* properties only (Fig. 1 line 4/8).
    ``spred(state, src_ids)`` / ``tpred(state, dst_ids)`` — algorithmic
    control.  Edges failing either predicate contribute the monoid
    identity (work elision happens at trace level per direction).

    ``frontier(state) -> [V] bool`` — optional frontier protocol: the
    source-side frontier mask driving this phase, fed to
    ``EdgeContext.choose_direction`` by dynamic (``PUSH_PULL``) configs
    to pick push vs. pull per iteration.  ``None`` marks a frontier-less
    phase, which dynamic configs run in the context's documented default
    direction.

    ``gatherable`` — structural opt-in to the sparse-gathered push path:
    set it True only if ``spred`` restricts contributing sources to
    (a subset of) the ``frontier`` mask, so reducing over only the
    frontier's gathered out-edges is equivalent to the dense masked
    scan.  A phase whose frontier merely steers the direction heuristic
    while every source contributes must leave it False, or sparse
    iterations would silently drop contributions.
    """
    monoid: Monoid
    vprop: Callable[[State, jnp.ndarray, jnp.ndarray], jnp.ndarray]
    spred: Optional[Callable[[State, jnp.ndarray], jnp.ndarray]] = None
    tpred: Optional[Callable[[State, jnp.ndarray], jnp.ndarray]] = None
    frontier: Optional[Callable[[State], jnp.ndarray]] = None
    gatherable: bool = False


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """A graph algorithm: state init, per-iteration step, convergence.

    Frontier protocol (optional): traversal-flavoured programs set
    ``frontier_init`` (initial [V] bool mask from the graph) and
    ``frontier_update`` (current mask extracted from state) and record
    the direction their step chose under :data:`FRONTIER_DIR_KEY`.
    ``frontier_update is not None`` is how ``run`` recognises a
    frontier-aware program (gating the per-iteration direction trace it
    reads from :data:`FRONTIER_DIR_KEY`); both extractors give harnesses
    and tests mask access without knowing each program's state layout.
    The direction *choice* itself happens inside ``step`` — programs
    call ``ctx.choose_direction`` on their phase's ``frontier`` mask and
    pass the result to ``ctx.propagate_dynamic``.  Frontier-less
    programs leave everything ``None`` and execute dynamic configs in
    the context's default direction.

    Batching protocol (optional): ``state_pad`` maps state keys to the
    fill value the batch packer must use for that leaf's padding rows
    (default 0).  A program whose zero state is *not* inert — e.g. MIS,
    where status 0 means "undecided" and an all-zero padding row would
    never satisfy per-graph convergence — declares the inert value here
    (``{"status": 2}``).  ``randomized`` marks a program whose ``init``
    draws from a PRNG key; ``run_batch`` derives decorrelated per-graph
    keys (``fold_in`` on the batch index) for such programs when the
    caller passes no explicit keys.

    Resilience protocol (optional, consumed by
    :mod:`repro.core.resilience`): ``monotone`` maps state keys to
    ``"non_increasing"``/``"non_decreasing"`` — the exact reorderable-
    combine property MIN/MAX-monoid fixpoints rely on, checked between
    checkpoints (the relation is transitive, so a K-iteration segment
    boundary check is as strong as a per-iteration one).  ``sentinels``
    maps sentinel names to ``(prev_state, cur_state) -> bool`` invariant
    predicates (True = healthy) written in jnp so they run both inside
    the segmented fused dispatch and on host snapshots.  ``certificate``
    is ``(ctx, state) -> bool``: a one-shot O(E) fixpoint proof checked
    on *converged* states, which catches corruptions (e.g. dropped
    updates that revert a vertex to an older-but-plausible value) that
    boundary sentinels structurally cannot see.

    Runner sharing (optional): ``runner_key`` names the parameters that
    ``step``, ``converged``, ``frontier_update`` and ``sentinels`` close
    over.  Two programs with the same ``name`` and ``runner_key`` must
    trace to the same runner, so they share one compiled executable: a
    parameter read only by ``init``, ``frontier_init``, ``extract`` or
    ``certificate`` (BFS's ``source``) stays out of the key.  ``None``
    (the default) gives every instance a runner of its own.

    Counters (optional): ``counters`` maps a name to a function
    ``(final state) -> integer scalar`` that reads a count the program
    keeps in its own state, such as how many vertex activations a run
    made.  ``run`` and ``run_batch`` read each into
    :attr:`~repro.core.executor.RunResult.counters` as a Python int after
    the run's results are on the host, so a counter adds no device work
    beyond the state update that keeps it.  ``None`` (the default)
    declares none, and leaves the runner exactly as it was.
    """
    name: str
    init: Callable[..., State]                     # (graph[, key]) -> state
    step: Callable[..., State]                     # (ctx, state, it) -> state
    converged: Callable[[State, State], jnp.ndarray]  # (prev, cur) -> bool
    extract: Callable[[State], Any]
    weighted: bool = False
    max_iters: int = 1024
    frontier_init: Optional[Callable[..., jnp.ndarray]] = None  # (graph)
    frontier_update: Optional[Callable[[State], jnp.ndarray]] = None
    state_pad: Optional[dict] = None               # key -> padding fill value
    randomized: bool = False                       # init consumes a PRNG key
    monotone: Optional[dict] = None                # key -> ordering direction
    sentinels: Optional[dict] = None               # name -> (prev, cur) -> ok
    certificate: Optional[Callable] = None         # (ctx, state) -> bool
    runner_key: Optional[Hashable] = None          # shared-runner params
    counters: Optional[dict] = None                # name -> (state) -> int

    @property
    def properties(self) -> AlgorithmicProperties:
        return TABLE_III[self.name]
