"""Configuration-specialized execution of vertex programs (paper Sec. II).

:class:`EdgeContext` binds a graph to a :class:`SystemConfig` and exposes
``propagate`` — the single entry point through which an algorithm's
edge-propagated updates execute.  The config picks:

- edge order + reduction flavour (push: by-src order, unsorted scatter;
  pull: by-dst order, sorted segmented reduce; owned: dst-block-binned),
- the accumulation locality (coherence: LLC vs owned/VMEM-blocked),
- the chunking/overlap schedule (consistency: DRF0/DRF1/DRFrlx).

Dynamic (``PUSH_PULL``) configs keep **both** pre-chunked edge orders live
and resolve the direction per call: frontier-aware programs pass a traced
boolean to :meth:`EdgeContext.propagate_dynamic` (typically computed by
:meth:`EdgeContext.choose_direction` from the current frontier), which
``lax.cond``s between the push and pull realisations inside jit.
Frontier-less programs fall back to the documented
:data:`EdgeContext.DEFAULT_DYNAMIC_DIRECTION`.

:meth:`EdgeContext.propagate_sparse` is the sparse-frontier upgrade of
``propagate_dynamic``: when the dynamic heuristic picked push *and* the
frontier's edge list fits the static gather capacity, the iteration
gathers exactly the frontier's out-edges from the CSR order
(:func:`repro.core.frontier.gather_frontier_edges`) and reduces over the
``[cap_e]`` slice (:func:`repro.kernels.segment_reduce.
gathered_segment_reduce`) — O(m_f) gathered work instead of the O(E)
masked scan.  Capacity overflow (detected via the true counts the sparse
containers carry) falls back to the dense pre-chunked path, never
dropping edges.

``run`` drives a program to convergence and records the per-iteration
direction and sparse-occupancy traces of frontier-aware programs.  Two
execution engines share the same program contract:

- ``engine="fused"`` (default): the whole convergence loop runs inside
  **one** jitted ``jax.lax.while_loop`` dispatch.  The carry holds the
  state, the iteration counter, the done flag and fixed-size
  ``[max_iters]`` device trace buffers that the loop body writes with
  ``lax.dynamic_update_index_in_dim``; the host syncs exactly once, at
  the end, and decodes the buffers into ``RunResult.direction_trace`` /
  ``occupancy_trace``.  ``RunResult.seconds`` therefore measures kernel
  work only — no per-iteration jit dispatch, no blocking convergence
  read.
- ``engine="host"``: the debugging oracle — one jitted, donated step
  per iteration with a blocking convergence read in between, the shape
  GPU frameworks call "kernel-per-iteration".  Trace scalars are
  carried off as async device copies and decoded after the timer
  stops, so host-vs-fused timing deltas are dominated by the
  per-iteration dispatch + sync cost the fused engine exists to
  remove (plus, for traced programs, two tiny async scalar-copy
  enqueues per iteration).

Construction cost is amortized by :data:`repro.core.plan_cache.
PLAN_CACHE`: the device graph, pre-chunked edge orders and blocked-
reducer tiling plans are cached per graph and shared across configs,
and whole bound contexts are reused via :meth:`EdgeContext.create`.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from functools import partial
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import spans
from repro.core.coherence import segment_reduce, segment_reduce_owned
from repro.core.config_space import (Coherence, Consistency, SystemConfig,
                                     UpdateProp)
from repro.core.consistency import scheduled_reduce
from repro.core.frontier import (ALPHA, choose_direction, dense_to_sparse,
                                 gather_frontier_edges)
from repro.core.plan_cache import PLAN_CACHE
from repro.core.vertex_program import (FRONTIER_DIR_KEY, FRONTIER_OCC_KEY,
                                       EdgePhase, Monoid, VertexProgram,
                                       dense_occupancy)
from repro.kernels.autotune import autotune_plan, build_reducer
from repro.kernels.segment_reduce import (DEFAULT_PLAN,
                                          gathered_segment_reduce)
from repro.graph.structure import Graph

__all__ = ["EdgeContext", "RunResult", "run", "run_batch",
           "ExecutorStats", "STATS"]


@dataclasses.dataclass
class ExecutorStats:
    """Process-wide device-dispatch counter (tests and benchmarks).

    ``dispatches`` counts *timed* jitted invocations issued by ``run``:
    the host engine increments once per iteration step, the fused
    engine exactly once per run.  Warmup compilation is not counted —
    it happens outside the timed region on both engines.
    """
    dispatches: int = 0

    def reset(self) -> None:
        self.dispatches = 0

    @staticmethod
    def plan_cache() -> dict:
        """Plan-cache counters, global and per kind.

        ``plan_cache()["by_kind"]["tuned_tiling"]`` is how autotune
        cache effectiveness (tunes vs recalls) is observed without
        reaching into :data:`~repro.core.plan_cache.PLAN_CACHE`
        directly.
        """
        return PLAN_CACHE.stats()


STATS = ExecutorStats()


def _normalize_autotune(autotune) -> str:
    """Canonicalize the ``autotune=`` knob to 'off'|'heuristic'|'measure'."""
    if autotune in (None, False, "off"):
        return "off"
    if autotune is True:
        return "measure"
    if autotune in ("heuristic", "measure"):
        return autotune
    raise ValueError(f"unknown autotune mode {autotune!r}; expected "
                     "'off', 'heuristic', 'measure' or a bool")

#: Max compiled runner executables retained per graph (LRU): generous
#: for design-space sweeps (18 cells x 2 engines fits), bounded for
#: program-per-root loops.
_EXEC_FN_CAPACITY = 64


def _pad_reshape(arr, n_chunks, fill):
    e = arr.shape[0]
    ec = -(-e // n_chunks)  # ceil
    pad = ec * n_chunks - e
    if pad:
        arr = jnp.concatenate([arr, jnp.full((pad,), fill, arr.dtype)])
    return arr.reshape(n_chunks, ec)


class EdgeContext:
    """Graph + SystemConfig bound together; reusable across iterations."""

    #: Direction used when a ``PUSH_PULL`` config meets a phase that did
    #: not resolve one (no frontier, no explicit ``direction=``).  PUSH is
    #: the safe default: the dynamic configs exist for traversal apps
    #: whose frontiers start sparse, and source-outer iteration with
    #: ``spred`` elision does no worse than pull on a sparse frontier
    #: while avoiding pull's full destination scan.  Frontier-aware
    #: programs should instead call :meth:`propagate_dynamic`.
    DEFAULT_DYNAMIC_DIRECTION = UpdateProp.PUSH

    @staticmethod
    def default_sparse_capacity(graph: Graph) -> int:
        """Default sparse-gather edge capacity: ``ceil(E/alpha)``.

        The push->pull trigger fires once ``m_f*alpha > E``, so a
        dynamic push frontier rarely carries more out-edges than that;
        anything larger falls back to the dense path via the overflow
        flags.
        """
        return min(graph.n_edges,
                   max(16, -(-graph.n_edges // int(ALPHA))))

    @classmethod
    def create(cls, graph: Graph, config: SystemConfig,
               use_pallas: bool = False,
               sparse_edge_capacity: Optional[int] = None,
               autotune=None) -> "EdgeContext":
        """Cached constructor: reuse the bound context for a repeated
        (graph, config, use_pallas, capacity, autotune) cell.

        Contexts are immutable after construction, so sharing one across
        ``run`` calls is safe; the underlying artifacts are additionally
        shared *across* configs through :data:`PLAN_CACHE` regardless of
        which constructor built them.
        """
        if sparse_edge_capacity is None:
            sparse_edge_capacity = cls.default_sparse_capacity(graph)
        cap = int(sparse_edge_capacity)
        mode = _normalize_autotune(autotune)

        def build():
            ctx = cls(graph, config, use_pallas=use_pallas,
                      sparse_edge_capacity=cap, autotune=mode)
            # a cache-owned context must not pin its graph, or the
            # cache's eviction-on-collection could never fire (cache ->
            # context -> graph would keep the graph alive forever)
            ctx._graph_strong = None
            return ctx

        with TraceAnnotation(spans.CONTEXT):
            return PLAN_CACHE.get(
                graph, "context", (config, bool(use_pallas), cap, mode),
                build)

    def __init__(self, graph: Graph, config: SystemConfig,
                 use_pallas: bool = False,
                 sparse_edge_capacity: Optional[int] = None,
                 autotune=None):
        # directly constructed contexts keep their graph alive like any
        # object would; :meth:`create` clears the strong reference on
        # cache-owned contexts so eviction can fire (see build() there)
        self._graph_strong: Optional[Graph] = graph
        self._graph_ref = weakref.ref(graph)
        self.config = config
        self.use_pallas = use_pallas
        self.autotune = _normalize_autotune(autotune)
        self.n_nodes = graph.n_nodes
        self.n_edges = graph.n_edges
        cache = PLAN_CACHE
        g = cache.get(graph, "device", (), graph.device_put)
        # Sparse-gather capacities (static: jit needs fixed shapes).
        # See :meth:`default_sparse_capacity` for the edge-capacity
        # rationale.  The vertex capacity rides along at the same size:
        # on the symmetric inputs the paper uses, every reachable
        # frontier vertex has >= 1 out-edge, so n_f <= m_f.  Pass 0 to
        # disable the sparse path.
        if sparse_edge_capacity is None:
            sparse_edge_capacity = self.default_sparse_capacity(graph)
        self.sparse_edge_capacity = int(sparse_edge_capacity)
        self._sparse_vertex_capacity = max(
            1, min(self.n_nodes, self.sparse_edge_capacity))
        # a device scalar, so occupancy divides at run time (correctly
        # rounded) as the batched context's per-graph [B] capacities do,
        # never by a compile-time constant the compiler may turn into a
        # reciprocal multiply
        self._cap_e_f32 = jnp.float32(self.sparse_edge_capacity)
        self._row_ptr_out = g.row_ptr_out
        self._csr_raw = (g.src, g.dst, g.weight)
        n_chunks = 1 if config.consistency is Consistency.DRF0 \
            else config.n_chunks
        v = graph.n_nodes
        self._out_degree = g.out_degree

        # Pre-chunked edge arrays per direction.  Padding edges carry the
        # sentinel id V on both endpoints; they reduce into the extra
        # segment V and contribute the identity regardless.  Chunked
        # orders depend only on (edge order, n_chunks), never on the
        # full config, so the cache shares them across cells — a 12-cell
        # sweep builds each (order, n_chunks) pair once.
        def chunked(edges):
            src, dst, w = edges
            return (_pad_reshape(src, n_chunks, v),
                    _pad_reshape(dst, n_chunks, v),
                    _pad_reshape(w, n_chunks, 0.0))

        self._reducer = None
        self._pull_reducer = None
        # Reducer tiling plans: the static DEFAULT_PLAN unless the
        # autotune knob asks the degree-aware tuner for this graph's
        # plan (heuristic: zero-measurement suggest_plan; measure:
        # empirical candidate sweep, process- and disk-cached).  The
        # tuner times the "mixed" objective (one MXU sum + one VPU min
        # per call) because one bound reducer instance serves whatever
        # monoids the program's phases use.
        self._gather_plan = None
        if (config.prop is UpdateProp.PUSH_PULL
                and self.sparse_edge_capacity > 0):
            self._gather_plan = self._resolve_plan(
                graph, "gathered", cap_e=self.sparse_edge_capacity)
        if config.coherence is Coherence.DENOVO:
            owned = cache.get(graph, "edges_owned", (), g.edges_owned)
            self._push_edges = cache.get(graph, "chunked",
                                         ("owned", n_chunks),
                                         lambda: chunked(owned))
            if use_pallas and config.prop is not UpdateProp.PULL:
                self._owned_raw = owned
                plan = self._resolve_plan(graph, "owned")
                self._reducer = cache.get(
                    graph, "owned_reducer", plan,
                    lambda: build_reducer(graph, "owned", plan))
        else:
            self._push_edges = cache.get(
                graph, "chunked", ("csr", n_chunks),
                lambda: chunked((g.src, g.dst, g.weight)))
        self._pull_edges = cache.get(
            graph, "chunked", ("csc", n_chunks),
            lambda: chunked((g.src_in, g.dst_in, g.weight_in)))
        # each reducer's host-side tiling plan walks the full edge set, so
        # only build the directions this config can actually execute
        if use_pallas and config.prop is not UpdateProp.PUSH:
            self._pull_raw = (g.src_in, g.dst_in, g.weight_in)
            plan = self._resolve_plan(graph, "pull")
            self._pull_reducer = cache.get(
                graph, "pull_reducer", plan,
                lambda: build_reducer(graph, "pull", plan))
        self.n_chunks = n_chunks

    def _resolve_plan(self, graph: Graph, order: str,
                      cap_e: Optional[int] = None):
        """This context's tiling plan for one edge order."""
        if self.autotune == "off":
            return DEFAULT_PLAN
        if order == "gathered" and self.autotune == "heuristic":
            # the degree heuristic has no model of the scatter split;
            # the gathered path keeps its single-scatter default
            return DEFAULT_PLAN
        return autotune_plan(graph, order=order, kind="mixed",
                             mode=self.autotune, cap_e=cap_e)

    @property
    def plan_signature(self) -> tuple:
        """Identity of the resolved tiling plans (exec-fn cache key
        material): two contexts that differ only in tuned plans must
        not share a compiled runner."""
        def sig(red):
            return red.plan.astuple() if red is not None else None
        return (sig(self._reducer), sig(self._pull_reducer),
                self._gather_plan.astuple()
                if self._gather_plan is not None else None)

    @property
    def graph(self) -> Optional[Graph]:
        """The host graph this context was built from.

        Directly constructed contexts hold it strongly (always
        available); cache-owned contexts hold it weakly, so this is
        ``None`` once such a graph has been garbage-collected.
        """
        return self._graph_strong or self._graph_ref()

    # ------------------------------------------------------------------
    def resolve_direction(self,
                          direction: Optional[UpdateProp] = None) -> UpdateProp:
        """Resolve a per-phase direction to a concrete PUSH or PULL.

        Precedence: explicit ``direction`` argument > the config's static
        direction > :data:`DEFAULT_DYNAMIC_DIRECTION` for ``PUSH_PULL``
        configs whose caller resolved nothing.
        """
        direction = direction or self.config.prop
        if direction is UpdateProp.PUSH_PULL:
            direction = self.DEFAULT_DYNAMIC_DIRECTION
        return direction

    def choose_direction(self, frontier: jnp.ndarray, prev_pull,
                         unvisited: Optional[jnp.ndarray] = None
                         ) -> jnp.ndarray:
        """Traced bool (True=pull) for this iteration's edge direction.

        Static configs return their fixed direction as a constant, so
        frontier-aware programs can call this unconditionally and stay
        correct (and recompile-free) across the whole design space.
        """
        prop = self.config.prop
        if prop is not UpdateProp.PUSH_PULL:
            return jnp.asarray(prop is UpdateProp.PULL)
        with jax.named_scope(spans.DIRECTION):
            return choose_direction(frontier, self._out_degree, self.n_edges,
                                    self.n_nodes, prev_pull,
                                    unvisited=unvisited)

    def dynamic_direction(self, want_pull) -> jnp.ndarray:
        """An algorithm-chosen direction as this context's traced flag.

        For programs whose per-iteration direction is *algorithmic*
        rather than frontier-driven (CC's alternating hooking rounds):
        under a static config the config's direction wins (a constant,
        so only that branch compiles); under ``PUSH_PULL`` the wish is
        honoured as a traced bool.  Always returns something safe to
        record under :data:`FRONTIER_DIR_KEY` — the trace reports the
        direction that actually executed.
        """
        prop = self.config.prop
        if prop is not UpdateProp.PUSH_PULL:
            return jnp.asarray(prop is UpdateProp.PULL)
        return jnp.asarray(want_pull, bool)

    # ------------------------------------------------------------------
    # Per-graph state helpers.  Sequentially these are trivial; their
    # :class:`~repro.core.batch.BatchedEdgeContext` overrides give the
    # same program text per-graph semantics on packed [B*n_q] arrays —
    # the contract that lets normalizing programs (PageRank's 1/V
    # terms, BC's per-root level counter) run batched without baking
    # packed totals into their arithmetic.

    @property
    def true_n_nodes(self):
        """True vertex count(s): an int here, ``[B]`` when batched —
        never counts the batch packer's inert padding vertices."""
        return self.n_nodes

    def per_vertex(self, x) -> jnp.ndarray:
        """Broadcast a per-graph scalar (``[B]`` when batched) to a
        per-vertex ``[V]`` array, each vertex receiving its own graph's
        value."""
        return jnp.broadcast_to(jnp.asarray(x), (self.n_nodes,))

    def align_per_graph(self, x) -> jnp.ndarray:
        """Align a per-graph scalar for elementwise use against
        per-vertex arrays.  Sequentially this is the identity — the
        scalar participates via normal broadcasting, keeping the step's
        HLO in the scalar*vector shape whose rounding is stable across
        the host and fused compilations (materializing a ``[V]``
        operand invites fma contraction differences between the two
        engines).  Batched it expands ``[B]`` to packed rows.  Use
        ``per_vertex`` instead when the result itself must be a ``[V]``
        array (e.g. to index with ``[src]``)."""
        return jnp.asarray(x)

    def per_graph_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        """Sum a per-vertex array within each graph: scalar here,
        ``[B]`` when batched."""
        return jnp.sum(x)

    def per_graph_any(self, x: jnp.ndarray) -> jnp.ndarray:
        """Any-reduce a per-vertex bool array within each graph: scalar
        here, ``[B]`` when batched."""
        return jnp.any(x)

    def vertex_offsets(self) -> jnp.ndarray:
        """Each vertex's graph base offset into the vertex id space.

        Sequentially every vertex lives at its local id, so this is a
        scalar 0; batched it is the ``[B*n_q]`` array of packed row
        bases (``i*n_q`` for graph i's rows).  Programs that index
        state by *vertex-id-valued state* (CC's pointer jumping,
        ``label[label]``) must add it first — local label values only
        address the right rows of a packed array after the shift.
        """
        return jnp.int32(0)

    def cond_per_graph(self, pred, true_fn, false_fn, state):
        """Per-graph two-way branch over full state pytrees.

        Sequentially ``pred`` is a scalar and this is ``lax.cond``
        (one branch executes).  Batched, graphs may disagree — BC's
        forward/backward phases flip at per-graph times — so both
        branches execute on the packed arrays and each graph's rows
        select its own branch's result.  Both branches must return
        pytrees of identical structure/shapes.
        """
        return jax.lax.cond(jnp.asarray(pred, bool).reshape(()),
                            true_fn, false_fn, state)

    # ------------------------------------------------------------------
    def propagate(self, state, phase: EdgePhase,
                  direction: Optional[UpdateProp] = None,
                  dtype=jnp.float32) -> jnp.ndarray:
        """Execute one edge-propagated reduction; returns [V] reduced."""
        return self._propagate(state, phase, self.resolve_direction(direction),
                               dtype)

    def propagate_dynamic(self, state, phase: EdgePhase, pull,
                          dtype=jnp.float32) -> jnp.ndarray:
        """Like ``propagate`` but direction is a traced bool (True=pull).

        Under a static config the flag is ignored (the config's direction
        wins and only one branch is compiled); under ``PUSH_PULL`` both
        pre-chunked edge orders are traced and ``lax.cond`` executes
        exactly one per iteration — the paper's dynamic mode.
        """
        if self.config.prop is not UpdateProp.PUSH_PULL:
            return self._propagate(state, phase,
                                   self.resolve_direction(None), dtype)
        return jax.lax.cond(
            jnp.asarray(pull, bool),
            lambda st: self._propagate(st, phase, UpdateProp.PULL, dtype),
            lambda st: self._propagate(st, phase, UpdateProp.PUSH, dtype),
            state)

    def propagate_sparse(self, state, phase: EdgePhase, pull,
                         dtype=jnp.float32):
        """``propagate_dynamic`` with an O(m_f) sparse-gather fast path.

        Returns ``(reduced [V], occupancy)``.  ``occupancy`` is a traced
        float scalar: ``m_f / sparse_edge_capacity`` when this iteration
        ran the sparse-gathered path, -1.0 when it ran a dense O(E) scan
        (programs record it under :data:`FRONTIER_OCC_KEY` so ``run``
        can trace sparse-vs-dense residency per iteration).

        The sparse path fires only when *all* of: the config is dynamic
        (static cells keep their specialized dense realisations), the
        phase declares itself ``gatherable`` (see below), the heuristic
        chose push (pull's full destination scan is inherently dense),
        and the frontier's vertex *and* edge lists fit their static
        capacities.  Overflow of either capacity falls back to the
        dense pre-chunked path — slower, never wrong.  Pull iterations
        never pay the gather: the push/pull branch is the outer
        ``lax.cond``, so the gather is traced only inside the push
        branch.

        Soundness precondition: gathering reduces *only* the frontier's
        out-edges, so every edge contributing a non-identity message on
        the dense push path must have a frontier source.  A phase
        asserts that structurally via ``EdgePhase.gatherable`` — the
        BFS/SSSP/BC phases set it because their ``spred`` restricts
        sources to exactly the frontier mask.  A phase whose frontier
        only steers the direction heuristic (every source contributes)
        leaves it False and always runs the dense path.
        """
        # One constant for every dense-marked branch: the early return,
        # the pull branch and the overflow arm of the push branch all
        # return this same jnp.float32 scalar (dtype/weak-type symmetry
        # is what lets the fused while_loop carry the occupancy).
        dense_occ = dense_occupancy()
        if (self.config.prop is not UpdateProp.PUSH_PULL
                or phase.frontier is None or not phase.gatherable
                or self.sparse_edge_capacity == 0):
            return self.propagate_dynamic(state, phase, pull, dtype), dense_occ

        def dense_pull(st):
            return self._propagate(st, phase, UpdateProp.PULL, dtype), \
                dense_occ

        def push(st):
            with jax.named_scope(spans.FRONTIER):
                front = dense_to_sparse(phase.frontier(st),
                                        self._sparse_vertex_capacity)
                edges = gather_frontier_edges(front.ids, self._row_ptr_out,
                                              self.sparse_edge_capacity)
                fits = ~front.overflowed & ~edges.overflowed
                occ = jnp.where(
                    fits,
                    edges.count.astype(jnp.float32) / self._cap_e_f32,
                    dense_occ)
            out = jax.lax.cond(
                fits,
                lambda s: self._propagate_gathered(s, phase, edges.edge_ids,
                                                   dtype),
                lambda s: self._propagate(s, phase, UpdateProp.PUSH, dtype),
                st)
            return out, occ

        return jax.lax.cond(jnp.asarray(pull, bool), dense_pull, push, state)

    def _propagate_gathered(self, state, phase: EdgePhase,
                            edge_ids: jnp.ndarray, dtype) -> jnp.ndarray:
        """Push-direction reduction over a gathered [cap_e] edge subset.

        ``edge_ids`` indexes the CSR (by-src) edge arrays; -1 marks
        padding.  Padding and predicate-failing edges are routed to the
        reducer's trash segment, which contributes the monoid identity —
        the same convention as the dense path's masked scan.  For
        min/max and exact (integer) sums the result is bit-identical to
        the dense path; inexact float sums may differ in final ULPs
        because the gathered order sums edges differently than the
        chunked schedule.
        """
        src, dst, w = self._csr_raw
        with jax.named_scope(spans.EDGE_GATHER):
            valid = edge_ids >= 0
            at = jnp.where(valid, edge_ids, 0)
            sv, tv, wv = src[at], dst[at], w[at]
            keep = valid
            if phase.spred is not None:
                keep &= phase.spred(state, sv)
            if phase.tpred is not None:
                keep &= phase.tpred(state, tv)
            msg = phase.vprop(state, sv, wv).astype(dtype)
            ids = jnp.where(keep, tv, -1)
        with jax.named_scope(spans.EDGE_REDUCE):
            return gathered_segment_reduce(msg, ids, self.n_nodes,
                                           phase.monoid.name,
                                           plan=self._gather_plan)

    def _propagate(self, state, phase: EdgePhase, direction: UpdateProp,
                   dtype) -> jnp.ndarray:
        cfg = self.config
        pull = direction is UpdateProp.PULL
        src_c, dst_c, w_c = self._pull_edges if pull else self._push_edges
        v = self.n_nodes
        monoid = phase.monoid
        ident = monoid.identity(dtype)

        reducer = self._pull_reducer if pull else self._reducer
        if reducer is not None:
            # Pallas blocked kernel over the whole (unpadded) edge set in
            # block-binned order (owned order for push, CSC order for
            # pull); masked edges contribute the monoid identity,
            # kernel-internal DMA pipelining plays the consistency role.
            so, do, wo = self._pull_raw if pull else self._owned_raw
            with jax.named_scope(spans.EDGE_GATHER):
                mask = jnp.ones(so.shape, bool)
                if phase.spred is not None:
                    mask &= phase.spred(state, so)
                if phase.tpred is not None:
                    mask &= phase.tpred(state, do)
                msg = phase.vprop(state, so, wo).astype(dtype)
            with jax.named_scope(spans.EDGE_REDUCE):
                return reducer.masked(msg, mask, monoid.name, ident=ident)

        def chunk_reduce(i):
            with jax.named_scope(spans.EDGE_GATHER):
                src = jax.lax.dynamic_index_in_dim(src_c, i, keepdims=False)
                dst = jax.lax.dynamic_index_in_dim(dst_c, i, keepdims=False)
                w = jax.lax.dynamic_index_in_dim(w_c, i, keepdims=False)
                sv = jnp.minimum(src, v - 1)
                tv = jnp.minimum(dst, v - 1)
                mask = (src < v) & (dst < v)
                if phase.spred is not None:
                    mask &= phase.spred(state, sv)
                if phase.tpred is not None:
                    mask &= phase.tpred(state, tv)
                msg = phase.vprop(state, sv, w).astype(dtype)
                msg = jnp.where(mask, msg, ident)
                # by-dst order keeps ids = dst: sorted ids -> dense local
                # (non-atomic) update, and chunks of a sorted array stay
                # sorted.  Rewriting masked ids to the sentinel would
                # break the sorted invariant the flag asserts; masked
                # edges already carry the identity, which no-ops in the
                # combine, and padding edges carry dst = v themselves.
                ids = dst if pull else jnp.where(mask, dst, v)
            with jax.named_scope(spans.EDGE_REDUCE):
                if pull:
                    return segment_reduce(msg, ids, v + 1, monoid,
                                          indices_are_sorted=True)
                if cfg.coherence is Coherence.DENOVO:
                    return segment_reduce_owned(msg, ids, v + 1, monoid)
                return segment_reduce(msg, ids, v + 1, monoid)

        out = scheduled_reduce(chunk_reduce, self.n_chunks,
                               cfg.consistency, monoid)
        return out[:v]


@dataclasses.dataclass
class RunResult:
    state: Any
    iterations: int
    seconds: float
    converged: bool
    #: per-iteration edge-direction letters ("S"=push, "T"=pull) for
    #: frontier-aware programs; None for programs without the protocol.
    direction_trace: Optional[str] = None
    #: per-iteration sparse-gather occupancy (m_f / cap_e; -1.0 for a
    #: dense iteration) for programs recording FRONTIER_OCC_KEY; None
    #: for programs without the protocol.
    occupancy_trace: Optional[List[float]] = None
    #: which execution engine produced this result ("fused" | "host").
    engine: str = "fused"
    #: timed jitted invocations this run issued: 1 for the fused engine,
    #: ``iterations`` for the host engine (warmup compiles excluded).
    dispatches: int = 0
    #: True when a serving-gateway per-request deadline expired before
    #: convergence: ``state`` then holds the partial-iteration state
    #: after the last completed scheduling slice (and ``converged`` is
    #: False).  Always False for direct ``run()``/``run_batch`` runs.
    timed_out: bool = False
    #: Structured run outcome: "converged" | "iter_limit" | "timed_out"
    #: | "faulted".  Derived from the flags when not set explicitly;
    #: "faulted" is produced only by the resilience layer
    #: (:mod:`repro.core.resilience`) when recovery is exhausted.
    outcome: Optional[str] = None
    #: Fault record for resilient runs: the per-attempt fault history
    #: (sentinel trips, exceptions) plus whether recovery succeeded.
    #: None for runs that never faulted.
    fault: Optional[dict] = None
    #: Executions this result took: 1 for a clean run, >1 when
    #: :class:`~repro.core.resilience.RetryPolicy` re-executed.
    attempts: int = 1
    #: Name of the :class:`SystemConfig` this run actually executed
    #: under (e.g. "DD1"); None for paths that never stamp it.
    config_name: Optional[str] = None
    #: How that config was chosen: "caller" (the config argument as
    #: passed), "static" / "static_partial" (the prose decision trees)
    #: or "learned" (the trained model) — see
    #: :func:`repro.core.specialize_learned.resolve_config`.
    config_source: str = "caller"

    def __post_init__(self):
        if self.outcome is None:
            self.outcome = ("converged" if self.converged else
                            "timed_out" if self.timed_out else
                            "iter_limit")

    @property
    def sparse_iterations(self) -> Optional[int]:
        """How many iterations ran the O(m_f) gathered path."""
        if self.occupancy_trace is None:
            return None
        return sum(1 for o in self.occupancy_trace if o >= 0.0)

    @property
    def mean_sparse_occupancy(self) -> Optional[float]:
        """Mean m_f/cap_e over the sparse-gathered iterations."""
        occ = [o for o in (self.occupancy_trace or []) if o >= 0.0]
        return sum(occ) / len(occ) if occ else None

    def extract(self, program: VertexProgram):
        return program.extract(self.state)


def _trace_flags(program: VertexProgram, state) -> tuple:
    # direction tracing is part of the frontier protocol: the program
    # declares itself frontier-aware via frontier_update and records its
    # per-iteration choice under FRONTIER_DIR_KEY
    traced = (program.frontier_update is not None
              and isinstance(state, dict) and FRONTIER_DIR_KEY in state)
    occ_traced = traced and FRONTIER_OCC_KEY in state
    return traced, occ_traced


def _cached_exec_fn(program: VertexProgram, ctx: EdgeContext,
                    params: tuple, build):
    """Fetch a jitted/compiled runner callable through the plan cache.

    A fresh ``jax.jit`` closure per ``run`` call would miss jax's jit
    cache every time, recompiling the step (host) or the entire fused
    while_loop per repeat of a sweep — usually the dominant sweep cost.
    Entries are keyed on what the runner is built from: the program's
    ``(name, runner_key)`` when it declares one, so every BFS root
    shares one runner, else ``id(program)``; plus the context/engine
    params.  An entry holds the program it was first built from
    strongly, so a program id can never be recycled while its entry is
    alive; entries die with the graph, and the bucket is LRU-bounded so
    a stream of distinct program instances on one long-lived graph
    (e.g. exact BC looping over roots) cannot accumulate unbounded
    compiled executables.
    """
    g = ctx.graph
    runner = (id(program) if program.runner_key is None
              else (program.name, program.runner_key))
    key = (runner, ctx.config, ctx.use_pallas,
           ctx.sparse_edge_capacity, ctx.plan_signature) + params
    if g is None:  # graph already collected; nothing to key on
        return build()[1]
    return PLAN_CACHE.get(g, "exec_fn", key, build,
                          capacity=_EXEC_FN_CAPACITY)[1]


def _jit_hoisted(fn: Callable, args: tuple, donate_argnums: tuple = (),
                 compile: bool = False) -> Callable:
    """``jax.jit(fn)`` with the arrays ``fn`` closes over passed to the
    executable as arguments instead of embedded in it.

    A jitted closure compiles every device array it captures into its
    executable as a constant.  The runners close over an
    :class:`EdgeContext`, so each would carry the graph's edge arrays
    through the compiler: at Graph500 scale 20 about 1 GB per runner,
    minutes of compile and tens of GB of host memory.  ``fn`` is traced
    once on ``args`` to find what it captures; the returned callable
    takes the same arguments as ``fn`` (``compile=True`` compiles it
    ahead of time for those arguments' shapes).
    """
    with TraceAnnotation(spans.TRACE):
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    consts = [jnp.asarray(c) for c in closed.consts]
    out_tree = jax.tree.structure(out_shape)

    def call(consts, *a):
        out = jax.core.eval_jaxpr(closed.jaxpr, consts, *jax.tree.leaves(a))
        return jax.tree.unflatten(out_tree, out)

    jitted = jax.jit(call,
                     donate_argnums=tuple(i + 1 for i in donate_argnums))
    if compile:
        with TraceAnnotation(spans.COMPILE):
            jitted = jitted.lower(consts, *args).compile()
    return partial(jitted, consts)


def _run_host(program: VertexProgram, ctx: EdgeContext, state,
              limit: int, warmup: bool) -> RunResult:
    """Kernel-per-iteration oracle engine: one jitted dispatch per step
    plus a blocking convergence read between steps."""

    def build():
        def step_fn(st, it):
            new = program.step(ctx, st, it)
            done = program.converged(st, new)
            return new, done
        step = _jit_hoisted(step_fn, (state, jnp.int32(0)),
                            donate_argnums=(0,))
        if warmup:  # compile outside the timed region (paper times
            # kernels only).  `step` donates its input, so warm the jit
            # cache on a copy.  Inside build(): a cached step is already
            # compiled, so repeats skip the warmup execution too.
            copy = jax.tree.map(lambda x: x.copy(), state)
            jax.block_until_ready(step(copy, jnp.int32(0)))
        return program, step

    step = _cached_exec_fn(program, ctx, ("host",), build)
    traced, occ_traced = _trace_flags(program, state)
    # Per-iteration trace scalars are carried off as *async* device
    # copies (the originals are donated to the next step) and decoded
    # into host bools/floats only after the timer stops — the timed
    # region contains no host-blocking trace reads.  Host-vs-fused
    # timing deltas are then dominated by the per-iteration dispatch +
    # convergence-sync cost (traced programs additionally enqueue two
    # scalar copies per iteration here, a second-order effect).
    dir_raw: List[jax.Array] = []
    occ_raw: List[jax.Array] = []
    t0 = time.perf_counter()
    it, done = 0, False
    while it < limit:
        STATS.dispatches += 1
        state, done_dev = step(state, jnp.int32(it))
        it += 1
        if traced:
            dir_raw.append(state[FRONTIER_DIR_KEY].copy())
        if occ_traced:
            occ_raw.append(state[FRONTIER_OCC_KEY].copy())
        done = bool(done_dev)  # the host engine's inherent per-step sync
        if done:
            break
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    trace = "".join("T" if bool(d) else "S" for d in dir_raw)
    occ_trace = [float(o) for o in occ_raw]
    return RunResult(state=state, iterations=it, seconds=dt, converged=done,
                     direction_trace=trace if traced else None,
                     occupancy_trace=occ_trace if occ_traced else None,
                     engine="host", dispatches=it)


def _fused_loop(program: VertexProgram, ctx: EdgeContext, limit: int,
                traced: bool, occ_traced: bool) -> Callable:
    """The fused engine's ``(state, dir_buf, occ_buf)`` convergence loop,
    the function its runner is traced and compiled from."""

    def fused(st, db, ob):
        def cond(carry):
            _, it, done, _, _ = carry
            return (it < limit) & ~done

        def body(carry):
            st, it, done, db, ob = carry
            with jax.named_scope(spans.VERTEX_STEP):
                new = program.step(ctx, st, it)
                done = program.converged(st, new)
            if traced:
                db = jax.lax.dynamic_update_index_in_dim(
                    db, jnp.asarray(new[FRONTIER_DIR_KEY], bool), it, 0)
            if occ_traced:
                ob = jax.lax.dynamic_update_index_in_dim(
                    ob, jnp.asarray(new[FRONTIER_OCC_KEY], jnp.float32),
                    it, 0)
            return new, it + jnp.int32(1), done, db, ob

        return jax.lax.while_loop(
            cond, body,
            (st, jnp.int32(0), jnp.asarray(False), db, ob))

    return fused


def _run_fused(program: VertexProgram, ctx: EdgeContext, state,
               limit: int, warmup: bool) -> RunResult:
    """Device-resident engine: the whole convergence loop is one jitted
    ``lax.while_loop`` dispatch with one host sync at the end.

    Carry layout: ``(state, it, done, dir_buf, occ_buf)``.  The trace
    buffers are preallocated ``[limit]`` device arrays the body writes
    at index ``it`` via ``lax.dynamic_update_index_in_dim``; after the
    loop the first ``it`` entries decode to the same
    ``direction_trace``/``occupancy_trace`` strings/lists the host
    engine produces, preserving the frontier protocol bit for bit.
    """
    traced, occ_traced = _trace_flags(program, state)
    dir_buf = jnp.zeros((limit,), bool) if traced else None
    occ_buf = (jnp.full((limit,), dense_occupancy())
               if occ_traced else None)
    fused = _fused_loop(program, ctx, limit, traced, occ_traced)

    def build():
        # warmup AOT-compiles outside the timed region; unlike the host
        # engine's run-one-step warmup this executes nothing on device.
        # The compiled executable is cached per (runner, context,
        # limit) so sweep repeats skip the while_loop compile entirely.
        return program, _jit_hoisted(fused, (state, dir_buf, occ_buf),
                                     donate_argnums=(0, 1, 2),
                                     compile=warmup)

    fn = _cached_exec_fn(program, ctx,
                         ("fused", limit, traced, occ_traced), build)
    t0 = time.perf_counter()
    STATS.dispatches += 1
    with TraceAnnotation(spans.DISPATCH):
        state, it_dev, done_dev, dir_buf, occ_buf = fn(state, dir_buf,
                                                       occ_buf)
    with TraceAnnotation(spans.WAIT):
        jax.block_until_ready((state, it_dev, done_dev, dir_buf, occ_buf))
    dt = time.perf_counter() - t0
    # the run's single host sync is above; everything below is decoding
    with TraceAnnotation(spans.DECODE):
        it = int(it_dev)
        done = bool(done_dev)
        trace = None
        occ_trace = None
        if traced:
            trace = "".join("T" if b else "S"
                            for b in np.asarray(dir_buf)[:it])
        if occ_traced:
            occ_trace = [float(o) for o in np.asarray(occ_buf)[:it]]
    return RunResult(state=state, iterations=it, seconds=dt, converged=done,
                     direction_trace=trace, occupancy_trace=occ_trace,
                     engine="fused", dispatches=1)


def run(program: VertexProgram, graph: Graph, config: SystemConfig,
        key: Optional[jax.Array] = None, max_iters: Optional[int] = None,
        use_pallas: bool = False, warmup: bool = True,
        sparse_edge_capacity: Optional[int] = None,
        engine: str = "fused", autotune=None,
        checkpoint_every: int = 0, retry=None, sentinels: bool = True,
        ring_capacity: Optional[int] = None,
        fault_injector=None,
        checkpoint_dir: Optional[str] = None,
        specialize=None) -> RunResult:
    """Iterate ``program`` on ``graph`` under ``config`` to convergence.

    ``engine`` picks the convergence loop: ``"fused"`` (default) runs
    the whole loop on device as one ``lax.while_loop`` dispatch;
    ``"host"`` is the kernel-per-iteration debugging oracle the fused
    engine is tested against.  Both produce identical states,
    iteration counts and traces.

    ``autotune`` picks the Pallas reducer tiling plans: ``"off"``
    (default, also ``None``/``False``) keeps the static default tiling;
    ``"heuristic"`` derives a plan from the graph's degree features
    with zero measurement; ``"measure"`` (also ``True``) runs the
    empirical candidate sweep, cached per graph in ``PLAN_CACHE`` and
    persisted to ``results/autotune_cache.json`` keyed by degree
    signature, so sweeps and repeat traffic never re-tune.  Tiling is a
    performance choice only — results are unaffected.

    Resilience knobs (any of them set delegates to
    :func:`repro.core.resilience.run_resilient`, whose results are
    bit-identical to the plain engines): ``checkpoint_every=K``
    segments the convergence loop into K-iteration dispatches whose
    carry snapshots into a bounded host-side checkpoint ring and whose
    boundaries evaluate the program's invariant sentinels;
    ``retry=RetryPolicy(...)`` rolls back to a clean checkpoint and
    re-executes on failure, walking a degradation chain (autotuned →
    default tiling, sparse → dense frontier, fused → host engine);
    ``sentinels=False`` disables the sentinel battery and the
    converged-state certificate; ``ring_capacity`` bounds the ring;
    ``fault_injector`` is the seeded fault harness's hook
    (:mod:`repro.testing.faults`); ``checkpoint_dir`` spills every
    checkpoint boundary to a durable on-disk
    :class:`~repro.core.durability.CheckpointStore` and resumes a
    killed run from the newest intact generation, bit-identical to an
    uninterrupted run.

    ``specialize`` resolves which config actually runs: ``"off"``
    (default, also ``None``/``False``) executes the ``config`` argument
    as passed; ``"static"`` applies the paper's full decision tree to
    (program properties, graph taxonomy profile); ``"learned"``
    consults the trained model at
    :data:`repro.core.specialize_learned.DEFAULT_MODEL_PATH`, falling
    back learned -> static partial -> caller with a structured
    :class:`~repro.core.specialize_learned.SpecializeFallbackWarning`
    when a tier is unavailable.  The resolved config (inheriting the
    caller's ``n_chunks``) and its source are stamped on
    ``RunResult.config_name`` / ``config_source``.
    """
    if engine not in ("fused", "host"):
        raise ValueError(f"unknown engine {engine!r}; "
                         "expected 'fused' or 'host'")
    with TraceAnnotation(spans.RUN):
        config_source = "caller"
        if specialize not in (None, False, "off"):
            from repro.core.specialize_learned import resolve_config
            config, config_source = resolve_config(program, graph, config,
                                                   specialize)
        if (checkpoint_every or retry is not None
                or fault_injector is not None or checkpoint_dir is not None):
            from repro.core.resilience import run_resilient
            res = run_resilient(
                program, graph, config, key=key, max_iters=max_iters,
                use_pallas=use_pallas, warmup=warmup,
                sparse_edge_capacity=sparse_edge_capacity, engine=engine,
                autotune=autotune, checkpoint_every=checkpoint_every,
                retry=retry, sentinels=sentinels,
                ring_capacity=ring_capacity, fault_injector=fault_injector,
                checkpoint_dir=checkpoint_dir)
        else:
            ctx = EdgeContext.create(
                graph, config, use_pallas=use_pallas,
                sparse_edge_capacity=sparse_edge_capacity,
                autotune=autotune)
            with TraceAnnotation(spans.INIT):
                state = program.init(graph, key) if key is not None \
                    else program.init(graph)
                state = jax.tree.map(jnp.asarray, state)
            limit = max_iters or program.max_iters
            runner = _run_fused if engine == "fused" else _run_host
            res = runner(program, ctx, state, limit, warmup)
        res.config_name = config.name
        res.config_source = config_source
    return res


def run_batch(program: VertexProgram, graphs, config: SystemConfig,
              keys: Optional[list] = None,
              max_iters: Optional[int] = None, use_pallas: bool = False,
              warmup: bool = True,
              sparse_edge_capacity: Optional[int] = None,
              autotune=None,
              max_batch: Optional[int] = None,
              specialize=None) -> List[RunResult]:
    """Run ``program`` on many graphs as block-diagonal packed batches.

    The serving-path counterpart of :func:`run`: graphs are grouped
    into padding buckets (quantized ``(n, m)`` plus ``block_size`` —
    see :func:`repro.core.batch.bucket_key`), each bucket is packed
    into one block-diagonal graph (cached in :data:`PLAN_CACHE` per
    graph tuple) and driven to convergence by **one** fused
    ``lax.while_loop`` dispatch with per-graph convergence masking —
    B graphs cost one dispatch instead of B.  Results come back in
    input order, one :class:`RunResult` per graph, with
    ``engine="batched"`` and per-graph states, iteration counts and
    direction/occupancy traces **bit-identical** to per-graph
    sequential ``run(...)`` for programs whose reductions use
    order-independent monoids (min/max or exact integer sums — BFS,
    SSSP); inexact float sums may differ in final ULPs because the
    packed schedule reduces edges in a different order.  Each result's
    ``seconds`` is its batch's wall time divided by the batch size.

    ``keys`` optionally supplies one PRNG key per graph for programs
    with randomized init.  When omitted for a program that declares
    ``randomized=True`` (coloring, MIS), per-graph keys are derived as
    ``fold_in(key(0), batch_index)`` — every graph draws *independent*
    priorities; the old shared-default-key behavior correlated
    tie-breaks across supposedly independent batch members.  To
    reproduce one graph's batched result sequentially, pass the same
    ``fold_in(key(0), i)`` to :func:`run`.  ``max_batch`` caps how many
    graphs pack into one dispatch (a bucket with more graphs is
    split).  The remaining knobs mean what they mean on :func:`run`;
    ``sparse_edge_capacity`` is applied per graph (0 disables the
    sparse path batch-wide).

    ``specialize`` resolves each graph's config independently (see
    :func:`run`): grouping then keys on *(padding bucket, resolved
    config)*, so graphs whose predicted configs differ never share a
    packed dispatch, and every result carries its own
    ``config_name``/``config_source``.
    """
    from repro.core.batch import (BatchedEdgeContext, bucket_key,
                                  get_graph_batch, run_fused_batch)
    graphs = list(graphs)
    if keys is None and program.randomized:
        base = jax.random.key(0)
        keys = [jax.random.fold_in(base, i) for i in range(len(graphs))]
    if keys is not None and len(keys) != len(graphs):
        raise ValueError(f"{len(keys)} keys for {len(graphs)} graphs")
    if max_batch is not None and max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if specialize in (None, False, "off"):
        resolved = [(config, "caller")] * len(graphs)
    else:
        from repro.core.specialize_learned import resolve_config
        resolved = [resolve_config(program, g, config, specialize)
                    for g in graphs]
    limit = max_iters or program.max_iters
    groups: dict = {}
    for i, g in enumerate(graphs):
        groups.setdefault((bucket_key(g), resolved[i][0]), []).append(i)
    results: List[Optional[RunResult]] = [None] * len(graphs)
    for (_, group_config), idxs in groups.items():
        step = max_batch or len(idxs)
        for lo in range(0, len(idxs), step):
            part = idxs[lo:lo + step]
            batch = get_graph_batch(tuple(graphs[i] for i in part))
            bctx = BatchedEdgeContext.create(
                batch, group_config, use_pallas=use_pallas,
                sparse_edge_capacity=sparse_edge_capacity,
                autotune=autotune)
            states = [program.init(graphs[i]) if keys is None
                      else program.init(graphs[i], keys[i])
                      for i in part]
            packed = batch.pack_state(states, pad=program.state_pad)
            for i, r in zip(part, run_fused_batch(program, batch, bctx,
                                                  packed, limit, warmup)):
                r.config_name = group_config.name
                r.config_source = resolved[i][1]
                results[i] = r
    return results
