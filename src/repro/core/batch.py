"""Batched multi-graph serving execution (block-diagonal packing).

The paper's headline result — no single (coherence, consistency,
push/pull) configuration wins across workloads — means a serving system
must run *many* graphs under *many* configurations cheaply.  The frontier
executor binds exactly one graph per :class:`~repro.core.executor.
EdgeContext` and pays a full fused-loop dispatch per graph; for the
small graphs serving traffic is made of, that per-operation overhead
dominates (the effect Gunrock documents for small-graph GPU analytics,
and Besta et al. show is worst exactly when frontiers are tiny).

This module amortizes it by packing B structurally-compatible graphs
into **block-diagonal** CSR/CSC edge arrays and driving the whole batch
through **one** fused ``lax.while_loop`` dispatch:

- **Packing** (:func:`pack_graphs`).  Every graph in a batch is padded
  to the batch's bucket shape ``(n_q, m_q)`` (see :func:`bucket_shape`);
  graph *i* owns vertex rows ``[i*n_q, (i+1)*n_q)`` and edge rows
  ``[i*m_q, (i+1)*m_q)`` of the packed arrays.  Padding vertices carry
  only self-loop padding edges, so any influence they could have is
  confined to themselves; padding state rows are zero-filled and the
  padded segments are marked converged from iteration 0.  Because
  vertex ranges are disjoint, every destination segment of the packed
  edge list belongs to exactly one graph — the segment-reduce kernels
  (scatter, sorted-segment, owned-blocked, gathered) are reused
  *unchanged* on the packed arrays.

- **Per-graph semantics** (:class:`BatchedEdgeContext`).  Programs run
  against the same ``ctx`` API they use sequentially; direction choice
  (:meth:`~BatchedEdgeContext.choose_direction`) and sparse-gather
  occupancy are computed **per graph** from each graph's own frontier
  statistics and true ``(n, m)``, bit-identical to the scalar
  heuristic, while the *execution* realisation (which packed edge order
  to scan, whether to take the packed sparse gather) is a batch-level
  performance choice — sound for the order-independent monoids
  (min/max and exact integer sums) the traversal programs use.

- **Convergence masking** (:func:`run_fused_batch`).  The fused carry
  holds per-graph iteration counts and ``done`` flags plus
  ``[B, max_iters]`` direction/occupancy trace buffers; a graph's state
  freezes the iteration after it converges (so extra batch iterations
  cannot perturb it) and the loop exits once every graph's flag is set.
  Unbatching slices per-graph :class:`~repro.core.executor.RunResult`\\ s
  that are bit-identical to sequential ``run()`` — states, iteration
  counts, direction and occupancy traces.

Plan-cache integration: packed batches are cached under
``kind="batch_pack"`` keyed on the member graph identities (anchored on
the first graph, the rest pinned strongly so their ids cannot recycle),
and bound batch contexts under ``kind="batch_context"`` on the packed
graph — repeat serving traffic over the same graph set reuses the pack,
the context and the compiled batch runner outright.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spans
from repro.core.config_space import SystemConfig, UpdateProp
from repro.core.executor import (EdgeContext, RunResult, STATS,
                                 _cached_exec_fn, _jit_hoisted,
                                 _normalize_autotune, _trace_flags)
from repro.core.frontier import ALPHA, choose_direction_batch
from repro.core.plan_cache import PLAN_CACHE
from repro.core.vertex_program import (FRONTIER_DIR_KEY, FRONTIER_OCC_KEY,
                                       EdgePhase, VertexProgram,
                                       dense_occupancy)
from repro.graph.structure import Graph
from repro.kernels.segment_reduce import bin_edges_by_block

__all__ = ["bucket_shape", "bucket_key", "pack_graphs", "get_graph_batch",
           "GraphBatch", "BatchedEdgeContext", "run_fused_batch",
           "run_batch_slice"]

#: Smallest padded vertex/edge bucket: tiny graphs quantize up to these
#: so a bucket never degenerates to widths the [B, n_q] row views (and
#: the [B]-vs-[n_total] leaf classification) cannot distinguish.
MIN_BUCKET_N = 8
MIN_BUCKET_M = 16


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def bucket_shape(n_nodes: int, m_edges: int) -> Tuple[int, int]:
    """Quantized padded shape ``(n_q, m_q)`` for one graph.

    Power-of-two quantization bounds the distinct packed shapes (and
    therefore jit recompiles) at log-many buckets per decade while
    wasting at most 2x padding.  When edge padding is needed
    (``m_q > m``) the vertex quantum is bumped past ``n`` so at least
    one padding vertex exists to carry the padding self-loops — padding
    edges never touch real vertices.
    """
    n, m = int(n_nodes), int(m_edges)
    n_q = _next_pow2(max(n, MIN_BUCKET_N))
    m_q = _next_pow2(max(m, MIN_BUCKET_M))
    if m_q > m and n_q == n:
        n_q *= 2
    return n_q, m_q


def bucket_key(graph: Graph) -> Tuple[int, int, int]:
    """The padding-bucket a graph batches under: ``(n_q, m_q,
    block_size)``.  Graphs sharing a key are structurally compatible —
    they pack into one batch with bounded padding and identical packed
    shapes, so repeated traffic over a bucket reuses one compiled
    runner shape."""
    n_q, m_q = bucket_shape(graph.n_nodes, graph.n_edges)
    return (n_q, m_q, int(graph.block_size))


def _padded_local(g: Graph, n_q: int, m_q: int) -> dict:
    """One graph's arrays padded to ``(n_q, m_q)`` in local ids.

    Padding edges are self-loops spread over the padding vertices
    ``[n, n_q)`` (sorted, so both the CSR and CSC order of the padded
    graph remain sorted); padding rows extend both row-pointer arrays
    consistently.
    """
    n, m = g.n_nodes, g.n_edges
    pad_n, pad_m = n_q - n, m_q - m
    if pad_m and not pad_n:
        raise ValueError("padding edges need at least one padding vertex "
                         f"(n={n} == n_q={n_q} but m={m} < m_q={m_q})")
    a = lambda x: np.asarray(x)
    if pad_m:
        pv = np.sort(np.arange(pad_m, dtype=np.int64) % pad_n) + n
    else:
        pv = np.zeros(0, np.int64)
    counts = np.bincount(pv - n, minlength=pad_n) if pad_n \
        else np.zeros(0, np.int64)
    ones = np.ones(pad_m, np.float32)
    rp_pad = np.cumsum(counts)
    return {
        "src": np.concatenate([a(g.src), pv]),
        "dst": np.concatenate([a(g.dst), pv]),
        "weight": np.concatenate([a(g.weight), ones]),
        "row_ptr_out": np.concatenate([a(g.row_ptr_out), m + rp_pad]),
        "src_in": np.concatenate([a(g.src_in), pv]),
        "dst_in": np.concatenate([a(g.dst_in), pv]),
        "weight_in": np.concatenate([a(g.weight_in), ones]),
        "row_ptr_in": np.concatenate([a(g.row_ptr_in), m + rp_pad]),
        "out_degree": np.concatenate([a(g.out_degree), counts]),
        "in_degree": np.concatenate([a(g.in_degree), counts]),
    }


@dataclasses.dataclass
class GraphBatch:
    """B graphs packed block-diagonally into one padded :class:`Graph`.

    Graph *i* occupies vertices ``[i*n_q, i*n_q + n_i)`` (then padding
    to ``(i+1)*n_q``) and edges ``[i*m_q, i*m_q + m_i)`` of ``packed``.
    ``n_nodes_b``/``n_edges_b`` carry the **true** per-graph sizes the
    per-graph heuristics use.

    Lifecycle: the batch holds its packed graph and the member graphs
    ``1..B-1`` strongly (so their ids cannot recycle under the
    ``batch_pack`` cache entry) but the *anchor* graph ``0`` only
    weakly — the cache entry is keyed on the anchor's identity, so when
    the anchor is collected the entry is evicted and the whole chain
    (batch, packed graph, its contexts and compiled runners) dies with
    it instead of leaking.
    """
    packed: Graph
    n_q: int
    m_q: int
    n_nodes_b: np.ndarray
    n_edges_b: np.ndarray
    _anchor: Any = dataclasses.field(repr=False, default=None)
    _pinned: tuple = dataclasses.field(repr=False, default=())

    @property
    def size(self) -> int:
        return int(self.n_nodes_b.shape[0])

    @property
    def n_total(self) -> int:
        return self.size * self.n_q

    # ------------------------------------------------------------------
    def pack_state(self, states: Sequence[Any], pad: Optional[dict] = None):
        """Pack per-graph state pytrees into the block-diagonal layout.

        Per-graph ``[n_i, ...]`` vertex leaves become one
        ``[B*n_q, ...]`` leaf (padding rows zero-filled — inert, because
        padding vertices carry only self-loops and their segments are
        frozen from iteration 0); scalar leaves stack to ``[B]``.

        ``pad`` (a program's :attr:`~repro.core.vertex_program.
        VertexProgram.state_pad`) overrides the padding fill per state
        key, for programs whose zero value is *live* rather than inert
        — MIS pads ``status`` with 2 ("removed") because a padding row
        of undecided zeros would block per-graph convergence forever.
        """
        if len(states) != self.size:
            raise ValueError(f"expected {self.size} states, "
                             f"got {len(states)}")
        states = [jax.tree.map(jnp.asarray, s) for s in states]
        ns = [int(n) for n in self.n_nodes_b]

        def pack_leaf(fill, *ls):
            if ls[0].ndim == 0:
                return jnp.stack(ls)
            rows = []
            for leaf, n in zip(ls, ns):
                if leaf.shape[0] != n:
                    raise ValueError(
                        "state leaves must be per-vertex ([n, ...]) or "
                        f"scalar; got shape {leaf.shape} for a graph "
                        f"with {n} vertices")
                p = self.n_q - n
                if p:
                    leaf = jnp.concatenate(
                        [leaf, jnp.full((p,) + leaf.shape[1:], fill,
                                        leaf.dtype)])
                rows.append(leaf)
            return jnp.concatenate(rows)

        pad = pad or {}
        if pad and isinstance(states[0], dict):
            return {k: jax.tree.map(partial(pack_leaf, pad.get(k, 0)),
                                    *(s[k] for s in states))
                    for k in states[0]}
        return jax.tree.map(partial(pack_leaf, 0), *states)

    def unpack_state(self, packed_state) -> List[Any]:
        """Slice the packed state back into per-graph pytrees
        (``pack_state``'s inverse on the non-padding rows)."""
        n_total = self.n_total
        outs = []
        for i in range(self.size):
            n = int(self.n_nodes_b[i])

            def cut(a, i=i, n=n):
                if a.ndim and a.shape[0] == n_total:
                    return a[i * self.n_q: i * self.n_q + n]
                return a[i]

            outs.append(jax.tree.map(cut, packed_state))
        return outs

    # ------------------------------------------------------------------
    def pack_state_host(self, states: Sequence[Any],
                        pad: Optional[dict] = None):
        """:meth:`pack_state` on host (numpy) arrays — same layout,
        bit-identical values, no device dispatches.

        The serving gateway repacks a bucket every scheduling slice;
        doing the B-way concatenation with numpy keeps that per-slice
        host work out of the device dispatch queue (the packed leaves
        transfer once, at the jitted runner's call boundary).
        """
        if len(states) != self.size:
            raise ValueError(f"expected {self.size} states, "
                             f"got {len(states)}")
        ns = [int(n) for n in self.n_nodes_b]

        def pack_leaf(fill, *ls):
            ls = [np.asarray(l) for l in ls]
            if ls[0].ndim == 0:
                return np.stack(ls)
            rows = []
            for leaf, n in zip(ls, ns):
                if leaf.shape[0] != n:
                    raise ValueError(
                        "state leaves must be per-vertex ([n, ...]) or "
                        f"scalar; got shape {leaf.shape} for a graph "
                        f"with {n} vertices")
                p = self.n_q - n
                if p:
                    leaf = np.concatenate(
                        [leaf, np.full((p,) + leaf.shape[1:], fill,
                                       leaf.dtype)])
                rows.append(leaf)
            return np.concatenate(rows)

        pad = pad or {}
        if pad and isinstance(states[0], dict):
            return {k: jax.tree.map(partial(pack_leaf, pad.get(k, 0)),
                                    *(s[k] for s in states))
                    for k in states[0]}
        return jax.tree.map(partial(pack_leaf, 0), *states)

    def unpack_state_host(self, packed_state) -> List[Any]:
        """:meth:`unpack_state` to host (numpy) pytrees: one device
        sync per leaf, then per-graph numpy slices (copies, so the
        packed buffers are not pinned by the returned views)."""
        host = jax.tree.map(np.asarray, packed_state)
        n_total = self.n_total
        outs = []
        for i in range(self.size):
            n = int(self.n_nodes_b[i])

            def cut(a, i=i, n=n):
                if a.ndim and a.shape[0] == n_total:
                    return a[i * self.n_q: i * self.n_q + n].copy()
                return a[i]  # scalar indexing copies by construction

            outs.append(jax.tree.map(cut, host))
        return outs


def pack_graphs(graphs: Sequence[Graph]) -> GraphBatch:
    """Pack graphs into one block-diagonal padded :class:`Graph`.

    All graphs are padded to the batch bucket shape (the max of their
    per-graph :func:`bucket_shape`\\ s) so the packed arrays have shape
    ``[B*m_q]``/``[B*n_q]``; the by-src and by-dst orders are pure
    concatenations of the per-graph orders (vertex offsets are
    monotone), and the owned order is re-binned on the packed ids
    because per-graph vertex offsets need not align with block
    boundaries.
    """
    graphs = tuple(graphs)
    if not graphs:
        raise ValueError("pack_graphs needs at least one graph")
    block_size = graphs[0].block_size
    if any(g.block_size != block_size for g in graphs):
        raise ValueError("all graphs in a batch must share block_size")
    shapes = [bucket_shape(g.n_nodes, g.n_edges) for g in graphs]
    n_q = max(s[0] for s in shapes)
    m_q = max(s[1] for s in shapes)
    if any(m_q > g.n_edges and n_q == g.n_nodes for g in graphs):
        n_q *= 2  # room for the padding vertex the larger m_q now needs

    locs = [_padded_local(g, n_q, m_q) for g in graphs]
    b = len(graphs)

    def cat_edges(name, off):
        return np.concatenate([loc[name] + (i * off if off else 0)
                               for i, loc in enumerate(locs)])

    src = cat_edges("src", n_q)
    dst = cat_edges("dst", n_q)
    weight = np.concatenate([loc["weight"] for loc in locs])
    src_in = cat_edges("src_in", n_q)
    dst_in = cat_edges("dst_in", n_q)
    weight_in = np.concatenate([loc["weight_in"] for loc in locs])
    rp_out = np.concatenate(
        [loc["row_ptr_out"][:-1] + i * m_q for i, loc in enumerate(locs)]
        + [np.array([b * m_q], np.int64)])
    rp_in = np.concatenate(
        [loc["row_ptr_in"][:-1] + i * m_q for i, loc in enumerate(locs)]
        + [np.array([b * m_q], np.int64)])
    out_degree = np.concatenate([loc["out_degree"] for loc in locs])
    in_degree = np.concatenate([loc["in_degree"] for loc in locs])
    perm_owned, block_ptr = bin_edges_by_block(dst, b * n_q, block_size)

    i32 = lambda x: np.asarray(x, np.int32)
    packed = Graph(
        src=i32(src), dst=i32(dst), weight=np.float32(weight),
        row_ptr_out=i32(rp_out),
        src_in=i32(src_in), dst_in=i32(dst_in),
        weight_in=np.float32(weight_in), row_ptr_in=i32(rp_in),
        out_degree=i32(out_degree), in_degree=i32(in_degree),
        perm_owned=i32(perm_owned), block_ptr=i32(block_ptr),
        n_nodes=b * n_q, n_edges=b * m_q, block_size=int(block_size),
    )
    return GraphBatch(
        packed=packed, n_q=n_q, m_q=m_q,
        n_nodes_b=np.asarray([g.n_nodes for g in graphs], np.int64),
        n_edges_b=np.asarray([g.n_edges for g in graphs], np.int64),
        _anchor=weakref.ref(graphs[0]), _pinned=graphs[1:],
    )


def get_graph_batch(graphs: Sequence[Graph]) -> GraphBatch:
    """Cached :func:`pack_graphs`: one pack per (ordered) graph tuple.

    Keyed on the member identities and anchored on the first graph —
    see :class:`GraphBatch` for why that is safe against id recycling.
    """
    graphs = tuple(graphs)
    if not graphs:
        raise ValueError("get_graph_batch needs at least one graph")
    key = tuple(id(g) for g in graphs)
    return PLAN_CACHE.get(graphs[0], "batch_pack", key,
                          lambda: pack_graphs(graphs))


# ---------------------------------------------------------------------------
class BatchedEdgeContext:
    """A batch of graphs bound to one :class:`SystemConfig`.

    Drop-in for :class:`~repro.core.executor.EdgeContext` from a
    program's point of view — ``choose_direction`` returns ``[B]``
    per-graph flags computed from each graph's own frontier statistics
    (bit-identical to the sequential heuristic), ``propagate_sparse``
    returns ``[B]`` per-graph occupancies, and the reductions run once
    over the packed block-diagonal edge arrays through the wrapped
    packed-graph ``EdgeContext``.

    The packed *execution* direction (and the packed sparse-gather
    fallback) is a batch-level choice — the edge-weighted majority of
    the per-graph decisions — which is result-identical for the
    order-independent monoids (min/max, integer sums) the traversal
    programs reduce with; inexact float sums may differ in final ULPs
    from a sequential run, exactly like the dense-vs-gathered caveat on
    the sequential sparse path.
    """

    def __init__(self, batch: GraphBatch, config: SystemConfig,
                 use_pallas: bool = False,
                 sparse_edge_capacity: Optional[int] = None,
                 autotune=None):
        self.config = config
        self.use_pallas = use_pallas
        self.autotune = _normalize_autotune(autotune)
        self.B = batch.size
        self.n_q = batch.n_q
        self.m_q = batch.m_q
        self.n_total = batch.n_total
        #: user-level capacity knob (exec-fn cache key material): two
        #: contexts with different per-graph capacities trace different
        #: occupancy arithmetic even when the packed capacity collides.
        self.cap_key = (None if sparse_edge_capacity is None
                        else int(sparse_edge_capacity))
        n_b = batch.n_nodes_b
        m_b = batch.n_edges_b
        if sparse_edge_capacity is None:
            # per-graph sequential default: ceil(m/alpha), the same
            # formula as EdgeContext.default_sparse_capacity
            caps = np.minimum(m_b, np.maximum(16, -(-m_b // int(ALPHA))))
        else:
            caps = np.full(self.B, int(sparse_edge_capacity), np.int64)
        self._disabled = (sparse_edge_capacity is not None
                          and int(sparse_edge_capacity) == 0)
        if self._disabled:
            inner_cap: Optional[int] = 0
        elif sparse_edge_capacity is None:
            inner_cap = None  # packed default
        else:
            inner_cap = min(batch.packed.n_edges,
                            int(sparse_edge_capacity) * self.B)
        self.inner = EdgeContext.create(
            batch.packed, config, use_pallas=use_pallas,
            sparse_edge_capacity=inner_cap, autotune=self.autotune)
        self.n_nodes = batch.packed.n_nodes
        self.n_edges = batch.packed.n_edges
        self.n_nodes_b = jnp.asarray(n_b, jnp.int32)
        self.n_edges_b = jnp.asarray(m_b, jnp.int32)
        self.cap_b = jnp.asarray(caps, jnp.int32)
        self.vcap_b = jnp.asarray(
            np.maximum(1, np.minimum(n_b, caps)), jnp.int32)
        self._out_deg_rows = self.inner._out_degree.reshape(
            self.B, self.n_q)

    @classmethod
    def create(cls, batch: GraphBatch, config: SystemConfig,
               use_pallas: bool = False,
               sparse_edge_capacity: Optional[int] = None,
               autotune=None) -> "BatchedEdgeContext":
        """Cached constructor (``kind="batch_context"`` on the packed
        graph): a repeated (batch, config, knobs) cell reuses the bound
        context and, through it, the compiled batch runner."""
        cap = (None if sparse_edge_capacity is None
               else int(sparse_edge_capacity))
        mode = _normalize_autotune(autotune)
        return PLAN_CACHE.get(
            batch.packed, "batch_context",
            (config, bool(use_pallas), cap, mode),
            lambda: cls(batch, config, use_pallas=use_pallas,
                        sparse_edge_capacity=sparse_edge_capacity,
                        autotune=mode))

    # ------------------------------------------------------------------
    def resolve_direction(self, direction=None) -> UpdateProp:
        return self.inner.resolve_direction(direction)

    def choose_direction(self, frontier: jnp.ndarray, prev_pull,
                         unvisited: Optional[jnp.ndarray] = None
                         ) -> jnp.ndarray:
        """Per-graph traced direction flags ``[B]`` (True=pull).

        Each row reproduces the sequential heuristic on that graph's
        own frontier statistics and true ``(n, m)`` — the per-iteration
        direction trace of a batched run is bit-identical to the
        per-graph sequential traces.
        """
        prop = self.config.prop
        if prop is not UpdateProp.PUSH_PULL:
            return jnp.full((self.B,), prop is UpdateProp.PULL)
        with jax.named_scope(spans.DIRECTION):
            rows = frontier.reshape(self.B, self.n_q)
            urows = (unvisited.reshape(self.B, self.n_q)
                     if unvisited is not None else None)
            return choose_direction_batch(rows, self._out_deg_rows,
                                          self.n_edges_b, self.n_nodes_b,
                                          prev_pull, unvisited=urows)

    def dynamic_direction(self, want_pull) -> jnp.ndarray:
        """``[B]`` per-graph flags for an algorithm-chosen direction
        (static configs: the config's constant direction, like the
        sequential context)."""
        prop = self.config.prop
        if prop is not UpdateProp.PUSH_PULL:
            return jnp.full((self.B,), prop is UpdateProp.PULL)
        return jnp.broadcast_to(jnp.asarray(want_pull, bool), (self.B,))

    # ------------------------------------------------------------------
    # Per-graph state helpers (the batched overrides of the sequential
    # trivia on EdgeContext): scalars become [B], reductions become
    # row-wise over each graph's own n_q columns.  Padding rows receive
    # their graph's broadcast value and padding columns contribute to
    # row reductions — callers keep padding inert by construction
    # (zero/state_pad fills and padding-false masks), exactly like the
    # frontier statistics.

    @property
    def true_n_nodes(self) -> jnp.ndarray:
        """``[B]`` true per-graph vertex counts (no padding rows)."""
        return self.n_nodes_b

    def per_vertex(self, x) -> jnp.ndarray:
        """``[B]`` per-graph values -> ``[B*n_q]``, each graph's rows
        (padding included) filled with that graph's value."""
        x = jnp.asarray(x)
        if x.ndim == 0:
            return jnp.broadcast_to(x, (self.n_total,))
        return jnp.repeat(x, self.n_q, total_repeat_length=self.n_total)

    def align_per_graph(self, x) -> jnp.ndarray:
        """Batched alignment must materialize: each packed row needs
        its own graph's value (the sequential version is the identity;
        see ``EdgeContext.align_per_graph``)."""
        return self.per_vertex(x)

    def per_graph_sum(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(x.reshape((self.B, self.n_q) + x.shape[1:]),
                       axis=1)

    def per_graph_any(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.any(x.reshape((self.B, self.n_q) + x.shape[1:]),
                       axis=1)

    def vertex_offsets(self) -> jnp.ndarray:
        """``[B*n_q]`` packed row base (``i*n_q``) of each vertex's
        graph — the shift that turns vertex-id-valued state (CC
        labels) into packed row indices."""
        return jnp.repeat(
            jnp.arange(self.B, dtype=jnp.int32) * jnp.int32(self.n_q),
            self.n_q, total_repeat_length=self.n_total)

    def cond_per_graph(self, pred, true_fn, false_fn, state):
        """Per-graph branch select: both branches execute on the packed
        arrays (graphs may disagree — lax.cond needs one predicate) and
        each graph's rows keep its own branch's result via the freeze
        selector."""
        return self.freeze(jnp.asarray(pred, bool),
                           true_fn(state), false_fn(state))

    # ------------------------------------------------------------------
    def _frontier_edges_b(self, mask: jnp.ndarray) -> jnp.ndarray:
        rows = mask.reshape(self.B, self.n_q)
        return jnp.sum(jnp.where(rows, self._out_deg_rows, 0), axis=1)

    def _exec_direction(self, state, phase: EdgePhase, pull_b) -> jnp.ndarray:
        """The batch's single packed execution direction: the
        edge-weighted majority of the per-graph choices (graphs with an
        empty frontier — converged ones included — vote with weight 0).
        A perf-only choice: results are direction-independent for the
        order-independent monoids the batch path serves."""
        pull_b = jnp.asarray(pull_b, bool)
        if pull_b.ndim == 0:
            return pull_b
        if phase.frontier is None:
            return jnp.sum(pull_b.astype(jnp.int32)) * 2 > self.B
        m_f = self._frontier_edges_b(phase.frontier(state))
        m_pull = jnp.sum(jnp.where(pull_b, m_f, 0))
        m_push = jnp.sum(jnp.where(pull_b, 0, m_f))
        return m_pull > m_push

    def propagate(self, state, phase: EdgePhase, direction=None,
                  dtype=jnp.float32) -> jnp.ndarray:
        return self.inner.propagate(state, phase, direction, dtype)

    def propagate_dynamic(self, state, phase: EdgePhase, pull,
                          dtype=jnp.float32) -> jnp.ndarray:
        if self.config.prop is not UpdateProp.PUSH_PULL:
            return self.inner.propagate_dynamic(state, phase, False, dtype)
        return self.inner.propagate_dynamic(
            state, phase, self._exec_direction(state, phase, pull), dtype)

    def propagate_sparse(self, state, phase: EdgePhase, pull,
                         dtype=jnp.float32):
        """Batched ``propagate_sparse``: ``(reduced [B*n_q], occ [B])``.

        The occupancy vector carries each graph's *sequential*
        semantics — ``m_f / cap`` against that graph's own capacity
        when its sequential run would have taken the gathered push
        path, -1.0 otherwise — so per-graph occupancy traces unbatch
        bit-identically.  The reduction itself runs once over the
        packed arrays (packed sparse gather when the whole batch
        frontier fits the packed capacity, dense otherwise).
        """
        dense_b = jnp.full((self.B,), dense_occupancy())
        if (self.config.prop is not UpdateProp.PUSH_PULL
                or phase.frontier is None or not phase.gatherable
                or self._disabled):
            return (self.propagate_dynamic(state, phase, pull, dtype),
                    dense_b)
        pull_b = jnp.asarray(pull, bool)
        if pull_b.ndim == 0:
            pull_b = jnp.broadcast_to(pull_b, (self.B,))
        with jax.named_scope(spans.FRONTIER):
            mask = phase.frontier(state)
            rows = mask.reshape(self.B, self.n_q)
            m_f = jnp.sum(jnp.where(rows, self._out_deg_rows, 0), axis=1)
            n_f = jnp.sum(rows.astype(jnp.int32), axis=1)
            fits = (n_f <= self.vcap_b) & (m_f <= self.cap_b)
            occ = jnp.where(
                fits,
                m_f.astype(jnp.float32) / self.cap_b.astype(jnp.float32),
                dense_occupancy())
            occ = jnp.where(pull_b, dense_occupancy(), occ)
            m_pull = jnp.sum(jnp.where(pull_b, m_f, 0))
            m_push = jnp.sum(jnp.where(pull_b, 0, m_f))
        out, _ = self.inner.propagate_sparse(
            state, phase, m_pull > m_push, dtype)
        return out, occ

    # ------------------------------------------------------------------
    def per_graph_view(self, state):
        """Reshape packed leaves into per-graph rows: ``[B*n_q, ...]``
        -> ``[B, n_q, ...]``, ``[B]`` stays — the axis-0 view
        ``vmap``/``converged`` consume."""
        def rows(a):
            if a.ndim and a.shape[0] == self.n_total:
                return a.reshape((self.B, self.n_q) + a.shape[1:])
            return a
        return jax.tree.map(rows, state)

    def converged_per_graph(self, program: VertexProgram, prev,
                            new) -> jnp.ndarray:
        """``[B]`` per-graph convergence verdicts: the program's own
        ``converged`` vmapped over per-graph state rows.  Padding
        columns are zero-filled and frozen, so each row's verdict
        equals the sequential one."""
        return jax.vmap(program.converged)(self.per_graph_view(prev),
                                           self.per_graph_view(new))

    def freeze(self, done_b: jnp.ndarray, old, new):
        """Keep ``old`` state for graphs whose ``done`` flag is set.

        This is the convergence mask that makes extra batch iterations
        invisible to already-converged graphs: their unbatched state is
        exactly the state after their own final iteration.
        """
        def sel(o, n):
            if o.ndim and o.shape[0] == self.n_total:
                keep = jnp.repeat(done_b, self.n_q).reshape(
                    (self.n_total,) + (1,) * (o.ndim - 1))
            else:
                keep = done_b.reshape((self.B,) + (1,) * (o.ndim - 1))
            return jnp.where(keep, o, n)
        return jax.tree.map(sel, old, new)


# ---------------------------------------------------------------------------
def run_fused_batch(program: VertexProgram, batch: GraphBatch,
                    bctx: BatchedEdgeContext, state, limit: int,
                    warmup: bool) -> List[RunResult]:
    """One fused ``lax.while_loop`` dispatch for the whole batch.

    Carry layout: ``(state, it, it_b, done_b, dir_buf, occ_buf)`` —
    per-graph iteration counts ``it_b [B]`` advance while a graph's
    ``done_b`` flag is unset, the per-graph done flags mask state
    updates (:meth:`BatchedEdgeContext.freeze`) and fold into the
    single convergence predicate ``(it < limit) & ~all(done_b)``, and
    the ``[B, limit]`` trace buffers record each graph's per-iteration
    direction/occupancy exactly as the sequential fused engine does in
    its ``[limit]`` buffers.
    """
    B = bctx.B
    traced, occ_traced = _trace_flags(program, state)
    dir_buf = jnp.zeros((B, limit), bool) if traced else None
    occ_buf = (jnp.full((B, limit), dense_occupancy())
               if occ_traced else None)

    def fused(st, db, ob):
        def cond(carry):
            _, it, _, done_b, _, _ = carry
            return (it < limit) & ~jnp.all(done_b)

        def body(carry):
            st, it, it_b, done_b, db, ob = carry
            new = program.step(bctx, st, it)
            conv = bctx.converged_per_graph(program, st, new)
            merged = bctx.freeze(done_b, st, new)
            it_b = it_b + jnp.where(done_b, 0, 1).astype(jnp.int32)
            if traced:
                col = jnp.asarray(merged[FRONTIER_DIR_KEY], bool)
                db = jax.lax.dynamic_update_slice(db, col[:, None], (0, it))
            if occ_traced:
                col = jnp.asarray(merged[FRONTIER_OCC_KEY], jnp.float32)
                ob = jax.lax.dynamic_update_slice(ob, col[:, None], (0, it))
            return (merged, it + jnp.int32(1), it_b, done_b | conv,
                    db, ob)

        return jax.lax.while_loop(
            cond, body,
            (st, jnp.int32(0), jnp.zeros((B,), jnp.int32),
             jnp.zeros((B,), bool), db, ob))

    def build():
        return program, _jit_hoisted(fused, (state, dir_buf, occ_buf),
                                     donate_argnums=(0, 1, 2),
                                     compile=warmup)

    fn = _cached_exec_fn(
        program, bctx.inner,
        ("batched", B, bctx.n_q, bctx.m_q, limit, traced, occ_traced,
         bctx.cap_key), build)
    t0 = time.perf_counter()
    STATS.dispatches += 1
    state, it_dev, it_b_dev, done_dev, db, ob = fn(state, dir_buf, occ_buf)
    jax.block_until_ready((state, it_dev, it_b_dev, done_dev, db, ob))
    dt = time.perf_counter() - t0
    return _decode_batch_results(batch, state, it_b_dev, done_dev, db, ob,
                                 traced, occ_traced, dt)


def _decode_batch_results(batch: GraphBatch, state, it_b_dev, done_dev,
                          db, ob, traced: bool, occ_traced: bool,
                          dt: float) -> List[RunResult]:
    # the batch's single host sync is above; everything below is decoding
    it_b = np.asarray(it_b_dev)
    done_b = np.asarray(done_dev)
    db_np = np.asarray(db) if traced else None
    ob_np = np.asarray(ob) if occ_traced else None
    states = batch.unpack_state(state)
    results = []
    for i in range(batch.size):
        k = int(it_b[i])
        trace = ("".join("T" if b else "S" for b in db_np[i, :k])
                 if traced else None)
        occs = ([float(o) for o in ob_np[i, :k]] if occ_traced else None)
        results.append(RunResult(
            state=states[i], iterations=k, seconds=dt / batch.size,
            converged=bool(done_b[i]), direction_trace=trace,
            occupancy_trace=occs, engine="batched", dispatches=1))
    return results


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class BatchSlice:
    """One continuous-batching dispatch's outputs, decoded to host.

    ``advanced[i]`` is how many iterations graph *i* executed inside
    this slice; its per-iteration direction/occupancy columns are
    ``dir_cols[i, :advanced[i]]`` / ``occ_cols[i, :advanced[i]]``
    (``None`` when the program does not trace).  ``state`` stays a
    packed device pytree so the next slice can consume it without a
    host round-trip; ``converged_b`` reports per-graph convergence
    (reaching ``limit_b`` does *not* set it — callers distinguish
    "converged" from "out of budget" via ``it_b``).
    """
    state: Any
    it_b: np.ndarray
    converged_b: np.ndarray
    advanced: np.ndarray
    dir_cols: Optional[np.ndarray]
    occ_cols: Optional[np.ndarray]
    seconds: float


def run_batch_slice(program: VertexProgram, batch: GraphBatch,
                    bctx: BatchedEdgeContext, state,
                    it_b, done_b, limit_b, slice_len: int,
                    warmup: bool = True) -> BatchSlice:
    """Advance the packed batch by **up to** ``slice_len`` iterations.

    The continuous-batching engine under the serving gateway: unlike
    :func:`run_fused_batch` (every graph starts at iteration 0 and the
    loop runs to whole-batch convergence), this dispatch resumes each
    graph from its own carried ``it_b[i]`` and stops early at the slice
    boundary, where the scheduler can retire converged graphs and join
    newly admitted ones before the next dispatch.

    Per-graph semantics are exact across slicing and batch-composition
    churn:

    - ``program.step`` receives the **per-graph** iteration counters
      (``[B]`` int32) instead of a batch-level scalar — a graph that
      joined mid-stream sees its own 0, 1, 2, ... exactly as its
      sequential run would (CC's alternating hooking direction and
      CLR's round-numbered colors depend on this).
    - a graph stops advancing once it converges *or* reaches its own
      ``limit_b[i]`` (per-request ``max_iters``); its rows freeze, so
      cohabitating graphs see nothing.
    - ``done_b`` marks slots the scheduler parked (free slots between
      requests): their rows are frozen from the first iteration and
      their trace columns never read.

    One timed jitted dispatch per call; the compiled runner is cached
    per (program, packed graph, slice_len, capacities), so steady-state
    serving traffic over a stable bucket roster re-enters a compiled
    executable every slice.
    """
    B = bctx.B
    traced, occ_traced = _trace_flags(program, state)
    dir_buf = jnp.zeros((B, slice_len), bool) if traced else None
    occ_buf = (jnp.full((B, slice_len), dense_occupancy())
               if occ_traced else None)
    it_b = jnp.asarray(np.asarray(it_b, np.int32))
    done_b0 = jnp.asarray(np.asarray(done_b, bool))
    limit_b = jnp.asarray(np.asarray(limit_b, np.int32))

    def sliced(st, it_b, parked_b, limit_b, db, ob):
        def stopped(conv_b, it_b):
            return parked_b | conv_b | (it_b >= limit_b)

        def cond(carry):
            _, s, it_b, conv_b, _, _ = carry
            return (s < slice_len) & ~jnp.all(stopped(conv_b, it_b))

        def body(carry):
            st, s, it_b, conv_b, db, ob = carry
            frozen = stopped(conv_b, it_b)
            new = program.step(bctx, st, it_b)
            conv = bctx.converged_per_graph(program, st, new)
            merged = bctx.freeze(frozen, st, new)
            it_b = it_b + jnp.where(frozen, 0, 1).astype(jnp.int32)
            conv_b = conv_b | (conv & ~frozen)
            if traced:
                col = jnp.asarray(merged[FRONTIER_DIR_KEY], bool)
                db = jax.lax.dynamic_update_slice(db, col[:, None], (0, s))
            if occ_traced:
                col = jnp.asarray(merged[FRONTIER_OCC_KEY], jnp.float32)
                ob = jax.lax.dynamic_update_slice(ob, col[:, None], (0, s))
            return (merged, s + jnp.int32(1), it_b, conv_b, db, ob)

        return jax.lax.while_loop(
            cond, body,
            (st, jnp.int32(0), it_b, jnp.zeros((B,), bool), db, ob))

    def build():
        return program, _jit_hoisted(
            sliced, (state, it_b, done_b0, limit_b, dir_buf, occ_buf),
            donate_argnums=(0, 4, 5), compile=warmup)

    fn = _cached_exec_fn(
        program, bctx.inner,
        ("batched_slice", B, bctx.n_q, bctx.m_q, slice_len, traced,
         occ_traced, bctx.cap_key), build)
    t0 = time.perf_counter()
    STATS.dispatches += 1
    out_state, _, it_out, conv_out, db, ob = fn(
        state, it_b, done_b0, limit_b, dir_buf, occ_buf)
    jax.block_until_ready((out_state, it_out, conv_out, db, ob))
    dt = time.perf_counter() - t0
    it_in = np.asarray(it_b)
    it_np = np.asarray(it_out)
    return BatchSlice(
        state=out_state,
        it_b=it_np,
        converged_b=np.asarray(conv_out),
        advanced=it_np - it_in,
        dir_cols=np.asarray(db) if traced else None,
        occ_cols=np.asarray(ob) if occ_traced else None,
        seconds=dt,
    )
