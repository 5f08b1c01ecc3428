"""Pure-numpy oracles for the six applications (test-side ground truth)."""
from __future__ import annotations

import numpy as np

from repro.graph.structure import Graph

__all__ = ["pagerank_np", "sssp_np", "cc_np", "bc_np", "bfs_np",
           "is_independent_set", "is_maximal_independent_set",
           "is_proper_coloring"]


def _segment_min_into(out, vals, row_ptr):
    """``out[i] = min(out[i], vals[row_ptr[i]:row_ptr[i+1]].min())`` for
    every non-empty row of a sorted edge order."""
    nz = np.flatnonzero(np.diff(row_ptr))
    if nz.size:
        out[nz] = np.minimum(out[nz], np.minimum.reduceat(vals, row_ptr[nz]))
    return out


def bfs_np(g: Graph, source=0):
    """Level-synchronous BFS depths; -1 for unreachable vertices."""
    v = g.n_nodes
    row_ptr = np.asarray(g.row_ptr_out, np.int64)
    col = np.asarray(g.dst, np.int64)
    depth = np.full(v, -1, np.int32)
    depth[source] = 0
    frontier = np.asarray([source], np.int64)
    level = 0
    while frontier.size:
        starts = row_ptr[frontier]
        counts = row_ptr[frontier + 1] - starts
        # every out-edge of the frontier, as positions in the CSR order
        offs = np.cumsum(counts) - counts
        edges = np.repeat(starts - offs, counts) + np.arange(counts.sum())
        nbr = np.unique(col[edges])
        frontier = nbr[depth[nbr] == -1]
        level += 1
        depth[frontier] = level
    return depth


def pagerank_np(g: Graph, damping=0.85, tol=1e-6, max_iters=256):
    v = g.n_nodes
    src = np.asarray(g.src, np.int64)
    dst = np.asarray(g.dst, np.int64)
    out_deg = np.asarray(g.out_degree, np.float64)
    rank = np.full(v, 1.0 / v)
    inv = 1.0 / np.maximum(out_deg, 1)
    dangling = out_deg == 0
    for _ in range(max_iters):
        contrib = np.bincount(dst, weights=rank[src] * inv[src], minlength=v)
        dm = rank[dangling].sum()
        new = (1 - damping) / v + damping * (contrib + dm / v)
        if np.abs(new - rank).sum() < tol:
            rank = new
            break
        rank = new
    return rank.astype(np.float32)


def sssp_np(g: Graph, source=0):
    """Bellman-Ford (graphs are symmetric; no negative weights)."""
    v = g.n_nodes
    src = np.asarray(g.src_in, np.int64)   # by-dst order: dst sorted
    row_ptr = np.asarray(g.row_ptr_in, np.int64)
    w = np.asarray(g.weight_in, np.float64)
    dist = np.full(v, np.inf)
    dist[source] = 0.0
    for _ in range(v):
        new = _segment_min_into(dist.copy(), dist[src] + w, row_ptr)
        if np.array_equal(new, dist, equal_nan=True):
            break
        dist = new
    return dist.astype(np.float32)


def cc_np(g: Graph):
    """Min-vertex-id component labels via BFS union."""
    v = g.n_nodes
    src_in = np.asarray(g.src_in, np.int64)
    row_ptr_in = np.asarray(g.row_ptr_in, np.int64)
    dst = np.asarray(g.dst, np.int64)
    row_ptr_out = np.asarray(g.row_ptr_out, np.int64)
    label = np.arange(v)
    changed = True
    while changed:
        new = _segment_min_into(label.copy(), label[src_in], row_ptr_in)
        new = _segment_min_into(new, label[dst], row_ptr_out)
        new = new[new]  # pointer jump
        changed = not np.array_equal(new, label)
        label = new
    return label.astype(np.int32)


def bc_np(g: Graph, root=0):
    """Brandes single-root dependency scores (unweighted)."""
    v = g.n_nodes
    row_ptr = np.asarray(g.row_ptr_out, np.int64)
    col = np.asarray(g.dst, np.int64)
    depth = np.full(v, -1, np.int64)
    sigma = np.zeros(v)
    depth[root], sigma[root] = 0, 1.0
    frontier = [root]
    order = [root]
    while frontier:
        nxt = []
        for u in frontier:
            for e in range(row_ptr[u], row_ptr[u + 1]):
                t = col[e]
                if depth[t] == -1:
                    depth[t] = depth[u] + 1
                    nxt.append(t)
                    order.append(t)
                if depth[t] == depth[u] + 1:
                    sigma[t] += sigma[u]
        frontier = nxt
    delta = np.zeros(v)
    for u in reversed(order):
        for e in range(row_ptr[u], row_ptr[u + 1]):
            t = col[e]
            if depth[t] == depth[u] + 1:
                delta[u] += sigma[u] / sigma[t] * (1.0 + delta[t])
    delta[root] = 0.0
    return delta.astype(np.float32)


def is_independent_set(g: Graph, member: np.ndarray) -> bool:
    src = np.asarray(g.src, np.int64)
    dst = np.asarray(g.dst, np.int64)
    return not np.any(member[src] & member[dst])


def is_maximal_independent_set(g: Graph, member: np.ndarray) -> bool:
    if not is_independent_set(g, member):
        return False
    src = np.asarray(g.src, np.int64)
    dst = np.asarray(g.dst, np.int64)
    # every non-member must have a member neighbor
    covered = np.zeros(g.n_nodes, bool)
    covered[dst[member[src]]] = True
    covered[src[member[dst]]] = True
    return bool(np.all(member | covered))


def is_proper_coloring(g: Graph, color: np.ndarray) -> bool:
    src = np.asarray(g.src, np.int64)
    dst = np.asarray(g.dst, np.int64)
    return bool(np.all(color >= 0)
                and not np.any(color[src] == color[dst]))
