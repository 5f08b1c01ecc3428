"""Plain SSSP (Graph500 kernel 3): float32 distances by frontier-synchronous
Bellman-Ford over the benchmark's own weighted edge list.

- The graph: the generator's tuples without self-loops, symmetrised, each
  vertex pair keeping the **minimum** weight over its duplicate tuples in
  either orientation; a shortest path over the multigraph can only use
  the lightest of parallel edges.
- ``solve``: every vertex's distance from ``root`` (``inf`` where
  unreached).  Each iteration relaxes the out-edges of the vertices
  whose distance improved in the last one, ``cand = dist[u] + w`` with
  both operands float32, and keeps the minimum.
- ``work``: Graph500 TEPS counting, the same as for BFS: the input edge
  tuples within the root's component, duplicates and self-loops
  included.
- ``compare``: how many vertices' float32 distances differ (``inf``
  equals ``inf``); an answer of another shape gets every vertex wrong.
- ``control``: the same search with the weights rounded to bfloat16, the
  precision below the float32 the program states.

The limit is exact, ``wrong_dists`` 0.  Weights are at least 0, and
round-to-nearest float32 addition is monotone with ``fl(a + w) >= a``, so
the fixpoint of ``d(v) = min(d(v), min_u fl(d(u) + w_uv))`` is unique:
each ``d(v)`` is the minimum, over the walks from the root, of the
left-folded float32 sum along the walk.  It does not depend on the order
in which edges are relaxed, so push, pull, chunked schedules and the
sparse gather all reach it bit for bit.
"""
from __future__ import annotations

import numpy as np

WORK_RULE = "component_input_edges"
LIMITS = {"wrong_dists": 0}


class WeightedGraph:
    """CSR of the symmetric closure of the tuples without self-loops:
    ``col[ptr[v]:ptr[v + 1]]`` are the distinct neighbours of ``v``,
    sorted, and ``weight`` holds each pair's least tuple weight."""

    def __init__(self, n: int, src, dst, weight):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        weight = np.asarray(weight, np.float32)
        keep = src != dst
        src, dst, weight = src[keep], dst[keep], weight[keep]
        key = np.concatenate([src * n + dst, dst * n + src])
        weight = np.concatenate([weight, weight])
        order = np.lexsort((weight, key))
        key, weight = key[order], weight[order]
        first = np.ones(key.shape[0], bool)
        first[1:] = key[1:] != key[:-1]
        key, weight = key[first], weight[first]
        self.n = int(n)
        self.col = key % n
        self.weight = weight
        self.ptr = np.searchsorted(key // n, np.arange(self.n + 1))


class Reference:
    def __init__(self, edges: dict):
        self.edges = edges
        self.graph = WeightedGraph(edges["n"], edges["src"], edges["dst"],
                                   edges["weight"])


def prepare(edges: dict) -> Reference:
    return Reference(edges)


def bellman_ford(ptr, col, weight, n: int, root: int) -> np.ndarray:
    """Float32 distances from ``root`` in the weighted CSR, ``inf`` where
    unreached."""
    dist = np.full(n, np.inf, np.float32)
    dist[root] = 0.0
    frontier = np.asarray([root], np.int64)
    while frontier.size:
        starts = ptr[frontier]
        counts = ptr[frontier + 1] - starts
        first = np.cumsum(counts) - counts
        idx = np.repeat(starts - first, counts) + np.arange(counts.sum())
        cand = np.repeat(dist[frontier], counts) + weight[idx]
        new = dist.copy()
        np.minimum.at(new, col[idx], cand)
        frontier = np.flatnonzero(new < dist)
        dist = new
    return dist


def solve(ref: Reference, root) -> np.ndarray:
    g = ref.graph
    return bellman_ford(g.ptr, g.col, g.weight, g.n, int(root))


def work(ref: Reference, root, want: np.ndarray) -> int:
    return int(np.count_nonzero(np.isfinite(want[ref.edges["src"]])))


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    if got.shape != want.shape:
        return {"wrong_dists": int(want.shape[0])}
    got = np.asarray(got, np.float32)
    return {"wrong_dists": int(np.count_nonzero(got != want))}


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Float32 values rounded to the nearest bfloat16 (ties to even), kept
    in float32."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                       & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


def control(ref: Reference, root) -> np.ndarray:
    g = ref.graph
    return bellman_ford(g.ptr, g.col, to_bfloat16(g.weight), g.n, int(root))
