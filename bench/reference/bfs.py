"""Plain BFS: level-synchronous depths over the benchmark's own edge list.

- ``solve``: depth of every vertex from ``root`` (-1 where unreached),
  over the undirected graph the configuration states.
- ``work``: Graph500 TEPS counting: the input edge tuples within the
  root's component, duplicates and self-loops included.
- ``compare``: how many vertices' depths differ.  BFS levels are exact,
  so the limit is 0.
- ``control``: the same search over the tuples as directed edges, which
  breaks the stated guarantee that the graph is undirected.
"""
from __future__ import annotations

import numpy as np

from bench.reference.edges import UndirectedGraph

WORK_RULE = "component_input_edges"
LIMITS = {"wrong_depths": 0}


class Reference:
    def __init__(self, edges: dict):
        self.edges = edges
        self.graph = UndirectedGraph(edges["n"], edges["src"], edges["dst"])


def prepare(edges: dict) -> Reference:
    return Reference(edges)


def levels(ptr, col, n: int, root: int) -> np.ndarray:
    """Depths from ``root`` in the CSR ``(ptr, col)``, -1 where unreached."""
    depth = np.full(n, -1, np.int32)
    depth[root] = 0
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size:
        starts = ptr[frontier]
        counts = ptr[frontier + 1] - starts
        first = np.cumsum(counts) - counts
        idx = np.repeat(starts - first, counts) + np.arange(counts.sum())
        nbr = np.unique(col[idx])
        frontier = nbr[depth[nbr] == -1]
        level += 1
        depth[frontier] = level
    return depth


def solve(ref: Reference, root) -> np.ndarray:
    g = ref.graph
    return levels(g.ptr, g.col, g.n, int(root))


def work(ref: Reference, root, want: np.ndarray) -> int:
    return int(np.count_nonzero(want[ref.edges["src"]] >= 0))


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    if got.shape != want.shape:
        return {"wrong_depths": int(want.shape[0])}
    return {"wrong_depths": int(np.count_nonzero(got != want))}


def control(ref: Reference, root) -> np.ndarray:
    n = ref.edges["n"]
    src, dst = ref.edges["src"], ref.edges["dst"]
    keep = src != dst
    key = np.unique(src[keep] * n + dst[keep])
    ptr = np.searchsorted(key // n, np.arange(n + 1))
    return levels(ptr, key % n, n, int(root))
