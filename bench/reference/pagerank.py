"""Plain PageRank: damped power iteration in float64 over the benchmark's
own edge list.

- ``solve``: ranks with damping 0.85, the dangling mass spread evenly,
  iterated from 1/V until an iterate moves less than 1e-6 in L1 (at most
  256 iterations) -- the query ``pagerank()`` states.
- ``work``: LDBC Graphalytics EPS counting: every undirected edge of the
  graph, for each whole-graph query.
- ``compare``: the L1 distance between the served ranks and the
  reference's.
- ``control``: the same iteration with ranks and per-edge contributions
  rounded to bfloat16 (sums in float32), the precision below the float32
  the program states.
"""
from __future__ import annotations

import numpy as np

from bench.reference.edges import UndirectedGraph

WORK_RULE = "undirected_edges"
DAMPING = 0.85
TOL = 1e-6
MAX_ITERS = 256
#: Set from chip readings: see PERF.md, "Limits".
LIMITS = {"rank_l1": 1e-4}


class Reference:
    def __init__(self, edges: dict):
        self.graph = UndirectedGraph(edges["n"], edges["src"], edges["dst"])


def prepare(edges: dict) -> Reference:
    return Reference(edges)


def _iterate(g: UndirectedGraph, rnd) -> np.ndarray:
    n = g.n
    inv = 1.0 / np.maximum(g.degree, 1)
    dangling = g.degree == 0
    rank = rnd(np.full(n, 1.0 / n))
    for _ in range(MAX_ITERS):
        msg = rnd((rank * inv)[g.row])
        contrib = np.bincount(g.col, weights=msg, minlength=n)
        new = rnd((1.0 - DAMPING) / n
                  + DAMPING * (contrib + rank[dangling].sum() / n))
        moved = np.abs(new - rank).sum()
        rank = new
        if moved < TOL:
            break
    return rank


def solve(ref: Reference, query=None) -> np.ndarray:
    return _iterate(ref.graph, lambda x: x)


def work(ref: Reference, query, want: np.ndarray) -> int:
    return ref.graph.n_undirected_edges


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return {"rank_l1": float("inf")}
    return {"rank_l1": float(np.abs(got.astype(np.float64) - want).sum())}


def _bfloat16(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), held in float64."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def control(ref: Reference, query=None) -> np.ndarray:
    return _iterate(ref.graph, _bfloat16)
