"""Degree-matched stand-in for a published input graph.

A Zipf degree sequence (exponent ``alpha``) that holds exactly
``n_edges`` out-edges with every degree in ``[1, max_degree]``: the
sequence is scaled until its capped and floored sum is ``n_edges``, and
the rounding is settled by largest remainder.  It stays in id order, the
shape of a crawl-ordered input (neighbouring ids have near-equal degree).
It is wired by a configuration model in which each edge lands inside its
source's block of ``block_size`` ids with probability ``locality`` and
anywhere otherwise.  Only the targets are random, so every seed gives the
same degree sequence and a different wiring.  The edges are directed and
unweighted; ingest symmetrises them and removes self-loops and
duplicates.
"""
from __future__ import annotations

import numpy as np


def degrees(n: int, n_edges: int, alpha: float, max_degree: int):
    """Out-degrees by rank, summing to ``n_edges``, each in
    ``[1, max_degree]``."""
    if not n <= n_edges <= n * max_degree:
        raise ValueError(f"{n_edges} edges cannot give {n} vertices "
                         f"degrees in [1, {max_degree}]")
    share = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    lo, hi = 0.0, n_edges / share[-1]
    for _ in range(100):  # bisect the scale whose clipped sum is n_edges
        mid = (lo + hi) / 2
        if np.clip(mid * share, 1, max_degree).sum() < n_edges:
            lo = mid
        else:
            hi = mid
    real = np.clip(hi * share, 1, max_degree)
    deg = np.floor(real).astype(np.int64)
    short = n_edges - int(deg.sum())
    # floor(x) + 1 <= max_degree wherever x has a remainder
    deg[np.argsort(deg - real, kind="stable")[:short]] += 1
    return deg


def generate(params: dict, seed: int) -> dict:
    """``{"n", "src", "dst", "weight"}`` for ``params`` (``n``,
    ``n_edges``, ``alpha``, ``max_degree``, ``locality``,
    ``block_size``), the same for the same seed."""
    n = int(params["n"])
    block = int(params["block_size"])
    rng = np.random.default_rng([seed, 0])
    deg = degrees(n, int(params["n_edges"]), float(params["alpha"]),
                  int(params["max_degree"]))
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    e = src.shape[0]
    local = rng.random(e) < float(params["locality"])
    lo = src // block * block
    hi = np.minimum(lo + block, n)
    t_local = lo + rng.integers(0, block, size=e) % np.maximum(hi - lo, 1)
    t_remote = rng.integers(0, n, size=e)
    dst = np.where(local, t_local, t_remote)
    return {"n": n, "src": src, "dst": dst,
            "weight": np.ones(e, np.float32)}
