"""Graph500 Kronecker (R-MAT) edge list, as the Graph500 specification
generates it.

For each of ``2**scale * edge_factor`` edge tuples and each of ``scale``
bit levels, one quadrant of the adjacency matrix is drawn with
probabilities A, B, C and D = 1 - A - B - C (the reference code's two
draws per level).  Vertex labels are then permuted and the edge list
shuffled, both from the seed, so hubs do not sit at low ids.  Weights are
uniform in [0, 1).  The tuples are directed and may hold self-loops and
duplicates: the kernels treat the list as undirected, and ingest removes
both.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int) -> dict:
    """``{"n", "src", "dst", "weight"}`` for ``params`` (``scale``,
    ``edge_factor``, ``a``, ``b``, ``c``), the same for the same seed."""
    scale = int(params["scale"])
    a, b, c = float(params["a"]), float(params["b"]), float(params["c"])
    n = 1 << scale
    m = n * int(params["edge_factor"])
    rng = np.random.default_rng([seed, 0])
    ab = a + b
    c_norm = np.float32(c / (1.0 - ab))
    a_norm = np.float32(a / ab)
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        src_bit = rng.random(m, dtype=np.float32) > ab
        dst_bit = (rng.random(m, dtype=np.float32)
                   > np.where(src_bit, c_norm, a_norm))
        src += src_bit.astype(np.int64) << level
        dst += dst_bit.astype(np.int64) << level
    label = rng.permutation(n)
    order = rng.permutation(m)
    src, dst = label[src[order]], label[dst[order]]
    weight = rng.random(m, dtype=np.float32)
    return {"n": n, "src": src, "dst": dst, "weight": weight}
