"""Graph500's search keys: roots uniform among vertices of degree >= 1
(self-loops not counted), without replacement until every such vertex has
been drawn, the same for the same seed."""
from __future__ import annotations

import itertools

import numpy as np


def draw(edges: dict, seed: int):
    src, dst, n = edges["src"], edges["dst"], edges["n"]
    keep = src != dst
    deg = (np.bincount(src[keep], minlength=n)
           + np.bincount(dst[keep], minlength=n))
    rng = np.random.default_rng([seed, 1])
    roots = rng.permutation(np.flatnonzero(deg > 0))
    return itertools.cycle(int(r) for r in roots)
