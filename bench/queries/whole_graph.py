"""The one query of a whole-graph program (PageRank), repeated."""
from __future__ import annotations

import itertools


def draw(edges: dict, seed: int):
    return itertools.repeat(None)
