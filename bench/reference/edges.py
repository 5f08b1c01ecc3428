"""The benchmark's own view of a generated edge list, for the plain
references: undirected, without self-loops or duplicate pairs.

Built from the generator's tuples with numpy alone; nothing here reads
the ingest or the arrays of the system under test.
"""
from __future__ import annotations

import numpy as np


class UndirectedGraph:
    """CSR adjacency of the symmetric closure of ``(src, dst)``:
    ``col[ptr[v]:ptr[v + 1]]`` are the distinct neighbours of ``v``,
    sorted.  Weights are not kept: no reference here reads them."""

    def __init__(self, n: int, src, dst):
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        keep = src != dst
        key = np.unique(np.concatenate([src[keep] * n + dst[keep],
                                        dst[keep] * n + src[keep]]))
        self.n = int(n)
        self.row = key // n
        self.col = key % n
        self.ptr = np.searchsorted(self.row, np.arange(self.n + 1))
        self.degree = np.diff(self.ptr)

    @property
    def n_undirected_edges(self) -> int:
        return self.col.shape[0] // 2
