"""The paper's six graph inputs (Table II), recreated synthetically.

Published statistics (Table II of the paper):

| Graph | Vertices | Edges   | MaxDeg | AvgDeg | Volume(KB) | Reuse     | Imbal.   |
|-------|----------|---------|--------|--------|------------|-----------|----------|
| AMZ   | 410236   | 6713648 | 2770   | 16.265 | 1855 (H)   | 0.160 (M) | 0.00 (L) |
| DCT   | 52652    | 178076  | 38     | 3.382  | 60 (M)     | 0.359 (M) | 0.08 (M) |
| EML   | 265214   | 837912  | 7636   | 3.159  | 287 (H)    | 0.053 (L) | 1.00 (H) |
| OLS   | 88263    | 683186  | 10     | 7.740  | 201 (M)    | 0.445 (H) | 0.00 (L) |
| RAJ   | 20640    | 163178  | 3469   | 7.906  | 48 (L)     | 0.594 (H) | 0.62 (H) |
| WNG   | 61032    | 243088  | 4      | 3.919  | 79 (M)     | ~0.005(L) | 0.00 (L) |

(Note: Table II prints WNG's Reuse as "0.594" but classifies it L; Eq. 6
with AN_L=0.020, AN_R=3.899, avg-deg 3.919 gives 0.0051 -> the printed value
is a typesetting duplication of RAJ's; we reproduce the class, L.)

``paper_graph(name)`` materialises a synthetic graph whose generator knobs
were tuned so the taxonomy classification (H/M/L for Volume/Reuse/Imbalance)
matches Table II.  ``paper_graph(name, scale=k)`` divides vertex/edge counts
by ``k`` for CPU-friendly benchmarks while preserving Reuse/Imbalance classes
(Volume is recomputed from the true reduced size, so benchmark tables always
report the classification actually measured).

``PAPER_STATS`` carries the published numbers for metric-faithfulness tests
that must be independent of synthesis (Volume classification is a pure
function of |V|, |E|).

Real inputs: ``dataset_graph(name)`` loads the actual SuiteSparse /
SNAP edge list when a local copy exists under ``$REPRO_DATA_DIR`` (or
``./data``) and otherwise falls back to the synthetic stand-in with a
matched degree signature — downloads are never attempted at import or
benchmark time.  ``fetch_instructions()`` prints the exact URLs and
shell commands to place the real files; ``degree_profile(graph)``
reports which profile class (near-regular / road-like, social
power-law, web-crawl hub-heavy) a loaded graph actually lands in so
the stand-in <-> real swap is auditable.
"""
from __future__ import annotations

import os
import zlib
from functools import lru_cache
from pathlib import Path

import numpy as np

from repro.graph.generators import powerlaw_graph, regular_graph
from repro.graph.structure import Graph

__all__ = ["PAPER_GRAPHS", "PAPER_STATS", "PAPER_SOURCES",
           "DEGREE_PROFILES", "paper_graph", "dataset_graph",
           "load_real_graph", "real_graph_path", "degree_profile",
           "fetch_instructions"]

PAPER_GRAPHS = ("AMZ", "DCT", "EML", "OLS", "RAJ", "WNG")

# name -> (vertices, edges, max_deg, avg_deg, volume_kb, reuse, imbalance,
#          vol_class, reuse_class, imb_class) from Table II.
PAPER_STATS = {
    "AMZ": (410236, 6713648, 2770, 16.265, 1855.178, 0.160, 0.000, "H", "M", "L"),
    "DCT": (52652, 178076, 38, 3.382, 60.078, 0.359, 0.083, "M", "M", "M"),
    "EML": (265214, 837912, 7636, 3.159, 287.272, 0.053, 1.000, "H", "L", "H"),
    "OLS": (88263, 683186, 10, 7.740, 200.898, 0.445, 0.000, "M", "H", "L"),
    "RAJ": (20640, 163178, 3469, 7.906, 47.869, 0.594, 0.617, "L", "H", "H"),
    "WNG": (61032, 243088, 4, 3.919, 79.458, 0.0051, 0.000, "M", "L", "L"),
}

# Published AN_L / AN_R (Table II) for Reuse-metric regression tests.
PAPER_AN = {
    "AMZ": (2.616, 13.749),
    "DCT": (1.215, 2.167),
    "EML": (0.167, 2.992),
    "OLS": (3.446, 4.295),
    "RAJ": (4.697, 3.209),
    "WNG": (0.020, 3.899),
}


# name -> (degree-profile class, upstream dataset, fetch URL).  The
# profile classes are the ISSUE's taxonomy: how the degree distribution
# shapes push/pull and tiling behavior, independent of raw size.
#   near-regular : tight degree band, no hubs (road-network-like)
#   social       : power-law tail, moderate hubs
#   web-crawl    : heavy power-law, extreme hubs dominate edge mass
PAPER_SOURCES = {
    "AMZ": ("social", "SNAP com-Amazon (co-purchase)",
            "https://snap.stanford.edu/data/bigdata/communities/com-amazon.ungraph.txt.gz"),
    "DCT": ("near-regular", "SuiteSparse Pajek/dictionary28",
            "https://suitesparse-collection-website.herokuapp.com/MM/Pajek/dictionary28.tar.gz"),
    "EML": ("web-crawl", "SNAP email-EuAll",
            "https://snap.stanford.edu/data/email-EuAll.txt.gz"),
    "OLS": ("near-regular", "SuiteSparse olesnik0",
            "https://suitesparse-collection-website.herokuapp.com/MM/GHS_indef/olesnik0.tar.gz"),
    "RAJ": ("social", "SuiteSparse raj1 (circuit)",
            "https://suitesparse-collection-website.herokuapp.com/MM/Rajat/rajat01.tar.gz"),
    "WNG": ("near-regular", "SuiteSparse wing (FE mesh)",
            "https://suitesparse-collection-website.herokuapp.com/MM/DIMACS10/wing.tar.gz"),
}

# profile class -> the degree-feature bands a member should land in
# (checked against ``kernels.autotune.degree_features``; ``degree_skew``
# is the coefficient of variation of out-degree).
DEGREE_PROFILES = {
    "near-regular": {"degree_skew": (0.0, 0.6)},
    "social": {"degree_skew": (0.6, 3.0)},
    "web-crawl": {"degree_skew": (3.0, float("inf"))},
}


@lru_cache(maxsize=None)
def paper_graph(name: str, scale: int = 1, weighted: bool = False,
                block_size: int = 256) -> Graph:
    """Synthetic recreation of a Table II input (optionally scaled down)."""
    if name not in PAPER_STATS:
        raise KeyError(f"unknown paper graph {name!r}; one of {PAPER_GRAPHS}")
    v, e, max_deg, avg_deg = PAPER_STATS[name][:4]
    n = max(4 * block_size, v // scale)
    ne = max(n * 2, e // scale)
    # crc32, not hash(): str hashes are salted per process
    seed = zlib.crc32(name.encode()) % (2**31)
    if name == "AMZ":      # skewed but degree-ordered ids -> warp maxes
        # homogeneous within each tile -> Imbalance L (like the real input)
        return powerlaw_graph(n, ne // 2, alpha=1.2, max_degree=max_deg,
                              locality=0.21, degree_order="sorted", seed=seed,
                              weighted=weighted, block_size=block_size)
    if name == "DCT":      # light skew, moderate locality, mild imbalance
        return powerlaw_graph(n, ne // 2, alpha=0.7, max_degree=max_deg,
                              locality=0.31, hub_fraction=0.12, seed=seed,
                              weighted=weighted, block_size=block_size)
    if name == "EML":      # heavy power law, low locality, hubs everywhere
        return powerlaw_graph(n, ne // 2, alpha=1.6, max_degree=max_deg,
                              locality=0.05, hub_fraction=1.0, seed=seed,
                              weighted=weighted, block_size=block_size)
    if name == "OLS":      # near-regular, high locality
        return regular_graph(n, degree=max(2, int(avg_deg / 2)), locality=0.56,
                             seed=seed, weighted=weighted,
                             block_size=block_size)
    if name == "RAJ":      # small, skewed, high locality
        return powerlaw_graph(n, ne // 2, alpha=1.1, max_degree=max_deg,
                              locality=0.62, hub_fraction=0.7, seed=seed,
                              weighted=weighted, block_size=block_size)
    # WNG: degree ~4, almost perfectly regular, no locality
    return regular_graph(n, degree=2, locality=0.005, seed=seed,
                         weighted=weighted, block_size=block_size)


# ---------------------------------------------------------------------------
# real inputs: local edge lists with synthetic fallback
# ---------------------------------------------------------------------------
def _data_dir() -> Path:
    return Path(os.environ.get("REPRO_DATA_DIR", "data"))


def real_graph_path(name: str) -> Path | None:
    """Path of a locally fetched edge list for ``name``, or None.

    Accepted layouts under ``$REPRO_DATA_DIR`` (default ``./data``):
    ``<NAME>.txt``/``<NAME>.edges`` (whitespace ``src dst [weight]``
    rows, ``#``/``%`` comments) or ``<NAME>.mtx`` (MatrixMarket
    coordinate, 1-based).  Gzip variants (``.gz``) are accepted too.
    """
    base = _data_dir()
    for ext in (".txt", ".edges", ".mtx", ".txt.gz", ".edges.gz",
                ".mtx.gz"):
        p = base / f"{name}{ext}"
        if p.is_file():
            return p
    return None


def load_real_graph(path, weighted: bool = False,
                    block_size: int = 256) -> Graph:
    """Parse a local edge-list / MatrixMarket file into a :class:`Graph`.

    The paper's universal input format is symmetric, so edges are
    symmetrized; self loops and duplicates are dropped by
    ``Graph.from_coo``.  Vertex ids are compacted to ``0..V-1``.
    """
    path = Path(path)
    opener = __import__("gzip").open if path.suffix == ".gz" else open
    is_mtx = ".mtx" in path.suffixes or path.suffix == ".mtx"
    rows = []
    with opener(path, "rt") as fh:
        header_skipped = False
        for line in fh:
            line = line.strip()
            if not line or line[0] in "#%":
                continue
            if is_mtx and not header_skipped:
                header_skipped = True  # dimensions line
                continue
            parts = line.split()
            s, d = int(float(parts[0])), int(float(parts[1]))
            w = float(parts[2]) if weighted and len(parts) > 2 else 1.0
            rows.append((s, d, w))
    if not rows:
        raise ValueError(f"no edges parsed from {path}")
    arr = np.asarray(rows, np.float64)
    src, dst = arr[:, 0].astype(np.int64), arr[:, 1].astype(np.int64)
    if is_mtx:  # MatrixMarket is 1-based
        src, dst = src - 1, dst - 1
    # compact ids (SNAP lists are sparse in id space)
    ids, inv = np.unique(np.concatenate([src, dst]), return_inverse=True)
    src, dst = inv[:src.size], inv[src.size:]
    weight = arr[:, 2].astype(np.float32) if weighted else None
    return Graph.from_coo(src, dst, n_nodes=int(ids.size), weight=weight,
                          block_size=block_size, symmetrize=True)


def dataset_graph(name: str, scale: int = 1, weighted: bool = False,
                  block_size: int = 256, prefer_real: bool = True):
    """A Table II input: the real graph when fetched locally, else the
    synthetic stand-in.  Returns ``(graph, source)`` where ``source``
    is ``"real"`` or ``"synthetic"`` — benchmark tables record it so a
    run against stand-ins is never mistaken for one against the real
    inputs.  ``scale`` only applies to the synthetic path (the real
    file is whatever was fetched)."""
    if prefer_real:
        p = real_graph_path(name)
        if p is not None:
            return (load_real_graph(p, weighted=weighted,
                                    block_size=block_size), "real")
    return (paper_graph(name, scale=scale, weighted=weighted,
                        block_size=block_size), "synthetic")


def degree_profile(graph) -> dict:
    """Classify a graph into the :data:`DEGREE_PROFILES` taxonomy.

    Returns the ``kernels.autotune.degree_features`` dict extended with
    ``profile`` (the matched class) and ``signature`` (the quantized
    cache key) — the audit trail that a synthetic stand-in actually
    matches its real input's degree shape.
    """
    from repro.kernels.autotune import degree_features, degree_signature
    feats = degree_features(graph)
    skew = feats["degree_skew"]
    profile = next((cls for cls, bands in DEGREE_PROFILES.items()
                    if bands["degree_skew"][0] <= skew
                    < bands["degree_skew"][1]), "near-regular")
    return {**feats, "profile": profile,
            "signature": degree_signature(feats)}


def fetch_instructions(name: str | None = None) -> str:
    """Shell commands that place the real inputs where
    :func:`dataset_graph` finds them.  Never executed by this package —
    the container has no network; run them yourself where you do."""
    names = [name] if name else list(PAPER_GRAPHS)
    lines = [f"mkdir -p {_data_dir()}"]
    for n in names:
        profile, source, url = PAPER_SOURCES[n]
        lines.append(f"# {n}: {source} ({profile})")
        tgt = f"{_data_dir()}/{n}.txt.gz"
        if url.endswith(".tar.gz"):
            lines.append(f"curl -L {url} | tar -xzO '*.mtx' "
                         f"| gzip > {_data_dir()}/{n}.mtx.gz")
        else:
            lines.append(f"curl -L -o {tgt} {url}")
    return "\n".join(lines)
