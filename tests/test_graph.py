"""Graph substrate: structure invariants, generators, partitioner, sampler,
and the executor design-space equivalence property."""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import ALL_CONFIGS, STATIC_CONFIGS, SystemConfig, run
from repro.graph import (Graph, graph_stats, powerlaw_graph, random_graph,
                         regular_graph)
from repro.graph.partition import partition_edges_1d, partition_vertices
from repro.graph.sampler import NeighborSampler


class TestStructure:
    def test_orderings_same_edge_set(self, small_graph):
        g = small_graph
        a = set(zip(np.asarray(g.src).tolist(), np.asarray(g.dst).tolist()))
        b = set(zip(np.asarray(g.src_in).tolist(),
                    np.asarray(g.dst_in).tolist()))
        assert a == b and len(a) == g.n_edges

    def test_row_ptrs(self, small_graph):
        g = small_graph
        assert g.row_ptr_out[-1] == g.n_edges
        assert g.row_ptr_in[-1] == g.n_edges
        np.testing.assert_array_equal(
            np.diff(np.asarray(g.row_ptr_out)), np.asarray(g.out_degree))

    def test_owned_order_binned(self, small_graph):
        g = small_graph
        d = np.asarray(g.dst)[np.asarray(g.perm_owned)]
        blocks = d // g.block_size
        assert np.all(np.diff(blocks) >= 0)          # block-sorted
        bp = np.asarray(g.block_ptr)
        assert bp[-1] == g.n_edges

    def test_no_self_loops_no_dupes(self, small_graph):
        g = small_graph
        s, d = np.asarray(g.src), np.asarray(g.dst)
        assert not np.any(s == d)
        assert len(set(zip(s.tolist(), d.tolist()))) == g.n_edges

    def test_symmetric(self, small_graph):
        g = small_graph
        pairs = set(zip(np.asarray(g.src).tolist(),
                        np.asarray(g.dst).tolist()))
        assert all((b, a) in pairs for a, b in pairs)


def _paper_graph_digest(hash_seed: str) -> str:
    code = ("import hashlib, numpy as np\n"
            "from repro.graph import paper_graph\n"
            "g = paper_graph('RAJ', scale=16, weighted=True)\n"
            "h = hashlib.sha256()\n"
            "for a in (g.src, g.dst, g.weight): h.update(np.asarray(a))\n"
            "print(h.hexdigest())\n")
    src = str(Path(__file__).resolve().parent.parent / "src")
    env = {**os.environ, "PYTHONHASHSEED": hash_seed, "PYTHONPATH": src,
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    return out.stdout.strip()


def test_paper_graph_same_edges_in_every_process():
    """The Table II stand-ins are seeded from their names without
    Python's per-process string-hash salt."""
    assert _paper_graph_digest("1") == _paper_graph_digest("2")


class TestPartition:
    def test_edges_1d_covers_all(self, small_graph):
        g = small_graph
        part = partition_edges_1d(g, 8)
        real = part.dst < g.n_nodes
        assert real.sum() == g.n_edges
        pairs = set(zip(part.src[real].tolist(), part.dst[real].tolist()))
        orig = set(zip(np.asarray(g.src).tolist(),
                       np.asarray(g.dst).tolist()))
        assert pairs == orig

    def test_vertex_partition_owner(self, small_graph):
        g = small_graph
        part = partition_vertices(g, 4)
        per = part.vertex_offsets
        for d in range(4):
            real = part.dst[d] < g.n_nodes
            t = part.dst[d][real]
            assert np.all((t >= per[d]) & (t < per[d + 1]) | (t >= per[-1]))


class TestSampler:
    def test_sampled_edges_exist(self, small_graph):
        g = small_graph
        s = NeighborSampler(g, fanouts=(4, 3), seed=0)
        seeds = np.arange(16)
        blocks = s.sample(seeds)
        assert len(blocks) == 2
        edges = set(zip(np.asarray(g.src_in).tolist(),
                        np.asarray(g.dst_in).tolist()))
        blk = blocks[0]
        for src, dl, ok in zip(blk.src_global, blk.dst_local,
                               blk.edge_mask):
            if ok:
                assert (int(src), int(blk.seeds[dl])) in edges

    def test_fanout_shapes(self, small_graph):
        s = NeighborSampler(small_graph, fanouts=(5,), seed=1)
        blk = s.sample_hop(np.arange(10), 5)
        assert blk.src_global.shape == (50,)
        assert blk.dst_local.shape == (50,)


class TestExecutorEquivalence:
    """Paper invariant made executable: the 12 configs are semantically
    identical — only performance differs (hypothesis property)."""

    @given(st.integers(0, 10000))
    @settings(max_examples=5, deadline=None)
    def test_pagerank_config_equivalence(self, seed):
        from repro.algorithms import pagerank
        g = random_graph(100, 600, seed=seed, block_size=32)
        ref = None
        for cfg in STATIC_CONFIGS[::3]:
            out = np.asarray(
                run(pagerank(), g, cfg, max_iters=10).state["rank"])
            if ref is None:
                ref = out
            else:
                np.testing.assert_allclose(out, ref, atol=1e-5)

    @given(st.integers(2, 64), st.integers(0, 100))
    @settings(max_examples=5, deadline=None)
    def test_chunking_invariance(self, n_chunks, seed):
        """DRFrlx partial-reduction reordering never changes the result —
        the commutative-monoid legality argument (DESIGN.md §2)."""
        from repro.algorithms import sssp
        g = random_graph(80, 500, seed=seed, weighted=True, block_size=32)
        base = np.asarray(run(
            sssp(), g, SystemConfig.from_name("SG0")).state["dist"])
        chunked = np.asarray(run(
            sssp(), g, SystemConfig.from_name("SGR", n_chunks=n_chunks))
            .state["dist"])
        mask = np.isfinite(base)
        np.testing.assert_allclose(chunked[mask], base[mask], atol=1e-4)


def test_graph_stats(small_graph):
    st_ = graph_stats(small_graph)
    assert st_.n_nodes == small_graph.n_nodes
    assert st_.avg_degree == pytest.approx(
        small_graph.n_edges / small_graph.n_nodes)
