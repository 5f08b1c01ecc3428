"""All six applications vs. numpy oracles, across the config design space."""
import jax
import numpy as np
import pytest

from repro.algorithms import bc, cc, coloring, mis, pagerank, sssp
from repro.algorithms.reference import (bc_np, cc_np,
                                        is_maximal_independent_set,
                                        is_proper_coloring, pagerank_np,
                                        sssp_np)
from repro.core import STATIC_CONFIGS, SystemConfig, run

# a representative spread of the design space (full grid in benchmarks);
# since the ISSUE-6 port every app also runs the dynamic cells
CONFIGS = ["TG0", "SG0", "SG1", "SGR", "SD1", "SDR", "DG1", "DD1"]


class TestPageRank:
    @pytest.mark.parametrize("cfg", CONFIGS)
    def test_matches_oracle(self, small_graph, cfg):
        r = run(pagerank(), small_graph, SystemConfig.from_name(cfg))
        got = np.asarray(r.extract(pagerank()))
        assert np.abs(got - pagerank_np(small_graph)).max() < 1e-4
        assert r.converged

    def test_all_12_static_configs_agree(self, tiny_graph):
        outs = [np.asarray(run(pagerank(), tiny_graph, c).state["rank"])
                for c in STATIC_CONFIGS]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], atol=1e-5)

    def test_rank_sums_to_one(self, small_graph):
        r = run(pagerank(), small_graph, SystemConfig.from_name("SGR"))
        assert float(np.asarray(r.state["rank"]).sum()) == pytest.approx(
            1.0, abs=1e-3)


class TestSSSP:
    @pytest.mark.parametrize("cfg", CONFIGS)
    def test_matches_oracle(self, small_graph, cfg):
        r = run(sssp(), small_graph, SystemConfig.from_name(cfg))
        got = np.asarray(r.state["dist"])
        ref = sssp_np(small_graph)
        mask = np.isfinite(ref)
        assert np.allclose(got[mask], ref[mask], atol=1e-4)
        assert np.array_equal(np.isfinite(got), mask)


class TestMIS:
    @pytest.mark.parametrize("cfg", ["TG0", "SGR", "SD1", "DD1"])
    def test_is_maximal_independent(self, small_graph, cfg):
        r = run(mis(), small_graph, SystemConfig.from_name(cfg),
                key=jax.random.key(5))
        member = np.asarray(r.extract(mis()))
        assert is_maximal_independent_set(small_graph, member)

    def test_deterministic_given_key(self, small_graph):
        a = run(mis(), small_graph, SystemConfig.from_name("SGR"),
                key=jax.random.key(1))
        b = run(mis(), small_graph, SystemConfig.from_name("SDR"),
                key=jax.random.key(1))
        np.testing.assert_array_equal(np.asarray(a.state["status"]),
                                      np.asarray(b.state["status"]))


class TestColoring:
    @pytest.mark.parametrize("cfg", ["TG0", "SGR", "SD1", "DD1"])
    def test_proper_coloring(self, small_graph, cfg):
        r = run(coloring(), small_graph, SystemConfig.from_name(cfg))
        color = np.asarray(r.extract(coloring()))
        assert is_proper_coloring(small_graph, color)


class TestBC:
    @pytest.mark.parametrize("cfg", ["TG0", "SGR", "SD1", "DD1"])
    def test_matches_brandes(self, small_graph, cfg):
        r = run(bc(), small_graph, SystemConfig.from_name(cfg))
        got = np.asarray(r.extract(bc()))
        ref = bc_np(small_graph)
        np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


class TestCC:
    @pytest.mark.parametrize("cfg", ["DG0", "DG1", "DGR", "DD0", "DD1",
                                     "DDR"])
    def test_matches_components(self, small_graph, cfg):
        r = run(cc(), small_graph, SystemConfig.from_name(cfg))
        np.testing.assert_array_equal(np.asarray(r.state["label"]),
                                      cc_np(small_graph))

    def test_disconnected(self):
        from repro.graph import regular_graph
        import numpy as np
        from repro.graph.structure import Graph
        # two disjoint cliques
        src = np.array([0, 1, 2, 0, 1, 2, 5, 6, 7, 5, 6, 7])
        dst = np.array([1, 2, 0, 2, 0, 1, 6, 7, 5, 7, 5, 6])
        g = Graph.from_coo(src, dst, 10, symmetrize=True, block_size=4)
        r = run(cc(), g, SystemConfig.from_name("DD1"))
        lab = np.asarray(r.state["label"])
        assert lab[0] == lab[1] == lab[2] == 0
        assert lab[5] == lab[6] == lab[7] == 5
        assert lab[3] == 3 and lab[4] == 4 and lab[8] == 8 and lab[9] == 9


class TestPallasPath:
    """use_pallas routes the owned configs through the blocked kernel."""

    @pytest.mark.parametrize("prog,oracle,key", [
        (pagerank, pagerank_np, "rank"), (sssp, sssp_np, "dist")])
    def test_owned_kernel_path(self, tiny_graph, prog, oracle, key):
        r = run(prog(), tiny_graph, SystemConfig.from_name("SDR"),
                use_pallas=True)
        got = np.asarray(r.state[key])
        ref = oracle(tiny_graph)
        mask = np.isfinite(ref)
        assert np.allclose(got[mask], ref[mask], atol=1e-4)


class TestVectorizedOracles:
    """The numpy oracles equal the per-edge loop forms they replaced."""

    @staticmethod
    def _loop_refs(g, source):
        v = g.n_nodes
        src, dst = np.asarray(g.src, np.int64), np.asarray(g.dst, np.int64)
        row_ptr = np.asarray(g.row_ptr_out, np.int64)
        depth = np.full(v, -1, np.int32)
        depth[source] = 0
        frontier = [source]
        while frontier:
            nxt = []
            for u in frontier:
                for e in range(row_ptr[u], row_ptr[u + 1]):
                    if depth[dst[e]] == -1:
                        depth[dst[e]] = depth[u] + 1
                        nxt.append(dst[e])
            frontier = nxt
        w = np.asarray(g.weight, np.float64)
        dist = np.full(v, np.inf)
        dist[source] = 0.0
        for _ in range(v):
            new = dist.copy()
            np.minimum.at(new, dst, dist[src] + w)
            if np.array_equal(new, dist):
                break
            dist = new
        label = np.arange(v)
        while True:
            new = label.copy()
            np.minimum.at(new, dst, label[src])
            np.minimum.at(new, src, label[dst])
            new = new[new]
            if np.array_equal(new, label):
                break
            label = new
        inv = 1.0 / np.maximum(np.asarray(g.out_degree, np.float64), 1)
        dangling = np.asarray(g.out_degree) == 0
        rank = np.full(v, 1.0 / v)
        for _ in range(256):
            contrib = np.zeros(v)
            np.add.at(contrib, dst, rank[src] * inv[src])
            new = (1 - 0.85) / v + 0.85 * (contrib + rank[dangling].sum() / v)
            done = np.abs(new - rank).sum() < 1e-6
            rank = new
            if done:
                break
        return {"bfs": depth, "sssp": dist.astype(np.float32),
                "cc": label.astype(np.int32),
                "pr": rank.astype(np.float32)}

    @pytest.mark.parametrize("graph", ["rmat", "regular"])
    def test_equal_to_loop_forms(self, graph):
        from repro.algorithms.reference import bfs_np
        from repro.graph import regular_graph, rmat_graph
        g = (rmat_graph(10, 8, seed=2, weighted=True) if graph == "rmat"
             else regular_graph(300, 2, locality=0.1, seed=4, weighted=True))
        refs = self._loop_refs(g, source=3)
        np.testing.assert_array_equal(bfs_np(g, 3), refs["bfs"])
        np.testing.assert_array_equal(sssp_np(g, 3), refs["sssp"])
        np.testing.assert_array_equal(cc_np(g), refs["cc"])
        np.testing.assert_array_equal(pagerank_np(g), refs["pr"])
