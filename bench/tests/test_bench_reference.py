"""The plain references against the repository's numpy oracles, at tiny
sizes, and their controls against the references."""
import numpy as np
import pytest

from bench import run

SEED = 2**31 + 11


def _edges(config, **params):
    doc = run.load_json("configs", config)
    gen = run.load_module("generators", doc["generator"])
    return gen.generate({**doc["params"], **params}, SEED)


def _graph(edges):
    from repro.graph import Graph
    return Graph.from_coo(edges["src"], edges["dst"], edges["n"],
                          weight=edges["weight"], symmetrize=True)


@pytest.fixture(scope="module")
def g500():
    return _edges("graph500-s18", scale=9)


def test_bfs_matches_the_repository_oracle(g500):
    from repro.algorithms.reference import bfs_np
    bfs = run.load_module("reference", "bfs")
    ref = bfs.prepare(g500)
    graph = _graph(g500)
    for root in np.flatnonzero(ref.graph.degree)[:5]:
        want = bfs.solve(ref, root)
        np.testing.assert_array_equal(want, bfs_np(graph, int(root)))
        assert bfs.compare(want, want) == {"wrong_depths": 0}


def test_bfs_work_counts_input_tuples_in_the_component(g500):
    bfs = run.load_module("reference", "bfs")
    ref = bfs.prepare(g500)
    root = int(np.argmax(ref.graph.degree))
    want = bfs.solve(ref, root)
    inside = want[g500["src"]] >= 0
    assert np.array_equal(inside, want[g500["dst"]] >= 0)  # undirected
    assert bfs.work(ref, root, want) == int(inside.sum())
    assert 0 < int(inside.sum()) <= g500["src"].shape[0]


def test_bfs_control_breaks_the_answer(g500):
    bfs = run.load_module("reference", "bfs")
    ref = bfs.prepare(g500)
    root = int(np.argmax(ref.graph.degree))
    got = bfs.control(ref, root)
    readings = bfs.compare(got, bfs.solve(ref, root))
    assert readings["wrong_depths"] > bfs.LIMITS["wrong_depths"]


def test_pagerank_matches_the_repository_oracle():
    from repro.algorithms.reference import pagerank_np
    pr = run.load_module("reference", "pagerank")
    edges = _edges("amz-table2", n=2048, n_edges=12000)
    ref = pr.prepare(edges)
    want = pr.solve(ref)
    oracle = pagerank_np(_graph(edges))
    assert np.abs(want - oracle).sum() < 1e-6
    assert pr.work(ref, None, want) * 2 == _graph(edges).n_edges
    assert pr.compare(oracle, want)["rank_l1"] <= pr.LIMITS["rank_l1"]


def test_pagerank_control_fails_the_limit():
    pr = run.load_module("reference", "pagerank")
    ref = pr.prepare(_edges("amz-table2", n=2048, n_edges=12000))
    readings = pr.compare(pr.control(ref), pr.solve(ref))
    assert readings["rank_l1"] > 3 * pr.LIMITS["rank_l1"]
