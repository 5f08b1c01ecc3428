"""The SSSP cell's pieces at tiny sizes: the float32 reference against the
program (bit for bit) and the repository's oracle, its work count and
control, the driver that carries the program's counters, and the
activations metric."""
import dataclasses

import numpy as np
import pytest

from bench import run

SEED = 2**31 + 17
CELL = "g500-s18.sssp.dd1-pallas"


@pytest.fixture(scope="module")
def g500():
    doc = run.load_json("configs", "graph500-s18-sssp")
    gen = run.load_module("generators", doc["generator"])
    return gen.generate({**doc["params"], "scale": 10}, SEED)


@pytest.fixture(scope="module")
def sssp_ref():
    return run.load_module("reference", "sssp")


@pytest.fixture(scope="module")
def ref(g500, sssp_ref):
    return sssp_ref.prepare(g500)


@pytest.fixture(scope="module")
def graph(g500):
    from repro.graph import Graph
    return Graph.from_coo(g500["src"], g500["dst"], g500["n"],
                          weight=g500["weight"], symmetrize=True,
                          remove_self_loops=True, block_size=256)


def _roots(ref, k=3):
    return [int(r) for r in np.flatnonzero(ref.graph.ptr[1:]
                                           > ref.graph.ptr[:-1])[:k]]


@pytest.mark.parametrize("config,use_pallas", [("DD1", True), ("DD1", False),
                                               ("TG0", False),
                                               ("SGR", False)])
def test_reference_equals_the_program_bitwise(ref, sssp_ref, graph, config,
                                              use_pallas):
    from repro.algorithms import sssp
    from repro.core import SystemConfig, run as run_program
    for root in _roots(ref):
        want = sssp_ref.solve(ref, root)
        program = sssp(source=root)
        res = run_program(program, graph, SystemConfig.from_name(config),
                          use_pallas=use_pallas)
        got = np.asarray(res.extract(program))
        assert res.converged
        assert got.dtype == want.dtype == np.float32
        np.testing.assert_array_equal(got, want)
        assert sssp_ref.compare(got, want) == {"wrong_dists": 0}


def test_reference_agrees_with_the_repository_oracle(ref, sssp_ref, graph):
    from repro.algorithms.reference import sssp_np
    for root in _roots(ref, 5):
        want = sssp_ref.solve(ref, root)
        oracle = sssp_np(graph, root)
        assert np.array_equal(np.isfinite(want), np.isfinite(oracle))
        reached = np.isfinite(want)
        np.testing.assert_allclose(want[reached], oracle[reached],
                                   rtol=1e-6)


def test_work_is_the_bfs_count(g500, ref, sssp_ref):
    bfs_ref = run.load_module("reference", "bfs")
    bref = bfs_ref.prepare(g500)
    for root in _roots(ref):
        want = sssp_ref.solve(ref, root)
        depths = bfs_ref.solve(bref, root)
        assert np.array_equal(np.isfinite(want), depths >= 0)
        assert sssp_ref.work(ref, root, want) == \
            bfs_ref.work(bref, root, depths) > 0


def test_control_in_bfloat16_breaks_the_limit(ref, sssp_ref):
    for root in _roots(ref):
        readings = sssp_ref.compare(sssp_ref.control(ref, root),
                                    sssp_ref.solve(ref, root))
        assert readings["wrong_dists"] > sssp_ref.LIMITS["wrong_dists"]


def test_compare_counts_differing_vertices(sssp_ref):
    want = np.asarray([0.0, 0.5, np.inf], np.float32)
    assert sssp_ref.compare(want.copy(), want) == {"wrong_dists": 0}
    got = want.copy()
    got[1] = np.nextafter(np.float32(0.5), np.float32(1.0))
    assert sssp_ref.compare(got, want) == {"wrong_dists": 1}
    assert sssp_ref.compare(want[:2], want) == {"wrong_dists": 3}


def test_reference_keeps_the_lightest_duplicate(sssp_ref):
    edges = {"n": 3, "src": np.asarray([0, 1, 1, 2, 2]),
             "dst": np.asarray([1, 0, 2, 2, 1]),
             "weight": np.asarray([0.75, 0.25, 0.5, 0.0, 0.625],
                                  np.float32)}
    ref = sssp_ref.prepare(edges)
    np.testing.assert_array_equal(sssp_ref.solve(ref, 0),
                                  np.asarray([0.0, 0.25, 0.75], np.float32))
    assert sssp_ref.work(ref, 0, sssp_ref.solve(ref, 0)) == 5


def _query(answer, counters):
    from bench.drivers.closed_loop_counters import CountedQuery
    return CountedQuery(0, np.asarray(answer, np.float32), 3, True, None,
                        counters)


def test_activations_per_vertex_reads_the_counters():
    reader = run.load_module("metrics", "activations_per_vertex")
    inf = np.inf
    qs = [_query([0.0, 1.0, inf], {"activations": 5}),
          _query([0.0, 2.0, 3.0, inf], {"activations": 4})]
    assert reader.read(run.Window(qs, 1.0, 0)) == pytest.approx(9 / 5)
    bare = [run.Query(0, np.zeros(3, np.float32), 3, True, None),
            _query([0.0, 1.0], None)]
    assert reader.read(run.Window(bare, 1.0, 0)) is None
    assert reader.read(run.Window([], 1.0, 0)) is None


def test_driver_carries_counters_and_tolerates_their_absence(tiny_bench,
                                                             monkeypatch):
    cell = tiny_bench.load_cell(CELL)
    edges, graph, config = tiny_bench.build(cell, {})
    root = next(cell.queries.draw(edges, SEED))
    q = cell.driver.answer(cell.workload, graph, config, root)
    assert q.counters["activations"] >= np.isfinite(q.answer).sum() > 1

    import repro.core as core
    real = core.run

    class WithoutCounters:
        """A result as a system without ``RunResult.counters`` gives."""

        def __init__(self, res):
            self.res = res

        def __getattr__(self, name):
            if name == "counters":
                raise AttributeError(name)
            return getattr(self.res, name)
    monkeypatch.setattr(core, "run",
                        lambda *a, **kw: WithoutCounters(real(*a, **kw)))
    q = cell.driver.answer(cell.workload, graph, config, root)
    assert q.counters is None and q.converged


def test_traced_run_reports_activations(run_cell):
    rc, line = run_cell(CELL, trace=1)
    assert rc == 0 and line["correct"] is True
    assert line["checks"]["wrong_dists"] == {"value": 0, "limit": 0}
    assert line["metrics"]["activations_per_vertex"]["value"] > 1.0
    assert line["metrics"]["iters_per_query"]["value"] > 1.0


def test_broken_sssp_is_not_correct(run_cell, monkeypatch):
    import jax.numpy as jnp

    import repro.algorithms as algorithms
    real = algorithms.sssp

    def slightly_off(**kw):
        prog = real(**kw)

        def extract(st):
            d = prog.extract(st)
            return jnp.where(jnp.isfinite(d), d * (1 + 2**-20) + 2**-30, d)
        return dataclasses.replace(prog, extract=extract)
    monkeypatch.setattr(algorithms, "sssp", slightly_off)
    rc, line = run_cell(CELL, seconds=0.1)
    assert rc == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
