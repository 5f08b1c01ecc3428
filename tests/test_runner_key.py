"""Runner sharing through ``VertexProgram.runner_key``.

Programs that declare a ``runner_key`` share one compiled runner per
``(name, runner_key)`` and cell: every BFS root, every ``pagerank()``.
The declaration is checked against the traced runner itself (two
instances that differ only outside the key trace to the same jaxpr and
constants), the reuse against the plan cache's ``exec_fn`` counters and
JAX's backend-compile events, and the fixpoint certificate, which reads
``source``, stays per instance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import bc, bfs, cc, coloring, mis, pagerank, sssp
from repro.algorithms.reference import bfs_np, pagerank_np, sssp_np
from repro.core import STATS, EdgeContext, SystemConfig, executor, run
from repro.core.resilience import check_certificate
from repro.core.vertex_program import DENSE_OCC
from repro.graph import rmat_graph

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: (factory, kwargs of instance a, kwargs of instance b): the two differ
#: in every parameter outside the program's runner_key
SHARED = {
    "bfs": (bfs, {"source": 0}, {"source": 7}),
    "sssp": (sssp, {"source": 0}, {"source": 7}),
    "pagerank": (pagerank, {}, {}),
}
CELLS = [("DG1", False), ("DD1", False), ("DD1", True)]


def _graph(seed=1):
    return rmat_graph(7, 8, seed=seed, weighted=True, block_size=128)


@pytest.fixture(scope="module")
def graph():
    return _graph()


def _fused_jaxpr(program, graph, config, use_pallas):
    """The fused engine's runner for ``program`` on ``graph``, traced
    as ``run`` traces it."""
    ctx = EdgeContext.create(graph, SystemConfig.from_name(config),
                             use_pallas=use_pallas)
    state = jax.tree.map(jnp.asarray, program.init(graph))
    traced, occ_traced = executor._trace_flags(program, state)
    limit = program.max_iters
    db = jnp.zeros((limit,), bool) if traced else None
    ob = jnp.full((limit,), DENSE_OCC, jnp.float32) if occ_traced else None
    fused = executor._fused_loop(program, ctx, limit, traced, occ_traced)
    return jax.make_jaxpr(fused)(state, db, ob)


def _exec_fn():
    c = STATS.plan_cache()["by_kind"].get("exec_fn", {})
    return c.get("misses", 0), c.get("hits", 0)


class _Compiles:
    """Counts JAX's backend compiles while registered."""

    def __init__(self):
        self.n = 0

    def __call__(self, event, start, end, **kwargs):
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self)


def _roots(graph, n):
    deg = np.asarray(graph.out_degree)
    return [int(v) for v in np.flatnonzero(deg > 0)[:n]]


@pytest.mark.parametrize("config,use_pallas", CELLS)
@pytest.mark.parametrize("app", list(SHARED))
def test_declared_key_traces_one_runner(graph, app, config, use_pallas):
    """Two instances with the same name and runner_key trace to the same
    runner: the same jaxpr, over the same constants."""
    factory, kw_a, kw_b = SHARED[app]
    a, b = factory(**kw_a), factory(**kw_b)
    assert a.runner_key is not None
    assert (a.name, a.runner_key) == (b.name, b.runner_key)
    ja = _fused_jaxpr(a, graph, config, use_pallas)
    jb = _fused_jaxpr(b, graph, config, use_pallas)
    assert str(ja) == str(jb)
    assert len(ja.consts) == len(jb.consts)
    for x, y in zip(ja.consts, jb.consts):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("param,value", [("damping", 0.9), ("tol", 1e-5)])
def test_keyed_parameter_gets_its_own_runner(param, value):
    """A parameter inside the key changes the runner, and so the cache
    entry: pagerank(damping=0.85) and pagerank(damping=0.9) never share."""
    g = _graph(seed=2)
    a, b = pagerank(), pagerank(**{param: value})
    assert a.runner_key != b.runner_key
    assert (str(_fused_jaxpr(a, g, "DG1", False))
            != str(_fused_jaxpr(b, g, "DG1", False)))
    cfg = SystemConfig.from_name("DG1")
    m0, _ = _exec_fn()
    run(a, g, cfg)
    run(b, g, cfg)
    assert _exec_fn()[0] == m0 + 2


@pytest.mark.parametrize("factory", [bc, cc, coloring, mis])
def test_undeclared_programs_keep_a_runner_per_instance(factory):
    g = _graph(seed=3)
    cfg = SystemConfig.from_name("DG1")
    a, b = factory(), factory()
    assert a.runner_key is None
    m0, _ = _exec_fn()
    run(a, g, cfg, max_iters=2)
    run(b, g, cfg, max_iters=2)
    assert _exec_fn()[0] == m0 + 2


@pytest.mark.parametrize("config,use_pallas", [("DG1", False),
                                               ("DD1", True)])
def test_bfs_roots_reuse_one_runner(config, use_pallas):
    """Five roots, five programs: one build, then four cache hits and no
    backend compile; every depth equals the oracle's."""
    g = _graph(seed=4)
    cfg = SystemConfig.from_name(config)
    counts, compiles = [], []
    for r in _roots(g, 5):
        m0, h0 = _exec_fn()
        with _Compiles() as seen:
            program = bfs(source=r)
            res = run(program, g, cfg, use_pallas=use_pallas)
            got = np.asarray(res.extract(program))
        m1, h1 = _exec_fn()
        counts.append((m1 - m0, h1 - h0))
        compiles.append(seen.n)
        np.testing.assert_array_equal(got, bfs_np(g, r))
    assert counts == [(1, 0)] + [(0, 1)] * 4
    assert compiles[0] > 0 and compiles[1:] == [0] * 4


def test_pagerank_queries_reuse_one_runner():
    g = _graph(seed=5)
    cfg = SystemConfig.from_name("SGR")
    counts, compiles = [], []
    for _ in range(2):
        m0, h0 = _exec_fn()
        with _Compiles() as seen:
            program = pagerank()
            res = run(program, g, cfg)
            got = np.asarray(res.extract(program))
        m1, h1 = _exec_fn()
        counts.append((m1 - m0, h1 - h0))
        compiles.append(seen.n)
        assert np.abs(got - pagerank_np(g)).sum() < 1e-4
    assert counts == [(1, 0), (0, 1)]
    assert compiles[0] > 0 and compiles[1] == 0


@pytest.mark.parametrize("factory,oracle", [(bfs, bfs_np), (sssp, sssp_np)])
def test_certificate_stays_per_instance(factory, oracle):
    """Checkpointed runs from two sources on one graph share the segment
    runner but not the certificate, which reads the source: both
    certify, and each source's proof rejects the other's answer."""
    g = _graph(seed=6)
    cfg = SystemConfig.from_name("DG1")
    a, b = _roots(g, 2)
    pa, pb = factory(source=a), factory(source=b)
    ra = run(pa, g, cfg, checkpoint_every=4)
    rb = run(pb, g, cfg, checkpoint_every=4)
    for program, res, src in ((pa, ra, a), (pb, rb, b)):
        assert res.outcome == "converged" and res.fault is None
        np.testing.assert_allclose(np.asarray(res.extract(program)),
                                   oracle(g, src), rtol=1e-6)
    ctx = EdgeContext.create(g, cfg)
    assert check_certificate(pb, ctx, rb.state) is True
    assert check_certificate(pa, ctx, rb.state) is False
