"""Program counters: ``VertexProgram.counters`` read into
``RunResult.counters``.

SSSP counts its vertex activations (the source, then each improvement of
a distance).  The count is checked against a numpy frontier
Bellman-Ford on every engine and per graph of a batch; programs that
declare no counters keep their runner and report ``None``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import bfs, pagerank, sssp
from repro.core import EdgeContext, SystemConfig, executor, run, run_batch
from repro.core.vertex_program import DENSE_OCC
from repro.graph import rmat_graph


def _graph(seed=3):
    return rmat_graph(8, 8, seed=seed, weighted=True, block_size=128)


@pytest.fixture(scope="module")
def graph():
    return _graph()


def _np_activations(graph, source):
    """Float32 frontier Bellman-Ford over ``graph``'s out-edges:
    ``(dist, activations)``, the source counted once and every vertex
    once per iteration in which its distance improved."""
    ptr = np.asarray(graph.row_ptr_out, np.int64)
    col = np.asarray(graph.dst, np.int64)
    w = np.asarray(graph.weight, np.float32)
    dist = np.full(graph.n_nodes, np.inf, np.float32)
    dist[source] = 0.0
    frontier = np.asarray([source])
    count = 1
    while frontier.size:
        counts = ptr[frontier + 1] - ptr[frontier]
        idx = np.concatenate([np.arange(ptr[u], ptr[u + 1])
                              for u in frontier])
        cand = np.repeat(dist[frontier], counts) + w[idx]
        new = dist.copy()
        np.minimum.at(new, col[idx], cand)
        frontier = np.flatnonzero(new < dist)
        count += frontier.size
        dist = new
    return dist, count


ENGINES = {"fused": {}, "host": {"engine": "host"},
           "resilient": {"checkpoint_every": 4}}


@pytest.mark.parametrize("config", ["DD1", "SGR"])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_sssp_counts_activations_like_numpy(graph, config, engine):
    for source in (0, 5):
        dist, want = _np_activations(graph, source)
        res = run(sssp(source=source), graph, SystemConfig.from_name(config),
                  **ENGINES[engine])
        assert res.converged
        np.testing.assert_array_equal(np.asarray(res.state["dist"]), dist)
        assert res.counters == {"activations": want}
        assert type(res.counters["activations"]) is int
        assert want > np.isfinite(dist).sum()  # re-activations happen


def test_run_batch_counts_per_graph():
    graphs = [_graph(3), _graph(4)]
    config = SystemConfig.from_name("DD1")
    batched = run_batch(sssp(source=2), graphs, config)
    for g, res in zip(graphs, batched):
        alone = run(sssp(source=2), g, config)
        assert res.counters == alone.counters
        assert res.counters["activations"] == _np_activations(g, 2)[1]
    assert batched[0].counters != batched[1].counters


def _fused_jaxpr(program, graph, config):
    ctx = EdgeContext.create(graph, SystemConfig.from_name(config))
    state = jax.tree.map(jnp.asarray, program.init(graph))
    traced, occ_traced = executor._trace_flags(program, state)
    limit = program.max_iters
    db = jnp.zeros((limit,), bool) if traced else None
    ob = jnp.full((limit,), DENSE_OCC, jnp.float32) if occ_traced else None
    fused = executor._fused_loop(program, ctx, limit, traced, occ_traced)
    return str(jax.make_jaxpr(fused)(state, db, ob))


@pytest.mark.parametrize("factory", [bfs, pagerank])
def test_programs_without_counters_keep_their_runner(graph, factory):
    program = factory()
    assert program.counters is None
    without = dataclasses.replace(program, counters=None)
    assert _fused_jaxpr(program, graph, "DD1") == \
        _fused_jaxpr(without, graph, "DD1")
    # reading a counter happens after the loop, never inside it
    key = next(iter(program.init(graph)))
    counted = dataclasses.replace(program, counters={"k": lambda s: s[key]})
    assert _fused_jaxpr(counted, graph, "DD1") == \
        _fused_jaxpr(program, graph, "DD1")
    assert run(program, graph, SystemConfig.from_name("DD1")).counters \
        is None
