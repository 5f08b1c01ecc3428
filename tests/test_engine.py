"""Execution engines: the fused device-resident ``lax.while_loop``
runner vs the host kernel-per-iteration oracle, the plan cache that
amortizes EdgeContext construction, and the vectorized reducer tiling
plan.

Acceptance criteria covered here: the fused engine is bit-identical to
the host engine on state, iterations and both traces across the full
config matrix for BFS/SSSP/BC (the PR 1 oracle apps); a fused run
issues exactly one timed jit dispatch; max_iters truncation reports
``converged=False`` identically on both engines; a repeated 12-cell
EdgeContext construction hits the plan cache; and ``plan_tiles``'s
numpy bucket arithmetic matches the per-block loop it replaced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.algorithms import bc, bfs, sssp
from repro.algorithms.reference import bfs_np
from repro.core import (ALL_CONFIGS, PLAN_CACHE, STATS, EdgeContext,
                        SystemConfig, run)
from repro.core.vertex_program import DENSE_OCC
from repro.graph import powerlaw_graph, random_graph, rmat_graph

CONFIG_NAMES = [c.name for c in ALL_CONFIGS]
APPS = {"BFS": bfs, "SSSP": sssp, "BC": bc}


@pytest.fixture(scope="module")
def rand_g():
    return random_graph(64, 400, seed=0, weighted=True, block_size=32)


@pytest.fixture(scope="module")
def sf_g():
    return powerlaw_graph(200, 1500, alpha=1.2, seed=1, weighted=True,
                          block_size=32)


def _assert_results_identical(a, b):
    assert a.iterations == b.iterations
    assert a.converged == b.converged
    assert a.direction_trace == b.direction_trace
    assert a.occupancy_trace == b.occupancy_trace
    la, lb = jax.tree.leaves(a.state), jax.tree.leaves(b.state)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestFusedVsHost:
    """Fused engine == host engine, bit for bit, over the full matrix."""

    @pytest.mark.parametrize("app", list(APPS))
    @pytest.mark.parametrize("cfg", CONFIG_NAMES)
    def test_matrix_bit_identical(self, rand_g, cfg, app):
        program = APPS[app]()
        host = run(program, rand_g, SystemConfig.from_name(cfg),
                   engine="host")
        fused = run(program, rand_g, SystemConfig.from_name(cfg),
                    engine="fused")
        _assert_results_identical(host, fused)
        assert host.engine == "host" and fused.engine == "fused"

    def test_scale_free_dynamic_cell(self, sf_g):
        """The direction-switching DD1 cell (mixed S/T trace, sparse
        gathers) on a scale-free input — the hardest trace to preserve."""
        host = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                   engine="host")
        fused = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                    engine="fused")
        _assert_results_identical(host, fused)
        assert "S" in fused.direction_trace and "T" in fused.direction_trace
        assert fused.sparse_iterations >= 1
        np.testing.assert_array_equal(np.asarray(fused.state["depth"]),
                                      bfs_np(sf_g))

    def test_pallas_fast_path(self, sf_g):
        host = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                   engine="host", use_pallas=True)
        fused = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                    engine="fused", use_pallas=True)
        _assert_results_identical(host, fused)

    @pytest.mark.parametrize("engine", ["host", "fused"])
    def test_max_iters_truncation(self, sf_g, engine):
        """A truncated run reports converged=False with exactly
        max_iters iterations and max_iters-long traces."""
        r = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                max_iters=2, engine=engine)
        assert not r.converged
        assert r.iterations == 2
        assert len(r.direction_trace) == 2
        assert len(r.occupancy_trace) == 2

    def test_truncation_identical_across_engines(self, sf_g):
        host = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                   max_iters=2, engine="host")
        fused = run(bfs(), sf_g, SystemConfig.from_name("DD1"),
                    max_iters=2, engine="fused")
        _assert_results_identical(host, fused)

    def test_unknown_engine_rejected(self, rand_g):
        with pytest.raises(ValueError, match="unknown engine"):
            run(bfs(), rand_g, SystemConfig.from_name("SG1"),
                engine="gpu")

    def test_frontierless_program_fused(self, rand_g):
        """Programs without the frontier protocol (no traces) run fused
        too — the trace buffers simply stay out of the carry.  All six
        registered apps now speak the protocol (ISSUE 6), so this path
        is covered by an inline smoothing program."""
        from repro.core.vertex_program import SUM, EdgePhase, VertexProgram
        phase = EdgePhase(monoid=SUM,
                          vprop=lambda st, src, w: st["x"][src])
        prog = VertexProgram(
            name="BFS",  # borrow a Table III row; properties are unused
            init=lambda g: {"x": jnp.ones((g.n_nodes,), jnp.float32)},
            step=lambda ctx, st, it: {
                "x": 0.5 * st["x"] + 0.25 * ctx.propagate(st, phase)},
            converged=lambda prev, cur: jnp.asarray(False),
            extract=lambda st: st["x"],
        )
        host = run(prog, rand_g, SystemConfig.from_name("SG1"),
                   max_iters=5, engine="host")
        fused = run(prog, rand_g, SystemConfig.from_name("SG1"),
                    max_iters=5, engine="fused")
        assert fused.direction_trace is None
        assert fused.occupancy_trace is None
        _assert_results_identical(host, fused)


class TestDispatchCount:
    def test_fused_is_one_dispatch(self, sf_g):
        """The whole convergence loop is a single timed jitted
        invocation, however many iterations it runs."""
        STATS.reset()
        r = run(bfs(), sf_g, SystemConfig.from_name("DD1"), engine="fused")
        assert r.iterations > 1  # a real multi-iteration run
        assert STATS.dispatches == 1
        assert r.dispatches == 1

    def test_host_is_one_dispatch_per_iteration(self, sf_g):
        STATS.reset()
        r = run(bfs(), sf_g, SystemConfig.from_name("DD1"), engine="host")
        assert STATS.dispatches == r.iterations
        assert r.dispatches == r.iterations

    def test_fused_without_warmup_still_one_dispatch(self, rand_g):
        STATS.reset()
        run(bfs(), rand_g, SystemConfig.from_name("SG1"), engine="fused",
            warmup=False)
        assert STATS.dispatches == 1


@pytest.mark.parametrize("runner", ["fused", "host", "checkpointed",
                                    "batch"])
def test_runners_take_graph_arrays_as_arguments(runner):
    """No runner compiles the graph's edge arrays into its executable:
    JAX warns about every lowering that captures more constant bytes
    than the threshold, here well under one edge array."""
    import warnings
    from repro.core import run_batch

    g = random_graph(512, 8000, seed=7, weighted=True, block_size=128)
    cfg = SystemConfig.from_name("DD1")
    before = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 4 * g.n_edges // 2)
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            if runner == "batch":
                run_batch(bfs(), [g, g], cfg, use_pallas=True)
            else:
                run(bfs(), g, cfg, use_pallas=True,
                    engine="host" if runner == "host" else "fused",
                    checkpoint_every=2 if runner == "checkpointed" else 0)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", before)
    assert not [w for w in caught if "constants were captured"
                in str(w.message)]


class TestPlanCache:
    def test_repeated_12_cell_construction_hits(self):
        """Binding the same graph to every config twice: the second
        sweep builds nothing (all context-level hits), and even the
        first sweep shares chunked orders across cells."""
        g = random_graph(48, 300, seed=3, weighted=True, block_size=16)
        PLAN_CACHE.clear()
        for cfg in ALL_CONFIGS:
            EdgeContext.create(g, SystemConfig.from_name(cfg.name))
        first = PLAN_CACHE.stats()
        # 18 configs share: 1 device graph + owned edges + chunked
        # orders per (order, n_chunks in {1, 8}) -> far fewer builds
        # than 18 full constructions
        assert first["misses"] < len(ALL_CONFIGS) * 4
        assert first["hits"] > 0
        for cfg in ALL_CONFIGS:
            EdgeContext.create(g, SystemConfig.from_name(cfg.name))
        second = PLAN_CACHE.stats()
        assert second["misses"] == first["misses"]  # nothing rebuilt
        assert second["hits"] == first["hits"] + len(ALL_CONFIGS)

    def test_distinct_graphs_do_not_collide(self):
        g1 = random_graph(32, 150, seed=1, block_size=16)
        g2 = random_graph(32, 150, seed=2, block_size=16)
        c1 = EdgeContext.create(g1, SystemConfig.from_name("SG1"))
        c2 = EdgeContext.create(g2, SystemConfig.from_name("SG1"))
        assert c1 is not c2
        assert c1 is EdgeContext.create(g1, SystemConfig.from_name("SG1"))

    def test_capacity_is_part_of_the_key(self):
        g = random_graph(32, 150, seed=1, block_size=16)
        a = EdgeContext.create(g, SystemConfig.from_name("DG1"))
        b = EdgeContext.create(g, SystemConfig.from_name("DG1"),
                               sparse_edge_capacity=0)
        assert a is not b
        # None normalizes to the documented default capacity
        assert a is EdgeContext.create(
            g, SystemConfig.from_name("DG1"),
            sparse_edge_capacity=EdgeContext.default_sparse_capacity(g))

    def test_eviction_on_graph_collection(self):
        import gc
        PLAN_CACHE.clear()
        g = random_graph(32, 150, seed=5, block_size=16)
        EdgeContext.create(g, SystemConfig.from_name("SG1"))
        assert len(PLAN_CACHE) > 0
        del g
        gc.collect()
        assert len(PLAN_CACHE) == 0

    def test_repeated_runs_reuse_compiled_runner(self, sf_g):
        """Sweep repeats hit the exec_fn cache: the fused while_loop is
        AOT-compiled once per (program, cell, limit), not per run."""
        import time
        program = bfs()
        cfg = SystemConfig.from_name("DD1")
        PLAN_CACHE.clear()
        r1 = run(program, sf_g, cfg, engine="fused")
        hits_before = PLAN_CACHE.stats()["hits"]
        misses_before = PLAN_CACHE.stats()["misses"]
        t0 = time.perf_counter()
        r2 = run(program, sf_g, cfg, engine="fused")
        warm_wall = time.perf_counter() - t0
        after = PLAN_CACHE.stats()
        assert after["misses"] == misses_before  # nothing rebuilt
        assert after["hits"] > hits_before       # context + exec_fn hits
        _assert_results_identical(r1, r2)
        assert warm_wall < 5.0  # no multi-second recompile on repeat

    def test_distinct_programs_get_distinct_runners(self, rand_g):
        """Two instances of a program that declares no runner_key must
        not share a compiled runner even on the same cell (the cache
        pins each program by identity)."""
        cfg = SystemConfig.from_name("SG1")
        misses = PLAN_CACHE.kind_stats("exec_fn")["misses"]
        a = run(bc(root=0), rand_g, cfg, engine="fused")
        b = run(bc(root=1), rand_g, cfg, engine="fused")
        assert PLAN_CACHE.kind_stats("exec_fn")["misses"] == misses + 2
        assert int(np.asarray(a.state["depth"])[0]) == 0
        assert int(np.asarray(b.state["depth"])[1]) == 0

    def test_exec_fn_bucket_is_bounded(self, rand_g):
        """A stream of distinct program instances on one long-lived
        graph (exact-BC-style per-root loops) must not accumulate
        unbounded compiled executables."""
        from repro.core import executor
        PLAN_CACHE.clear()
        cfg = SystemConfig.from_name("SG1")
        for src in range(executor._EXEC_FN_CAPACITY + 8):
            run(bc(root=src % rand_g.n_nodes), rand_g, cfg,
                max_iters=1, engine="fused")
        with PLAN_CACHE._lock:
            n_exec = sum(1 for k in PLAN_CACHE._store
                         if k[1] == "exec_fn")
        assert n_exec <= executor._EXEC_FN_CAPACITY

    def test_cached_context_produces_correct_results(self, sf_g):
        """Reuse through the cache does not change answers (contexts
        are immutable): two runs on the same cell, one cold one warm."""
        PLAN_CACHE.clear()
        r1 = run(bfs(), sf_g, SystemConfig.from_name("DD1"))
        r2 = run(bfs(), sf_g, SystemConfig.from_name("DD1"))
        _assert_results_identical(r1, r2)
        np.testing.assert_array_equal(np.asarray(r2.state["depth"]),
                                      bfs_np(sf_g))


def _plan_tiles_loop_ref(block_ptr, tile_e):
    """The per-block Python loop plan_tiles replaced — kept as oracle."""
    block_ptr = np.asarray(block_ptr, np.int64)
    n_blocks = block_ptr.shape[0] - 1
    gather, tbid, tfirst = [], [], []
    for b in range(n_blocks):
        lo, hi = block_ptr[b], block_ptr[b + 1]
        n = int(hi - lo)
        n_tiles = max(1, -(-n // tile_e))
        idx = np.full(n_tiles * tile_e, -1, np.int64)
        idx[:n] = np.arange(lo, hi)
        for t in range(n_tiles):
            gather.append(idx[t * tile_e:(t + 1) * tile_e])
            tbid.append(b)
            tfirst.append(1 if t == 0 else 0)
    return (np.stack(gather).astype(np.int32),
            np.asarray(tbid, np.int32), np.asarray(tfirst, np.int32))


class TestPlanTilesVectorized:
    @given(st.integers(0, 2**31 - 1), st.integers(1, 17))
    @settings(max_examples=25, deadline=None)
    def test_matches_loop_reference(self, seed, tile_e):
        from repro.kernels.segment_reduce.kernel import plan_tiles
        rng = np.random.default_rng(seed)
        counts = rng.integers(0, 40, int(rng.integers(1, 20)))
        block_ptr = np.concatenate([[0], np.cumsum(counts)])
        got = plan_tiles(block_ptr, tile_e)
        ref = _plan_tiles_loop_ref(block_ptr, tile_e)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(g, r)

    def test_empty_blocks_get_padding_tile(self):
        from repro.kernels.segment_reduce.kernel import plan_tiles
        gather, tbid, tfirst = plan_tiles(np.asarray([0, 0, 3, 3]), 4)
        assert gather.shape == (3, 4)
        np.testing.assert_array_equal(tbid, [0, 1, 2])
        np.testing.assert_array_equal(tfirst, [1, 1, 1])
        np.testing.assert_array_equal(gather[0], [-1, -1, -1, -1])
        np.testing.assert_array_equal(gather[1], [0, 1, 2, -1])
        np.testing.assert_array_equal(gather[2], [-1, -1, -1, -1])

    def test_reducer_exposes_plan_size(self):
        from repro.kernels.segment_reduce import BlockedSegmentReducer
        red = BlockedSegmentReducer(
            np.asarray([0, 0, 1, 5, 9]), np.asarray([0, 3, 5]),
            num_segments=10, block_size=5, tile_e=2)
        assert red.n_tiles == red.gather_idx.shape[0]
        assert red.tile_e == 2


class TestOccupancyDtype:
    """The dense-iteration sentinel is one jnp.float32 scalar from
    every propagate_sparse branch (the while_loop carry requires it)."""

    def test_early_return_is_jnp_float32(self, rand_g):
        """Static config -> the early-return branch."""
        from repro.core import MIN, EdgePhase
        ctx = EdgeContext.create(rand_g, SystemConfig.from_name("SG1"))
        program = bfs()
        state = jax.tree.map(jnp.asarray, program.init(rand_g))
        phase = EdgePhase(monoid=MIN,
                          vprop=lambda st, s, w: st["depth"][s] + 1,
                          spred=lambda st, s: st["active"][s],
                          frontier=lambda st: st["active"],
                          gatherable=True)
        _, occ = ctx.propagate_sparse(state, phase, jnp.asarray(False),
                                      dtype=jnp.int32)
        assert isinstance(occ, jax.Array)
        assert occ.dtype == jnp.float32 and occ.shape == ()
        assert float(occ) == DENSE_OCC

    @pytest.mark.parametrize("pull", [False, True])
    def test_dynamic_branches_are_float32_scalars(self, rand_g, pull):
        from repro.core import MIN, EdgePhase
        ctx = EdgeContext.create(rand_g, SystemConfig.from_name("DG1"))
        program = bfs()
        state = jax.tree.map(jnp.asarray, program.init(rand_g))
        phase = EdgePhase(monoid=MIN,
                          vprop=lambda st, s, w: st["depth"][s] + 1,
                          spred=lambda st, s: st["active"][s],
                          frontier=lambda st: st["active"],
                          gatherable=True)
        _, occ = ctx.propagate_sparse(state, phase, jnp.asarray(pull),
                                      dtype=jnp.int32)
        assert occ.dtype == jnp.float32 and occ.shape == ()
        if pull:
            assert float(occ) == DENSE_OCC  # pull is inherently dense
        else:
            assert 0.0 <= float(occ) <= 1.0  # sparse gather fired

    def test_overflow_fallback_is_float32_sentinel(self, sf_g):
        from repro.core import MIN, EdgePhase
        ctx = EdgeContext.create(sf_g, SystemConfig.from_name("DG1"),
                                 sparse_edge_capacity=1)
        program = bfs()
        state = jax.tree.map(jnp.asarray, program.init(sf_g))
        # widen the frontier so its edges overflow capacity 1
        state = {**state,
                 "active": jnp.ones((sf_g.n_nodes,), bool)}
        phase = EdgePhase(monoid=MIN,
                          vprop=lambda st, s, w: st["depth"][s] + 1,
                          spred=lambda st, s: st["active"][s],
                          frontier=lambda st: st["active"],
                          gatherable=True)
        _, occ = ctx.propagate_sparse(state, phase, jnp.asarray(False),
                                      dtype=jnp.int32)
        assert occ.dtype == jnp.float32 and occ.shape == ()
        assert float(occ) == DENSE_OCC


class TestRmatWorkload:
    def test_rmat_generator_shape_and_symmetry(self):
        g = rmat_graph(scale=6, edge_factor=4, seed=7)
        assert g.n_nodes == 64
        assert g.n_edges > 0
        # symmetric universal input format: every edge has its reverse
        fwd = set(zip(np.asarray(g.src).tolist(),
                      np.asarray(g.dst).tolist()))
        assert all((d, s) in fwd for s, d in fwd)

    def test_dispatch_bench_writes_json(self, tmp_path):
        import json
        from benchmarks.dispatch import run_dispatch
        out = tmp_path / "BENCH_dispatch.json"
        res = run_dispatch(out_path=str(out), scale=5, repeats=1)
        assert out.exists()
        on_disk = json.loads(out.read_text())
        assert on_disk["summary"]["n_configs"] == len(ALL_CONFIGS)
        for cell in on_disk["configs"].values():
            assert cell["fused"]["dispatches"] == 1
            assert (cell["host"]["dispatches"]
                    == cell["host"]["iterations"])
            assert cell["fused"]["us_per_iteration"] > 0
        assert res["workload"]["generator"] == "rmat"
