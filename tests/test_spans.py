"""The program's profiler scopes and spans (``repro.core.spans``).

Each cell of the benchmark builds its fused runner here on a small
R-MAT graph; the compiled HLO's ``op_name`` metadata must carry every
device scope the cell's per-layer metrics read, and none of the scopes
its configuration bypasses.  ``run`` under the profiler writes its host
spans, nested as the trace reduction expects.
"""
import re
from pathlib import Path

import jax
import pytest

import repro.algorithms as algorithms
from repro.core import SystemConfig, executor, run, spans
from repro.graph import rmat_graph

ALL = set(spans.SCOPES)
FRONTIER_SCOPES = {spans.DIRECTION, spans.FRONTIER}


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(8, 8, seed=1)


def _compiled_scopes(monkeypatch, graph, app, config, use_pallas):
    """The scopes named in the op_name metadata of the fused runner that
    ``run`` compiles."""
    built = []
    real = executor._jit_hoisted

    def spy(*args, **kwargs):
        built.append(real(*args, **kwargs))
        return built[-1]

    monkeypatch.setattr(executor, "_jit_hoisted", spy)
    res = run(getattr(algorithms, app)(), graph,
              SystemConfig.from_name(config), use_pallas=use_pallas)
    assert res.converged and len(built) == 1
    op_names = re.findall(r'op_name="([^"]*)"', built[0].func.as_text())
    parts = {re.sub(r"^\w*\((.*)\)$", r"\1", p)
             for name in op_names for p in name.split("/")}
    return parts & ALL


@pytest.mark.parametrize("app,config,use_pallas,absent", [
    ("bfs", "DG1", False, set()),
    ("bfs", "DD1", True, {spans.SCHEDULE}),
    ("pagerank", "SGR", False, FRONTIER_SCOPES),
    ("pagerank", "TG0", False, FRONTIER_SCOPES | {spans.SCHEDULE}),
])
def test_runner_carries_the_cells_scopes(monkeypatch, graph, app, config,
                                         use_pallas, absent):
    assert _compiled_scopes(monkeypatch, graph, app, config,
                            use_pallas) == ALL - absent


def test_run_writes_its_host_spans_nested(tmp_path):
    # a graph of its own: on the module's graph this cell's runner is
    # already built, and a cache hit opens no trace or compile span
    prog = algorithms.bfs(source=0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        run(prog, rmat_graph(8, 8, seed=2), SystemConfig.from_name("DG1"))
    finally:
        jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(
        str(next(Path(tmp_path).rglob("*.xplane.pb"))))
    seen = {ev.name: (ev.start_ns, ev.end_ns)
            for plane in profile.planes for line in plane.lines
            for ev in line.events if ev.name in spans.SPANS}
    assert set(seen) == set(spans.SPANS)
    lo, hi = seen[spans.RUN]
    inner = [seen[name] for name in spans.SPANS[1:]]
    assert all(lo <= s <= e <= hi for s, e in inner)
    # context, init, trace, compile, dispatch, wait, decode: in that order
    assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))
