"""``bench/xspace.py``, ``bench/layers.py`` and the metrics that read the
program's scopes and spans: on made-up intervals, on hand-built windows
and on traces recorded on a TPU v5e, one from before the program had
scopes and spans (``amz.pr.sgr``) and one with them
(``g500-s18.bfs.dg1``, two roots)."""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from bench import layers, run, run_traced, trace, xspace
from repro.core import spans

DATA = Path(__file__).parent / "data"
BEFORE = DATA / "amz.pr.sgr.xplane.pb"
WITH_SPANS = DATA / "g500-s18.bfs.dg1.xplane.pb"


def _device_ops(profile):
    """``(plane name, [(event name, self time)])`` of each device plane
    that ran operations."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            ops = [ev for line in plane.lines if line.name == trace.OPS_LINE
                   for ev in line.events]
            if ops:
                own = trace._self_times([ev.start_ns for ev in ops],
                                        [ev.end_ns for ev in ops])
                out.append((plane.name,
                            [(ev.name, t) for ev, t in zip(ops, own)]))
    return out


def test_xspace_finds_tf_op_for_most_device_time():
    found = xspace.tf_ops(BEFORE)
    [(plane, ops)] = _device_ops(trace.load(BEFORE))
    ops = [(n, t) for n, t in ops if not trace._short(n).startswith("while")]
    covered = sum(t for n, t in ops if n in found[plane])
    # the scatter into the DRFrlx partials is the largest op without one
    assert 0.80 <= covered / sum(t for _, t in ops) <= 0.85
    assert all(v.startswith("jit(") for v in found[plane].values())


def _published_decoder():
    """``xplane_pb2`` as TensorFlow ships it, loaded without importing
    TensorFlow; skips where TensorFlow is not installed."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or spec.origin is None:
        pytest.skip("TensorFlow is not installed")
    path = Path(spec.origin).parent / "tsl/profiler/protobuf/xplane_pb2.py"
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_xspace_reads_what_the_published_decoder_reads():
    pb2 = _published_decoder()
    space = pb2.XSpace()
    space.ParseFromString(BEFORE.read_bytes())
    want = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        ids = {k for k, v in plane.stat_metadata.items()
               if v.name == xspace.TF_OP}
        names = {}
        for md in plane.event_metadata.values():
            tf_op = ""
            for stat in md.stats:
                if stat.metadata_id in ids:
                    tf_op = (stat.str_value
                             if stat.WhichOneof("value") == "str_value"
                             else plane.stat_metadata[stat.ref_value].name)
            names[md.name] = (tf_op if names.get(md.name, tf_op) == tf_op
                              else None)
        want[plane.name] = {k: v for k, v in names.items() if v}
    assert xspace.tf_ops(BEFORE) == want
    assert sum(len(v) for v in want.values()) > 0


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(call)/while/body/vertex_step/edge_gather/gather", "edge_gather"),
    ("jit(call)/while/body/vertex_step/vmap(edge_reduce)/scatter-add",
     "edge_reduce"),
    ("jit(call)/while/body/vertex_step/schedule/reduce_sum", "schedule"),
    ("jit(call)/while/body/vertex_step/cond/branch_1_fun/frontier/"
     "cumsum", "frontier"),
    ("jit(call)/while/body/vertex_step/add", "vertex_step"),
    ("jit(call)/while/body/vmap()/gather:", layers.UNSCOPED),
    ("jit(true_divide)/div:", layers.UNSCOPED),
    (None, layers.UNSCOPED),
])
def test_scope_of_names_the_innermost_scope(tf_op, scope):
    assert layers.scope_of(tf_op) == scope


def test_split_idle_counts_a_gap_under_the_innermost_span():
    gaps = (np.array([5.0, 30.0, 95.0]), np.array([15.0, 60.0, 120.0]))
    opened = [(0.0, 100.0, "run", 0), (1.0, 99.0, "repro.run", 1),
              (10.0, 40.0, "repro.compile", 1),
              (50.0, 90.0, "repro.wait", 1)]
    got = layers.split_idle(*gaps, opened)
    assert got == pytest.approx({"run": 1.0, "repro.run": 19.0,
                                 "repro.compile": 15.0, "repro.wait": 10.0,
                                 "none": 20.0})
    # what the driver's span read alone is what the split adds up to
    alone = layers.split_idle(*gaps, opened[:1])
    assert alone["run"] == pytest.approx(sum(
        v for k, v in got.items() if k != "none"))
    assert sum(got.values()) == pytest.approx(65.0)


def test_split_idle_puts_a_span_with_equal_bounds_inside_the_driver():
    gaps = (np.array([2.0]), np.array([4.0]))
    got = layers.split_idle(*gaps, [(1.0, 5.0, "repro.run", 1),
                                    (1.0, 5.0, "run", 0)])
    assert got == pytest.approx({"none": 0.0, "run": 0.0, "repro.run": 2.0})


@pytest.fixture(scope="module")
def before():
    profile = trace.load(BEFORE)
    return trace.reduce(profile), layers.reduce(profile, BEFORE)


def test_without_program_spans_the_reduction_is_trace_reduce(before):
    old, new = before
    assert new["window_s"] == old["window_s"]
    assert new["busy_s"] == old["busy_s"]
    assert new["op_s"] == old["op_s"]
    assert new["idle_s"] == pytest.approx(old["idle_s"], abs=1e-12)
    assert new["span_s"] == {}
    assert new["scope_s"] == pytest.approx(
        {layers.UNSCOPED: sum(old["op_s"].values())})


@pytest.fixture(scope="module")
def with_spans():
    profile = trace.load(WITH_SPANS)
    return trace.reduce(profile), layers.reduce(profile, WITH_SPANS)


def test_recorded_trace_scopes_sum_to_the_op_times(with_spans):
    old, new = with_spans
    assert (new["window_s"], new["busy_s"], new["op_s"]) == \
        (old["window_s"], old["busy_s"], old["op_s"])
    assert sum(new["scope_s"].values()) == pytest.approx(
        sum(new["op_s"].values()), rel=1e-9)
    assert new["scope_s"][spans.EDGE_GATHER] > 0
    assert new["scope_s"][spans.FRONTIER] > 0


def test_recorded_trace_splits_run_idle_by_program_span(with_spans):
    old, new = with_spans
    program = {k: v for k, v in new["idle_s"].items()
               if k.startswith("repro.")}
    assert program and max(program.values()) > 0
    assert new["idle_s"]["run"] + sum(program.values()) == pytest.approx(
        old["idle_s"]["run"], rel=1e-9)
    for name in ("build_query", "readback", "none"):
        assert new["idle_s"][name] == pytest.approx(old["idle_s"][name],
                                                    abs=1e-12)
    assert set(new["span_s"]) == set(spans.SPANS)
    gaps = dict(trace.breakdown(new)["idle_gaps"])
    assert any(k.startswith("repro.") for k in gaps)


# ---- the metric readers, on hand-built windows ------------------------

def _window(n_queries=2, trace_=None):
    queries = [run.Query(0, None, 3, True, None)] * n_queries
    return run.Window(queries, 1.0, 0, trace_)


TRACE = {"window_s": 10.0, "busy_s": 4.0, "op_s": {}, "idle_s": {},
         "scope_s": {spans.EDGE_GATHER: 2.0, spans.EDGE_REDUCE: 1.0,
                     spans.FRONTIER: 0.25, spans.DIRECTION: 0.05,
                     spans.SCHEDULE: 0.1, layers.UNSCOPED: 0.6},
         "span_s": {spans.RUN: 8.0, spans.TRACE: 0.3, spans.COMPILE: 0.5}}
EXPECTED = {"runner_build_ms": 400.0, "edge_gather_ms": 1000.0,
            "edge_reduce_ms": 500.0, "frontier_ms": 150.0,
            "schedule_ms": 50.0}


def _read(metric, window):
    return run.load_module("metrics", metric).read(window)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_layer_reader(metric):
    assert _read(metric, _window(trace_=TRACE)) == pytest.approx(
        EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_layer_reader_finds_nothing_without_its_scope_or_span(metric):
    bare = {**TRACE, "scope_s": {layers.UNSCOPED: 3.0}, "span_s": {}}
    assert _read(metric, _window(trace_=bare)) is None
    assert _read(metric, _window(trace_=None)) is None
    assert _read(metric, _window(0, TRACE)) is None
    old = {k: v for k, v in TRACE.items() if k not in ("scope_s", "span_s")}
    assert _read(metric, _window(trace_=old)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_layer_reader_reads_nothing_of_a_program_without_names(monkeypatch,
                                                                metric):
    monkeypatch.setattr(layers, "spans", None)
    assert _read(metric, _window(trace_=TRACE)) is None


@pytest.mark.parametrize("cell", ["g500-s18.bfs.dg1", "amz.pr.tg0"])
def test_run_traced_runs_a_cell(tiny_bench, capsys, tmp_path, cell):
    import json

    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keep = tmp_path / "window.xplane.pb"
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        rc = run_traced.main(["--workload", cell, "--seed", str(2**33 + 1),
                              "--seconds", "0.3", "--keep", str(keep)])
    finally:  # the run's own compile cache is gone: leave none in use
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and keep.stat().st_size > 0
    # no device plane on the CPU: the counters and the host clock only
    assert {"teps", "setup_s", "iters_per_query"} <= set(line["metrics"])
    assert list(line)[-1] == "checks"
