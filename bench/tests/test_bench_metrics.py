"""Each per-layer metric reader on canned windows."""
import pytest

from bench import run


def _window(queries=(), compiles=0, trace=None, **kw):
    return run.Window(list(queries), 1.0, compiles, trace, **kw)


def _query(iterations, occupancy):
    return run.Query(0, None, iterations, True, occupancy)


def _read(metric, window):
    return run.load_module("metrics", metric).read(window)


BFS = [_query(4, [-1.0, 0.2, -1.0, 0.5]), _query(2, [0.1, -1.0])]
TRACE = {"window_s": 10.0, "busy_s": 4.0, "idle_s": {},
         "op_s": {"fusion.1": 1.0, "seg_sum_pallas": 1.5,
                  "seg_minmax_pallas": 0.5}}


def test_teps():
    assert _read("teps", _window(BFS, work=3e6)) == 3e6
    assert _read("teps", _window(work=0.0)) is None


def test_setup_s():
    assert _read("setup_s", _window(BFS, setup_s=27.5)) == 27.5


def test_iters_per_query():
    assert _read("iters_per_query", _window(BFS)) == 3.0


def test_compiles_in_window():
    assert _read("compiles_in_window", _window(BFS, compiles=2)) == 2.0


def test_sparse_iter_share():
    assert _read("sparse_iter_share", _window(BFS)) == pytest.approx(50.0)
    dense_only = [_query(3, None)]
    assert _read("sparse_iter_share", _window(dense_only)) is None


def test_device_idle_share():
    assert _read("device_idle_share", _window(BFS, trace=TRACE)) == 60.0


def test_pallas_reduce_share():
    assert _read("pallas_reduce_share",
                 _window(BFS, trace=TRACE)) == pytest.approx(50.0)
    xla_only = {**TRACE, "op_s": {"fusion.1": 1.0}}
    assert _read("pallas_reduce_share", _window(BFS, trace=xla_only)) is None


@pytest.mark.parametrize("metric", ["iters_per_query", "compiles_in_window",
                                    "sparse_iter_share", "device_idle_share",
                                    "pallas_reduce_share"])
def test_readers_find_nothing_in_an_empty_window(metric):
    assert _read(metric, _window()) is None
