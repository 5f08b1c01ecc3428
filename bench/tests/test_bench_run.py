"""The harness end to end at tiny sizes on the CPU, its refusal without a
chip, and ``correct`` coming out false when the timed path is broken."""
import dataclasses

import pytest

from bench import run

CELLS = sorted(p.stem for p in (run.BENCH / "workloads").glob("*.json"))


def test_refuses_without_a_chip(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == ""
    assert "TPU" in captured.err


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(run_cell, cell):
    rc, line = run_cell(cell)
    assert rc == 0 and line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    w = run.load_json("workloads", cell)
    assert set(line["metrics"]) == set(w["metrics"]["end_to_end"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", ["g500-s18.bfs.dg1", "amz.pr.sgr"])
def test_traced_run_reports_per_layer_metrics(run_cell, cell):
    rc, line = run_cell(cell, trace=1)
    assert rc == 0 and line["correct"] is True
    w = run.load_json("workloads", cell)
    # no device plane on the CPU: only the counters can be read
    assert "iters_per_query" in line["metrics"]
    assert set(line["metrics"]) <= set(w["metrics"]["per_layer"])


def test_same_seed_same_queries(tiny_bench):
    import numpy as np
    cell = tiny_bench.load_cell("g500-s18.bfs.dg1")
    edges = cell.generator.generate(
        tiny_bench.load_json("configs", "graph500-s18")["params"], 99)
    a = cell.queries.draw(edges, 99)
    b = cell.queries.draw(edges, 99)
    roots = [next(a) for _ in range(20)]
    assert roots == [next(b) for _ in range(20)]
    keep = edges["src"] != edges["dst"]
    deg = np.bincount(np.concatenate([edges["src"][keep],
                                      edges["dst"][keep]]),
                      minlength=edges["n"])
    assert len(set(roots)) == 20 and all(deg[r] > 0 for r in roots)


def _broken(monkeypatch, program, change):
    """Make ``repro.algorithms.<program>`` build a broken program."""
    import repro.algorithms as algorithms
    real = getattr(algorithms, program)
    monkeypatch.setattr(algorithms, program,
                        lambda **kw: dataclasses.replace(real(**kw),
                                                         **change(real(**kw))))


def _state_unchanged(prog):
    return {"step": lambda ctx, st, it: st}


def _answer_altered(prog):
    def extract(st):
        out = prog.extract(st)
        return out.at[out.shape[0] // 2].add(out[0] * 0 + 1)
    return {"extract": extract}


@pytest.mark.parametrize("fault", [_state_unchanged, _answer_altered])
@pytest.mark.parametrize("cell,program", [("g500-s18.bfs.dg1", "bfs"),
                                          ("amz.pr.sgr", "pagerank")])
def test_broken_timed_path_is_not_correct(run_cell, monkeypatch, cell,
                                          program, fault):
    _broken(monkeypatch, program, fault)
    rc, line = run_cell(cell, seconds=0.1)
    assert rc == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1


@pytest.mark.parametrize("cell", ["g500-s18.bfs.dg1", "amz.pr.sgr"])
def test_control_fails_where_the_program_passes(tiny_bench, capsys, cell):
    import json

    from bench import control
    assert control.main(["--workload", cell, "--seconds", "0.2",
                         "--seeds", "3", str(2**32 + 3)]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    limits = tiny_bench.load_cell(cell).reference.LIMITS
    assert len(lines) == 2
    for line in lines:
        assert all(line["program"][k] <= v for k, v in limits.items())
        assert any(line["control"][k] > v for k, v in limits.items())


def test_seed_draws_the_queries_not_the_graph(tiny_bench):
    import numpy as np
    cell = tiny_bench.load_cell("g500-s18.bfs.dg1")
    a = tiny_bench.set_up(cell, 5, {})
    b = tiny_bench.set_up(cell, 2**40 + 6, {})
    for k in ("src", "dst", "weight"):
        np.testing.assert_array_equal(a[0][k], b[0][k])
    assert [next(a[3]) for _ in range(8)] != [next(b[3]) for _ in range(8)]
