"""Every cell file resolves to existing files, and BENCHMARK.json agrees
with the files the harness finds by name."""
import json
import re
from pathlib import Path

import pytest

from bench import run

CELLS = sorted(p.stem for p in (run.BENCH / "workloads").glob("*.json"))
SPEC = json.loads((run.REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = run.load_cell(name)
    w = cell.workload
    assert w["chips"] == 1 and len(w["why"]) <= 200
    assert cell.config["name"] == w["config"]
    assert callable(cell.generator.generate)
    assert callable(cell.queries.draw)
    assert callable(cell.driver.answer) and callable(cell.driver.loop)
    for fn in ("prepare", "solve", "work", "compare", "control"):
        assert callable(getattr(cell.reference, fn))
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    for reader in {**cell.end_to_end, **cell.per_layer}.values():
        assert callable(reader.read) and reader.UNIT
    import repro.algorithms as algorithms
    from repro.core import SystemConfig
    assert callable(getattr(algorithms, w["program"]))
    assert SystemConfig.from_name(w["system_config"]).name == \
        w["system_config"]


def test_benchmark_json_names_every_cell_file():
    assert sorted(c["name"] for c in SPEC["workloads"]) == CELLS
    for c in SPEC["workloads"]:
        w = run.load_json("workloads", c["name"])
        assert (c["config"], c["traffic"], c["chips"], c["why"]) == \
            (w["config"], w["traffic"], w["chips"], w["why"])


def test_benchmark_json_configs_are_the_files():
    for cfg in SPEC["configs"]:
        doc = json.loads((run.REPO / cfg["file"]).read_text())
        assert doc["name"] == cfg["name"] and doc["source"] == cfg["source"]
        assert sorted(doc["reduced"]) == sorted(cfg["reduced"])


def test_benchmark_json_metrics_match_the_readers():
    for m in SPEC["end_to_end"]:
        assert m["unit"] == run.load_module("metrics", m["name"]).UNIT
    for name in CELLS:
        w = run.load_json("workloads", name)
        assert set(w["metrics"]["end_to_end"]) <= \
            {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        reader = run.load_module("metrics", m["name"])
        assert m["unit"] == reader.UNIT
        for cell in m["workloads"]:
            w = run.load_json("workloads", cell)
            assert m["name"] in w["metrics"]["per_layer"]
            assert m["moves"] in w["metrics"]["end_to_end"]
    for name in CELLS:
        w = run.load_json("workloads", name)
        for metric in w["metrics"]["per_layer"]:
            listed = [m for m in SPEC["per_layer"] if m["name"] == metric]
            assert listed and name in listed[0]["workloads"]


def test_names_keep_to_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    names = [c["name"] for c in SPEC["configs"] + SPEC["workloads"]
             + SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    names += [c["traffic"] for c in SPEC["workloads"]]
    assert all(ok.match(n) for n in names)
    for path in Path(run.BENCH).rglob("*"):
        rel = path.relative_to(run.REPO).as_posix()
        if "__pycache__" not in rel:
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
