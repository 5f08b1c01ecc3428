"""Fixtures for the benchmark's CPU tests: the harness at tiny sizes.

Only these tests stub the harness's look for a chip and its compile
cache; the benchmark itself never falls back to the CPU.
"""
import json

import pytest

#: configuration parameters overridden for a CPU run
TINY = {"graph500-s18": {"scale": 9},
        "amz-table2": {"n": 2048, "n_edges": 12000}}


@pytest.fixture
def tiny_bench(monkeypatch):
    """``bench.run`` with tiny graphs, the CPU accepted as the chip, and
    the process-wide compile-cache settings left alone."""
    import jax

    from bench import run

    real = run.load_json

    def load_json(kind, name):
        doc = real(kind, name)
        if kind == "configs":
            doc["params"].update(TINY[name])
        return doc

    monkeypatch.setattr(run, "load_json", load_json)
    monkeypatch.setattr(run, "require_chip", lambda chips: jax.devices())
    monkeypatch.setattr(run, "enable_cache", lambda: None)
    return run


@pytest.fixture
def run_cell(tiny_bench, capsys):
    """One tiny run: ``(exit code, last stdout line as JSON or None)``."""
    def run_cell(cell, seed=2**31 + 5, seconds=0.3, trace=0):
        rc = tiny_bench.main(["--workload", cell, "--seed", str(seed),
                              "--seconds", str(seconds),
                              "--trace", str(trace)])
        lines = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(lines[-1]) if lines else None)
    return run_cell
