"""The chip bring-up script, rehearsed at tiny sizes on the CPU, and the
persistent compilation cache it turns on."""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_passes_every_phase(monkeypatch, tmp_path, capsys):
    # with the variable set the script leaves the cache to JAX, which
    # read the (then unset) variable at import: no cache in this test
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert _chip_smoke().main(["--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}
    cells = [l for l in lines if "oracle_match" in l]
    assert all(c["oracle_match"] for c in cells)
    run_cells = [c for c in cells if c["phase"] == "run"]
    assert len(run_cells) == 18 + 4 * 5
    assert {c["config"] for c in run_cells if c["use_pallas"]} == {
        "SD1", "DD1"}
    gateway = [c for c in cells if c["phase"] == "gateway"]
    assert len(gateway) >= 8
    assert {(c["config"], c["use_pallas"]) for c in gateway} == {
        ("DG1", False), ("DD1", True)}


def test_without_a_chip_the_script_fails_before_printing(monkeypatch,
                                                          tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        _chip_smoke().main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_cache_left_to_jax_when_the_environment_names_it(monkeypatch,
                                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
