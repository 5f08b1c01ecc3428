"""Tiny sizes for the benchmark's CPU tests (``bench/tests/``) of the
configurations that ``bench/tests/conftest.py``'s ``TINY`` does not list:
each is merged into that table when pytest loads it."""
from pathlib import Path

#: configuration parameters overridden for a CPU run
TINY = {"graph500-s18-sssp": {"scale": 9}}

_BENCH_FIXTURES = Path(__file__).resolve().parent / "bench" / "tests" \
    / "conftest.py"


def pytest_plugin_registered(plugin):
    path = getattr(plugin, "__file__", None)
    if path and Path(path).resolve() == _BENCH_FIXTURES:
        for name, params in TINY.items():
            plugin.TINY.setdefault(name, params)
