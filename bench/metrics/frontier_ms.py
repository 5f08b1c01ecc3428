"""Direction and frontier: device milliseconds per query spent choosing
the direction and building the push branch's sparse frontier (the
program's ``direction`` and ``frontier`` scopes, ``bench/layers.py``)."""
from bench.layers import per_query_ms, spans

UNIT = "ms"


def read(window):
    if spans is None:
        return None
    return per_query_ms(window, "scope_s", spans.FRONTIER, spans.DIRECTION)
