"""Edge reduction: device milliseconds per query spent reducing messages
into vertices, by XLA segment ops or the Pallas reducers (the program's
``edge_reduce`` scope, ``bench/layers.py``)."""
from bench.layers import per_query_ms, spans

UNIT = "ms"


def read(window):
    if spans is None:
        return None
    return per_query_ms(window, "scope_s", spans.EDGE_REDUCE)
