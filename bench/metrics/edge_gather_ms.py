"""Edge messages: device milliseconds per query spent forming messages
from endpoint state (the program's ``edge_gather`` scope,
``bench/layers.py``)."""
from bench.layers import per_query_ms, spans

UNIT = "ms"


def read(window):
    if spans is None:
        return None
    return per_query_ms(window, "scope_s", spans.EDGE_GATHER)
