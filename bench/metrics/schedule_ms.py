"""Schedule: device milliseconds per query spent combining chunk
partials under DRF1 or DRFrlx (the program's ``schedule`` scope,
``bench/layers.py``)."""
from bench.layers import per_query_ms, spans

UNIT = "ms"


def read(window):
    if spans is None:
        return None
    return per_query_ms(window, "scope_s", spans.SCHEDULE)
