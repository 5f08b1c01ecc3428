"""Loop driver, building the runner: host milliseconds per query spent
tracing the fused runner to a jaxpr and lowering and compiling it, a
persistent-cache load included (the program's ``repro.trace`` and
``repro.compile`` spans, ``bench/layers.py``)."""
from bench.layers import per_query_ms, spans

UNIT = "ms"


def read(window):
    if spans is None:
        return None
    return per_query_ms(window, "span_s", spans.TRACE, spans.COMPILE)
