"""One caller in a closed loop: each query built as a user builds it
(``repro.algorithms.<program>(<query_arg>=query)``), run through
``repro.core.run`` on the fused engine, and its answer read back to the
host before the next query starts."""
from __future__ import annotations

import time

import numpy as np

from bench.records import Query


def answer(workload: dict, graph, config, query) -> Query:
    import jax
    import repro.algorithms as algorithms
    from repro.core import run

    kwargs = ({} if workload["query_arg"] is None
              else {workload["query_arg"]: query})
    with jax.profiler.TraceAnnotation("build_query"):
        program = getattr(algorithms, workload["program"])(**kwargs)
    with jax.profiler.TraceAnnotation("run"):
        res = run(program, graph, config, use_pallas=workload["use_pallas"],
                  engine="fused")
    with jax.profiler.TraceAnnotation("readback"):
        got = np.asarray(res.extract(program))
    return Query(query, got, res.iterations, res.converged,
                 res.occupancy_trace)


def loop(workload: dict, graph, config, queries, seconds: float):
    """Queries back to back until ``seconds`` have passed; the window ends
    when the last one has its answer.  Returns ``(answered, seconds)``."""
    done = []
    t0 = time.perf_counter()
    while True:
        done.append(answer(workload, graph, config, next(queries)))
        if time.perf_counter() - t0 >= seconds:
            return done, time.perf_counter() - t0
