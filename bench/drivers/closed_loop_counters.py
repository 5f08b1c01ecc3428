"""``closed_loop``'s one caller in a closed loop, with the program's own
counters carried into each answered query.

Each query is built, run and read back exactly as ``closed_loop`` does;
the query record adds ``RunResult.counters`` (``None`` where the program
declares no counters, or where the system under test has no such
field).  A later change to the benchmark should fold this into
``closed_loop.py``, so that one driver serves every cell.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from bench.records import Query


@dataclasses.dataclass
class CountedQuery(Query):
    """An answered query and the counters its program declared."""
    counters: Optional[dict] = None


def answer(workload: dict, graph, config, query) -> CountedQuery:
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
    return CountedQuery(query, got, res.iterations, res.converged,
                        res.occupancy_trace,
                        getattr(res, "counters", None))


def loop(workload: dict, graph, config, queries, seconds: float):
    """Queries back to back until ``seconds`` have passed; the window ends
    when the last one has its answer.  Returns ``(answered, seconds)``."""
    done = []
    t0 = time.perf_counter()
    while True:
        done.append(answer(workload, graph, config, next(queries)))
        if time.perf_counter() - t0 >= seconds:
            return done, time.perf_counter() - t0
