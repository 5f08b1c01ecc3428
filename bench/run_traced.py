#!/usr/bin/env python3
"""One traced run of a cell that reads the program's scopes and spans.

    python bench/run_traced.py --workload <cell> --seed <n> --seconds <s>
                               [--keep <file>]

The run is ``bench/run.py --trace 1``'s: the same set-up, window, check
and result line, on the chip only.  The trace is reduced by
``bench/layers.py`` instead of ``bench/trace.py``, which gives the same
``window_s``, ``busy_s`` and ``op_s`` and splits the idle time by the
program's host spans as well.  ``metrics`` holds the cell's end-to-end
metrics, read under the profiler (against a ``--trace 0`` run they give
what tracing costs), its per-layer metrics and those of
``LAYER_METRICS`` that find something to read; the line adds the
reduction's ``scope_s`` and ``span_s``.  ``--keep`` copies the trace
file (``.xplane.pb``) there.  The run keeps its compiled programs in a
compile cache of its own, so its set-up compiles what ``run.py`` may
load from a shared one.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402  (puts the program on the path)
from bench import layers, trace  # noqa: E402

#: per-layer metrics that read the program's own scopes and spans
LAYER_METRICS = ("runner_build_ms", "edge_gather_ms", "edge_reduce_ms",
                 "frontier_ms", "schedule_ms")


def measure(cell, graph, config, queries, seconds, compile_log,
            keep=None):
    """The driver's window under the profiler, reduced by ``layers``."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    wall0 = time.time()
    done, elapsed = cell.driver.loop(cell.workload, graph, config, queries,
                                     seconds)
    compiles = compile_log.count(wall0, time.time())
    jax.profiler.stop_trace()
    path = next(Path(trace_dir).rglob("*.xplane.pb"))
    reduced = layers.reduce(trace.load(path), path)
    if keep:
        Path(keep).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, keep)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return run.Window(done, elapsed, compiles, reduced)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    cell = run.load_cell(args.workload)
    try:
        devices = run.require_chip(cell.workload["chips"])
    except run.ChipMissing as e:
        print(f"bench/run_traced.py: {e}", file=sys.stderr)
        return 3

    import jax

    run.enable_cache()
    # The device scopes live in the executable's HLO metadata, which JAX
    # leaves out of its persistent-cache key: a shared cache would serve
    # executables that a program with other scopes, or none, compiled.
    # This run's set-up compiles into a cache of its own.
    cache_dir = tempfile.mkdtemp(prefix="bench-cache-")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compile_log = run.CompileLog()
    jax.monitoring.register_event_time_span_listener(compile_log)
    try:
        split = {"start_s": time.perf_counter() - run.T_START}
        edges, graph, config, queries = run.set_up(cell, args.seed, split)
        setup_s = time.perf_counter() - run.T_START
        window = measure(cell, graph, config, queries, args.seconds,
                         compile_log, args.keep)
    finally:
        jax.monitoring.unregister_event_time_span_listener(compile_log)
        shutil.rmtree(cache_dir, ignore_errors=True)
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    failed, checks, work = run.check(cell, edges, window)
    window = dataclasses.replace(window, work=work, setup_s=setup_s)
    attempted = len(window.queries)
    readers = {**cell.end_to_end, **cell.per_layer,
               **{m: run.load_module("metrics", m) for m in LAYER_METRICS}}
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(window)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    result = {"correct": attempted > 0 and failed == 0,
              "attempted": attempted, "failed": failed, "metrics": metrics,
              "device": device}
    if window.trace is not None:
        device["busy_s"] = window.trace["busy_s"]
        device["window_s"] = window.trace["window_s"]
        result["breakdown"] = trace.breakdown(window.trace)
        result["scope_s"] = window.trace["scope_s"]
        result["span_s"] = window.trace["span_s"]
    result["setup_split"] = split
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
