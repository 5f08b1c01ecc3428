#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``.  Every piece it names is a
file found by that name: its configuration (``bench/configs/<config>.json``,
whose ``generator`` is ``bench/generators/<generator>.py``), its query
rule (``bench/queries/<queries>.py``), its driver
(``bench/drivers/<driver>.py``), its plain reference
(``bench/reference/<reference>.py``) and the reader of each of its metrics
(``bench/metrics/<metric>.py``).  A run:

1. refuses to go on unless JAX finds a TPU, and as many chips as the cell
   asks for (no result is printed then);
2. turns on JAX's persistent compilation cache;
3. generates the configuration's graph with the benchmark's generator,
   from the configuration's fixed ``graph_seed`` (every run holds the same
   graph, so the same array sizes and compiled programs);
4. ingests it through ``Graph.from_coo`` and ``EdgeContext.create``;
5. answers one query outside the window, to warm up;
6. lets the driver answer queries drawn from ``--seed`` for ``--seconds``;
7. checks every answer the window timed against the plain reference;
8. prints one JSON line: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``),
   ``setup_split`` (the seconds of each set-up step), then ``checks``,
   each compared number beside its limit, which also end standard
   error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records the window with the profiler and reports its per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
for _path in (str(REPO), str(REPO / "src")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from bench.records import Query, Window  # noqa: E402,F401

#: JAX's event for each backend compile, a persistent-cache load included
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class ChipMissing(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    generator: object
    queries: object
    driver: object
    reference: object
    end_to_end: dict
    per_layer: dict


def load_cell(name: str) -> Cell:
    workload = load_json("workloads", name)
    config = load_json("configs", workload["config"])
    reference = load_module("reference", workload["reference"])
    if reference.WORK_RULE != workload["work"]:
        raise ValueError(f"{name}: reference counts {reference.WORK_RULE!r},"
                         f" the cell states {workload['work']!r}")
    readers = {kind: {m: load_module("metrics", m)
                      for m in workload["metrics"][kind]}
               for kind in ("end_to_end", "per_layer")}
    return Cell(workload, config,
                load_module("generators", config["generator"]),
                load_module("queries", workload["queries"]),
                load_module("drivers", workload["driver"]), reference,
                readers["end_to_end"], readers["per_layer"])


def require_chip(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise ChipMissing(f"needs {chips} TPU chip(s); JAX found "
                          f"{len(devices)} {devices[0].platform!r} device(s)")
    return devices


def enable_cache() -> None:
    """JAX's persistent compilation cache, in the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names another directory.  Every program
    goes to it, however fast it compiled, so that a later run of the cell
    compiles nothing."""
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CompileLog:
    """Wall-clock start of every backend compile (persistent-cache loads
    included), from JAX's monitoring events."""

    def __init__(self):
        self.starts = []

    def __call__(self, event, start, end, **kwargs):
        if event == COMPILE_EVENT:
            self.starts.append(start)

    def count(self, lo: float, hi: float) -> int:
        return sum(1 for s in self.starts if lo <= s <= hi)


def build(cell: Cell, split: dict):
    """Generate the cell's graph and ingest it through the program's own
    path; returns ``(edges, graph, config)`` and adds each step's seconds
    to ``split``."""
    from repro.core import EdgeContext, SystemConfig
    from repro.graph import Graph

    def lap(step):
        now = time.perf_counter()
        split[step] = now - lap.t
        lap.t = now
    lap.t = time.perf_counter()

    w = cell.workload
    edges = cell.generator.generate(cell.config["params"],
                                    cell.config["graph_seed"])
    lap("generate_s")
    ingest = cell.config["ingest"]
    graph = Graph.from_coo(edges["src"], edges["dst"], edges["n"],
                           weight=edges["weight"],
                           symmetrize=ingest["symmetrize"],
                           remove_self_loops=ingest["remove_self_loops"],
                           block_size=ingest["block_size"])
    lap("from_coo_s")
    config = SystemConfig.from_name(w["system_config"])
    EdgeContext.create(graph, config, use_pallas=w["use_pallas"])
    lap("context_s")
    return edges, graph, config


def draw_and_warm(cell: Cell, edges: dict, graph, config, seed: int):
    """The query stream drawn from ``seed``, past its first query, which
    is answered here to warm up."""
    queries = cell.queries.draw(edges, seed)
    cell.driver.answer(cell.workload, graph, config, next(queries))
    return queries


def set_up(cell: Cell, seed: int, split: dict):
    """Build the graph, draw the query stream from ``seed`` and warm up;
    returns ``(edges, graph, config, queries)``."""
    edges, graph, config = build(cell, split)
    t0 = time.perf_counter()
    queries = draw_and_warm(cell, edges, graph, config, seed)
    split["warm_up_s"] = time.perf_counter() - t0
    return edges, graph, config, queries


def measure(cell: Cell, graph, config, queries, seconds: float,
            compile_log: CompileLog, trace: bool) -> Window:
    """The driver's window; with ``trace`` under the profiler."""
    import jax
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    wall0 = time.time()
    done, elapsed = cell.driver.loop(cell.workload, graph, config, queries,
                                     seconds)
    compiles = compile_log.count(wall0, time.time())
    reduced = None
    if trace:
        from bench import trace as device_trace
        jax.profiler.stop_trace()
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        reduced = device_trace.reduce(device_trace.load(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(done, elapsed, compiles, reduced)


def check(cell: Cell, edges: dict, window: Window):
    """Compare every answer with the plain reference; returns
    ``(failed, checks, work)``: queries that fail a limit or did not
    converge, each number's worst reading beside its limit, and the
    work the answered queries did by the cell's counting rule."""
    ref_mod = cell.reference
    ref = ref_mod.prepare(edges)
    wanted = {}
    worst = {k: 0 for k in ref_mod.LIMITS}
    worst["unconverged"] = 0
    failed, work = 0, 0
    for q in window.queries:
        key = q.query
        if key not in wanted:
            want = ref_mod.solve(ref, key)
            wanted[key] = (want, ref_mod.work(ref, key, want))
        want, w = wanted[key]
        work += w
        readings = ref_mod.compare(q.answer, want)
        bad = not q.converged
        worst["unconverged"] += int(bad)
        for k, v in readings.items():
            worst[k] = max(worst[k], v)
            bad |= not v <= ref_mod.LIMITS[k]
        failed += int(bad)
    limits = {**ref_mod.LIMITS, "unconverged": 0}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in worst.items()}
    return failed, checks, work


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        devices = require_chip(cell.workload["chips"])
    except ChipMissing as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3

    import jax

    enable_cache()
    compile_log = CompileLog()
    jax.monitoring.register_event_time_span_listener(compile_log)
    try:
        split = {"start_s": time.perf_counter() - T_START}
        edges, graph, config, queries = set_up(cell, args.seed, split)
        setup_s = time.perf_counter() - T_START
        window = measure(cell, graph, config, queries, args.seconds,
                         compile_log, bool(args.trace))
    finally:
        jax.monitoring.unregister_event_time_span_listener(compile_log)
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    failed, checks, work = check(cell, edges, window)
    window = dataclasses.replace(window, work=work, setup_s=setup_s)
    attempted = len(window.queries)
    result = {"correct": attempted > 0 and failed == 0,
              "attempted": attempted, "failed": failed}
    readers = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(window)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and window.trace is not None:
        from bench import trace as device_trace
        device["busy_s"] = window.trace["busy_s"]
        device["window_s"] = window.trace["window_s"]
        result["breakdown"] = device_trace.breakdown(window.trace)
    result["setup_split"] = split
    result["checks"] = checks
    print(f"set-up: {json.dumps(split)}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
