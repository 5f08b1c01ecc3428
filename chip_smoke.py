#!/usr/bin/env python3
"""Bring-up check: the graph-analytics main path on one TPU chip.

    python chip_smoke.py             # on a machine with a TPU
    python chip_smoke.py --rehearse  # the same phases at tiny sizes, CPU only

Everything runs in this one process, on inputs generated from fixed
seeds.  Every answer is checked against the numpy oracle of
``repro.algorithms.reference``; the first mismatch or fault raises.

- Phase ``run``: ``repro.core.run`` with the fused engine.  BFS under
  all 18 configs on the Table II AMZ input, then BFS, SSSP, PR and CC
  under TG0, SGR, DG1, SD1 and DD1 on a Graph500 R-MAT graph (scale 20,
  edge factor 16).  SD1 and DD1 run with ``use_pallas=True``, so both
  blocked Pallas reducers and the gathered sparse path run.
- Phase ``gateway``: a ``GraphGateway`` answers BFS, SSSP and CC tickets
  on the Table II inputs through two routes, DG1 on XLA and DD1 on the
  Pallas reducers.  Its fault and degradation counters must stay 0.

Each cell prints one JSON line of bring-up readings: sizes, config,
iterations, set-up, compile and run seconds, oracle match and the
device's peak bytes in use.  The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU (and without ``--rehearse``) the script exits non-zero
before it prints anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

#: phase sizes: (AMZ scale divisor, R-MAT scale, gateway scale divisor)
FULL = {"amz_scale": 1, "rmat_scale": 20, "gateway_scale": 1}
REHEARSE = {"amz_scale": 400, "rmat_scale": 10, "gateway_scale": 256}

RMAT_CONFIGS = (("TG0", False), ("SGR", False), ("DG1", False),
                ("SD1", True), ("DD1", True))

#: gateway tickets: (app, Table II input, route); route "xla" is DG1 on
#: XLA, route "pallas" is DD1 on the Pallas reducers
GATEWAY_TICKETS = (("BFS", "AMZ", "xla"), ("SSSP", "AMZ", "pallas"),
                   ("CC", "DCT", "xla"), ("BFS", "EML", "pallas"),
                   ("SSSP", "OLS", "xla"), ("CC", "RAJ", "pallas"),
                   ("BFS", "WNG", "xla"), ("SSSP", "DCT", "pallas"),
                   ("CC", "AMZ", "pallas"), ("BFS", "RAJ", "xla"))
ROUTES = {"xla": ("DG1", False), "pallas": ("DD1", True)}

#: gateway counters that must read 0 after the phase
CLEAN_COUNTERS = ("faulted", "quarantined", "slice_retries",
                  "solo_degraded_slices", "shed")


def _oracles():
    import numpy as np

    from repro.algorithms.reference import bfs_np, cc_np, pagerank_np, sssp_np

    def exact(ref):
        return lambda got: bool(np.array_equal(got, ref))

    def sssp(g):
        ref = sssp_np(g)
        finite = np.isfinite(ref)
        return lambda got: bool(
            np.array_equal(np.isfinite(got), finite)
            and np.allclose(got[finite], ref[finite], atol=1e-4))

    def pagerank(g):
        # ranks of a 1M-vertex graph are ~1e-6 each, so compare the
        # distributions by L1 distance (the programs' own convergence
        # norm) and each vertex relative to its rank
        ref = pagerank_np(g)
        return lambda got: bool(
            np.abs(got - ref).sum() < 1e-4
            and np.allclose(got, ref, rtol=1e-3, atol=1e-9))

    return {"BFS": lambda g: exact(bfs_np(g)), "SSSP": sssp,
            "PR": pagerank, "CC": lambda g: exact(cc_np(g))}


def _peak_bytes(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _check(rec: dict, ok: bool) -> None:
    rec["oracle_match"] = ok
    _emit(rec)
    if not ok:
        raise AssertionError(f"answer differs from the oracle: {rec}")


def phase_run(sizes: dict, dev) -> None:
    import numpy as np

    from repro.algorithms import REGISTRY
    from repro.core import ALL_CONFIGS, SystemConfig, run
    from repro.core.executor import EdgeContext
    from repro.graph import paper_graph, rmat_graph

    oracles = _oracles()

    def cell(name, g, app, config, use_pallas, check):
        t0 = time.perf_counter()
        EdgeContext.create(g, config, use_pallas=use_pallas)
        setup = time.perf_counter() - t0
        prog = REGISTRY[app]()
        t0 = time.perf_counter()
        res = run(prog, g, config, use_pallas=use_pallas)
        got = np.asarray(res.extract(prog))
        wall = time.perf_counter() - t0
        rec = {"phase": "run", "graph": name, "V": g.n_nodes,
               "E": g.n_edges, "app": app, "config": config.name,
               "use_pallas": use_pallas, "iterations": res.iterations,
               "converged": res.converged, "setup_s": setup,
               "compile_s": wall - res.seconds, "run_s": res.seconds,
               "peak_bytes_in_use": _peak_bytes(dev)}
        _check(rec, res.converged and check(got))

    t0 = time.perf_counter()
    amz = paper_graph("AMZ", scale=sizes["amz_scale"], weighted=True)
    _emit({"phase": "run", "graph": "AMZ", "generate_s":
           time.perf_counter() - t0, "V": amz.n_nodes, "E": amz.n_edges})
    check = oracles["BFS"](amz)
    for config in ALL_CONFIGS:
        cell("AMZ", amz, "BFS", config, False, check)

    t0 = time.perf_counter()
    rmat = rmat_graph(sizes["rmat_scale"], edge_factor=16, seed=1,
                      weighted=True)
    name = f"RMAT{sizes['rmat_scale']}"
    _emit({"phase": "run", "graph": name, "generate_s":
           time.perf_counter() - t0, "V": rmat.n_nodes, "E": rmat.n_edges})
    for app in ("BFS", "SSSP", "PR", "CC"):
        check = oracles[app](rmat)
        for cname, use_pallas in RMAT_CONFIGS:
            cell(name, rmat, app, SystemConfig.from_name(cname), use_pallas,
                 check)


def phase_gateway(sizes: dict, dev) -> None:
    import numpy as np

    from repro.algorithms import REGISTRY
    from repro.core import SystemConfig
    from repro.graph import paper_graph
    from repro.launch.serve import GraphGateway

    oracles = _oracles()
    graphs = {n: paper_graph(n, scale=sizes["gateway_scale"], weighted=True)
              for n in {g for _, g, _ in GATEWAY_TICKETS}}
    programs = {app: REGISTRY[app]() for app, _, _ in GATEWAY_TICKETS}
    t0 = time.perf_counter()
    with GraphGateway() as gw:
        tickets = []
        for app, gname, route in GATEWAY_TICKETS:
            cname, use_pallas = ROUTES[route]
            tickets.append(gw.submit(programs[app], graphs[gname],
                                     SystemConfig.from_name(cname),
                                     use_pallas=use_pallas))
        results = [t.result() for t in tickets]
        stats = gw.stats()
    wall = time.perf_counter() - t0
    for (app, gname, route), res in zip(GATEWAY_TICKETS, results):
        g = graphs[gname]
        got = np.asarray(res.extract(programs[app]))
        rec = {"phase": "gateway", "graph": gname, "V": g.n_nodes,
               "E": g.n_edges, "app": app, "config": res.config_name,
               "use_pallas": ROUTES[route][1],
               "iterations": res.iterations, "outcome": res.outcome,
               "peak_bytes_in_use": _peak_bytes(dev)}
        _check(rec, res.outcome == "converged" and oracles[app](g)(got))
    counters = {k: stats[k] for k in CLEAN_COUNTERS}
    _emit({"phase": "gateway", "tickets": len(results), "wall_s": wall,
           "slices": stats["slices"], **counters})
    dirty = {k: v for k, v in counters.items() if v}
    if dirty:
        raise RuntimeError(f"gateway degraded or faulted: {dirty}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same phases at tiny sizes on the CPU")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax

    dev = jax.devices()[0]
    want = "cpu" if args.rehearse else "tpu"
    if dev.platform != want:
        raise SystemExit(f"chip_smoke: needs platform {want!r}, JAX found "
                         f"{dev.platform!r}"
                         + ("" if args.rehearse else
                            "; use --rehearse on a CPU"))
    sizes = REHEARSE if args.rehearse else FULL
    for name, phase in (("run", phase_run), ("gateway", phase_gateway)):
        t0 = time.perf_counter()
        phase(sizes, dev)
        gc.collect()
        _emit({"phase": name, "phase_wall_s": time.perf_counter() - t0})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
