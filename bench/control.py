#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program's and the
control's, on the chip, at the cell's own size.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

One process builds the graph once, as ``bench/run.py`` does.  For each
seed it then draws the queries, warms up and answers a window of them,
and reads each compared number for the program's answers and for the
reference's control on the same queries (the plain reference with one
guarantee broken, or computed in the precision below the program's).
One JSON line per seed:
``{"seed", "queries", "program": {...}, "control": {...}}`` with the
worst reading of each number.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import run  # noqa: E402


def readings(cell: run.Cell, edges: dict, window: run.Window) -> dict:
    ref_mod = cell.reference
    ref = ref_mod.prepare(edges)
    sides = {"program": {}, "control": {}}
    for key in {q.query for q in window.queries}:
        want = ref_mod.solve(ref, key)
        answers = [("program", q.answer) for q in window.queries
                   if q.query == key]
        answers.append(("control", ref_mod.control(ref, key)))
        for side, a in answers:
            for k, v in ref_mod.compare(a, want).items():
                sides[side][k] = max(sides[side].get(k, v), v)
    return {"queries": len(window.queries), **sides}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    try:
        run.require_chip(cell.workload["chips"])
    except run.ChipMissing as e:
        print(f"bench/control.py: {e}", file=sys.stderr)
        return 3
    run.enable_cache()
    log = run.CompileLog()
    edges, graph, config = run.build(cell, {})
    for seed in args.seeds:
        queries = run.draw_and_warm(cell, edges, graph, config, seed)
        window = run.measure(cell, graph, config, queries, args.seconds,
                             log, trace=False)
        print(json.dumps({"seed": seed,
                          **readings(cell, edges, window)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
