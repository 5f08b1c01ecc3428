"""Benchmark harness entry point — one section per paper artifact.

Prints ``name,us_per_call,derived`` CSV rows; detailed JSON lands in
results/.  Fast subsets by default so `python -m benchmarks.run` finishes
on one CPU; pass --full for the complete Fig. 5 grid.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.fig5 import run_fig5
from benchmarks.fig6 import run_fig6
from benchmarks.table2 import run_table2
from benchmarks.table5 import run_table5
from repro.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full 6x6 Fig.5 grid (slow); default is a "
                         "representative subset")
    ap.add_argument("--scale", type=int, default=32)
    ap.add_argument("--json", action="store_true",
                    help="additionally run the host-vs-fused engine "
                         "benchmark and write machine-readable "
                         "results/BENCH_dispatch.json (per-engine "
                         "us/iteration for the pinned RMAT workload "
                         "across the design-space configs)")
    ap.add_argument("--dispatch-only", action="store_true",
                    help="with --json: skip the paper-artifact sections "
                         "and only write BENCH_dispatch.json (CI uses "
                         "this to track the perf trajectory cheaply)")
    ap.add_argument("--autotune-only", action="store_true",
                    help="only run the reducer-autotuner benchmark and "
                         "write results/BENCH_autotune.json (tuned-vs-"
                         "default us/iteration across the 18 configs on "
                         "three degree profiles)")
    ap.add_argument("--autotune-smoke", action="store_true",
                    help="with --autotune-only: tiny graphs + 2-candidate "
                         "grid (the CI smoke job)")
    ap.add_argument("--batch-only", action="store_true",
                    help="only run the batched-serving benchmark and "
                         "write results/BENCH_batch.json (batched vs "
                         "sequential us/graph across batch sizes and the "
                         "18 configs)")
    ap.add_argument("--batch-smoke", action="store_true",
                    help="with --batch-only: tiny graphs, B<=4 (the CI "
                         "smoke job)")
    ap.add_argument("--serve-only", action="store_true",
                    help="only run the streaming-gateway load benchmark "
                         "and write results/BENCH_serve.json (continuous "
                         "batching vs serve-one-at-a-time throughput and "
                         "latency under closed- and open-loop arrivals)")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="with --serve-only: tiny pool, 64 requests (the "
                         "CI smoke job)")
    ap.add_argument("--resilience-only", action="store_true",
                    help="only run the checkpoint-overhead / fault-"
                         "recovery benchmark and write results/"
                         "BENCH_resilience.json (checkpointed-vs-plain "
                         "fused us/iteration across the 18 configs, "
                         "bit-identity, and warm-ring vs cold-restart "
                         "recovery from an injected NaN)")
    ap.add_argument("--resilience-smoke", action="store_true",
                    help="with --resilience-only: tiny graph, 3 repeats "
                         "(the CI smoke job)")
    ap.add_argument("--chaos-only", action="store_true",
                    help="only run the kill-and-restart chaos benchmark "
                         "and write results/BENCH_chaos.json (crash "
                         "recovery from durable checkpoints and the "
                         "gateway write-ahead journal: recovery seconds, "
                         "lost-work ratio, overload shed rate, end-state "
                         "bit-identity)")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="with --chaos-only: tiny graphs (the CI smoke "
                         "job)")
    ap.add_argument("--matrix-only", action="store_true",
                    help="only run the 6-app x 6-input workload matrix "
                         "and write results/BENCH_matrix.json (per-cell "
                         "seconds across the design-space configs plus "
                         "each workload's specialization gain over TG0)")
    ap.add_argument("--matrix-smoke", action="store_true",
                    help="with --matrix-only: tiny stand-ins, reduced "
                         "config set (the CI smoke job)")
    ap.add_argument("--specialize-only", action="store_true",
                    help="only train + evaluate the learned best-config "
                         "specializer on results/BENCH_matrix.json "
                         "(run --matrix-only first), refreshing results/"
                         "specialize_model.json and writing results/"
                         "BENCH_specialize.json (accuracy vs measured "
                         "best and e2e geomean vs always-X baselines)")
    ap.add_argument("--specialize-smoke", action="store_true",
                    help="with --specialize-only: expect a --smoke "
                         "matrix artifact (the CI smoke job)")
    args = ap.parse_args()
    enable_compile_cache()

    print("name,us_per_call,derived")

    if args.matrix_only:
        from benchmarks.matrix import run_matrix
        run_matrix(smoke=args.matrix_smoke)
        return

    if args.specialize_only:
        from benchmarks.specialize import run_specialize
        run_specialize(smoke=args.specialize_smoke)
        return

    if args.autotune_only:
        from benchmarks.autotune import run_autotune
        run_autotune(smoke=args.autotune_smoke,
                     repeats=2 if args.autotune_smoke else 5)
        return

    if args.batch_only:
        from benchmarks.batch import run_batch_bench
        run_batch_bench(smoke=args.batch_smoke)
        return

    if args.serve_only:
        from benchmarks.serve import run_serve_bench
        run_serve_bench(smoke=args.serve_smoke)
        return

    if args.resilience_only:
        from benchmarks.resilience import run_resilience_bench
        run_resilience_bench(smoke=args.resilience_smoke)
        return

    if args.chaos_only:
        from benchmarks.chaos import run_chaos_bench
        run_chaos_bench(smoke=args.chaos_smoke)
        return

    if args.json or args.dispatch_only:  # --dispatch-only implies --json
        from benchmarks.dispatch import run_dispatch
        run_dispatch()
        if args.dispatch_only:
            return

    t0 = time.perf_counter()
    rows = run_table2()
    dt = (time.perf_counter() - t0) / max(len(rows), 1)
    n_class_ok = sum(
        r["computed_from_published"]["vol_class"]
        == r["published"]["vol_class"] for r in rows)
    print(f"table2_profile,{dt*1e6:.0f},vol_class_match={n_class_ok}/6")

    graphs = None if args.full else ["DCT", "RAJ", "OLS", "WNG"]
    apps = None if args.full else ["PR", "SSSP", "BFS", "MIS", "CLR", "CC"]
    t0 = time.perf_counter()
    fig5 = run_fig5(scale=args.scale, graphs=graphs, apps=apps)
    n_cells = len(fig5)
    dt = (time.perf_counter() - t0) / max(n_cells, 1)
    n_best_not_ref = sum(1 for v in fig5.values()
                         if v["best"] not in ("TG0", "DG1"))
    # dynamic cells whose frontier heuristic used BOTH directions in one
    # run — the per-iteration switching the D configs exist for
    n_mixed = sum(
        1 for v in fig5.values() for c, d in v["configs"].items()
        if c.startswith("D") and "S" in d.get("directions", "")
        and "T" in d.get("directions", ""))
    # dynamic cells where >=1 push iteration ran the O(m_f) sparse-
    # gathered path instead of the dense O(E) masked scan
    n_sparse_cells = sum(
        1 for v in fig5.values() for c, d in v["configs"].items()
        if c.startswith("D") and d.get("n_sparse", 0))
    print(f"fig5_sweep,{dt*1e6:.0f},cells={n_cells};"
          f"best_differs_from_ref={n_best_not_ref};"
          f"dyn_mixed_direction_cells={n_mixed};"
          f"dyn_sparse_gather_cells={n_sparse_cells}")

    t0 = time.perf_counter()
    t5 = run_table5(scale=args.scale)
    dt = time.perf_counter() - t0
    print(f"table5_model,{dt*1e6:.0f},"
          f"paper_faithful={t5['paper_faithful']['match_table_v']};"
          f"deployed_hits={t5['deployed_exact_hits']}")

    t0 = time.perf_counter()
    f6 = run_fig6()
    dt = time.perf_counter() - t0
    print(f"fig6_flexibility,{dt*1e6:.0f},cases={f6['n_cases']};"
          f"avg_reduction={f6['avg_reduction_pct']}%")

    # roofline (requires dry-run artifacts; skipped gracefully otherwise)
    try:
        from benchmarks.roofline import analyze
        src = "results/dryrun_opt" if Path("results/dryrun_opt").exists() \
            else "results/dryrun"
        rows = analyze(dryrun_dir=src)
        if rows:
            worst = min(rows, key=lambda r: r["roofline_fraction"] or 1)
            print(f"roofline,{len(rows)},cells={len(rows)};"
                  f"worst_fraction={worst['roofline_fraction']}"
                  f"@{worst['arch']}/{worst['shape']}")
        else:
            print("roofline,0,no_dryrun_artifacts")
    except Exception as exc:  # pragma: no cover
        print(f"roofline,0,error={exc}")


if __name__ == "__main__":
    main()
