"""Degree-aware empirical autotuner for the blocked segment reducers.

The paper's core finding — no single best configuration; specialize per
workload — applies to kernel tiling just as it does to push/pull and
consistency: the best ``(tile_e, block_mult)`` for the blocked Pallas
reducers depends on the graph's degree distribution.  A near-regular
low-degree graph wants small edge tiles (or coarser output blocks) so
tiles are not mostly padding; a heavy-tailed graph wants large tiles so
hub blocks take few grid steps.  Gunrock-style frameworks win their
speedups from exactly this per-workload kernel-parameter selection.

Three entry points, cheapest first:

- :func:`suggest_plan` — zero-measurement heuristic from
  :func:`degree_features`; what autotune-off-but-degree-aware callers
  (``run(..., autotune="heuristic")``) use.
- :func:`tune` — the empirical sweep: benchmark a candidate grid of
  :class:`~repro.kernels.segment_reduce.TilingPlan` points (pruned by
  the degree features so the sweep stays cheap; the static default is
  always one candidate) and return the fastest measured plan.
- :func:`autotune_plan` — :func:`tune` wrapped in two cache layers:
  the process-wide :data:`~repro.core.plan_cache.PLAN_CACHE` under
  ``kind="tuned_tiling"`` (keyed by graph identity, edge order, reduce
  kind, dtype, feature width, mode and — for the gathered order — the
  slice capacity) and a **disk** cache
  (``results/autotune_cache.json``, keyed by the quantized
  :func:`degree_signature` so structurally similar graphs hit warm).
  Sweeps and repeat serving traffic therefore never re-tune.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.segment_reduce import (DEFAULT_PLAN,
                                          BlockedSegmentReducer, TilingPlan,
                                          gathered_segment_reduce)

__all__ = [
    "degree_features", "degree_signature", "candidate_plans", "suggest_plan",
    "build_reducer", "measure_plan", "tune", "autotune_plan", "TuneResult",
    "load_disk_cache", "store_disk_entry", "persist_tune_result",
    "DEFAULT_CACHE_PATH",
]

#: Where tuned plans persist across processes (CI uploads it alongside
#: the benchmark artifact).
DEFAULT_CACHE_PATH = "results/autotune_cache.json"

#: Edge orders the blocked reducer serves; "gathered" is the sparse
#: frontier path whose only tunable is ``gather_splits``.
ORDERS = ("owned", "pull", "gathered")

_MIN_TILE = 128
_MAX_TILE = 4096


def _default_cap_e(n_edges: int) -> int:
    """The executor's default sparse-gather capacity for this edge count
    (same formula as ``EdgeContext.default_sparse_capacity``)."""
    # deferred: repro.core's package __init__ imports the executor,
    # which imports this module — cyclic at module-import time
    from repro.core.frontier import ALPHA
    return min(n_edges, max(16, -(-n_edges // int(ALPHA))))


# ---------------------------------------------------------------------------
# degree-distribution features and their quantized signature
# ---------------------------------------------------------------------------
def degree_features(graph) -> Dict[str, float]:
    """Degree-distribution features that steer candidate pruning.

    Per-*block* edge counts (``diff(block_ptr)``) matter most: both
    block-binned orders (owned and CSC/pull) bin edges by destination
    block, so the same counts describe either order's tiling problem.
    Headline degree stats (mean/p95 out-degree, skew, n/m) ride along
    for the signature and the heuristic.
    """
    deg = np.asarray(graph.out_degree, np.float64)
    per_block = np.diff(np.asarray(graph.block_ptr, np.int64)).astype(
        np.float64)
    mean_deg = float(deg.mean()) if deg.size else 0.0
    std_deg = float(deg.std()) if deg.size else 0.0
    return {
        "n_nodes": int(graph.n_nodes),
        "n_edges": int(graph.n_edges),
        "block_size": int(graph.block_size),
        "n_blocks": int(per_block.size),
        "mean_out_degree": mean_deg,
        "p95_out_degree": float(np.percentile(deg, 95)) if deg.size else 0.0,
        "max_out_degree": float(deg.max()) if deg.size else 0.0,
        # coefficient of variation: ~0 for regular graphs, >1 heavy tail
        "degree_skew": std_deg / mean_deg if mean_deg else 0.0,
        "nm_ratio": graph.n_nodes / max(graph.n_edges, 1),
        "mean_edges_per_block": float(per_block.mean())
        if per_block.size else 0.0,
        "p95_edges_per_block": float(np.percentile(per_block, 95))
        if per_block.size else 0.0,
        "max_edges_per_block": float(per_block.max())
        if per_block.size else 0.0,
    }


def _log2_bucket(x: float) -> int:
    return int(round(math.log2(x))) if x > 0 else 0


def degree_signature(graph_or_features) -> str:
    """Quantized feature key for the disk cache.

    Log2-bucketed sizes and degree shape: graphs of the same generator
    family and scale quantize to the same signature, so a tuned plan
    warms structurally similar graphs without an exact-graph match.
    """
    f = (graph_or_features if isinstance(graph_or_features, dict)
         else degree_features(graph_or_features))
    return (f"v{_log2_bucket(f['n_nodes'])}"
            f"e{_log2_bucket(f['n_edges'])}"
            f"b{int(f['block_size'])}"
            f"d{_log2_bucket(max(f['mean_out_degree'], 1.0))}"
            f"p{_log2_bucket(max(f['p95_out_degree'], 1.0))}"
            f"s{_log2_bucket(1.0 + f['degree_skew'])}")


# ---------------------------------------------------------------------------
# candidate grid (degree-pruned) and the zero-measurement heuristic
# ---------------------------------------------------------------------------
def _pow2_clamp(x: float, lo: int, hi: int) -> int:
    x = max(float(x), 1.0)
    return int(min(max(2 ** round(math.log2(x)), lo), hi))


def _coarsening(feats: Dict[str, float]) -> int:
    """Largest useful output-block coarsening for these block counts.

    Coarsen while typical blocks underfill the smallest tile and at
    least two coarse blocks remain (one block means no revisit
    structure left to exploit).
    """
    mult = 1
    epb = max(feats["mean_edges_per_block"], 1.0)
    while (mult < 8 and feats["n_blocks"] // (mult * 2) >= 2
           and epb * mult < _MIN_TILE):
        mult *= 2
    return mult


def candidate_plans(graph=None, features: Optional[Dict[str, float]] = None,
                    order: str = "owned", max_candidates: int = 6,
                    cap_e: Optional[int] = None) -> Tuple[TilingPlan, ...]:
    """The degree-pruned candidate grid; the static default comes first.

    For the blocked orders the grid spans ``tile_e`` powers of two from
    half the mean per-(coarse-)block edge count up to the p95 block
    (clamped to [128, 4096]) × block coarsening {1, best}; tiles far
    above the p95 block are pure padding and tiles far below the mean
    multiply grid steps, so neither is swept.  The "gathered" order's
    only tunable is the scatter split count, pruned against ``cap_e``
    — the slice capacity the plan will actually be measured at and
    serve (defaults to the executor's default capacity).
    """
    feats = features if features is not None else degree_features(graph)
    if order == "gathered":
        cands = [DEFAULT_PLAN]
        cap = int(cap_e) if cap_e else _default_cap_e(int(feats["n_edges"]))
        for splits in (2, 4):
            if cap // splits >= 256:  # tiny slices: splitting is all overhead
                cands.append(dataclasses.replace(
                    DEFAULT_PLAN, gather_splits=splits, source="candidate"))
        return tuple(cands[:max_candidates])

    plans: List[TilingPlan] = [DEFAULT_PLAN]

    def add(**kw):
        p = TilingPlan(source="candidate", **kw)
        if p.astuple() not in {q.astuple() for q in plans}:
            plans.append(p)

    epb = max(feats["mean_edges_per_block"], 1.0)
    if order == "pull":
        # The CSC order is fully dst-sorted, so output blocks may be
        # *refined* below the base block size — smaller blocks shrink
        # every tile's scatter footprint.  Tile sizes track the
        # refined per-block edge count.  Refinement candidates come
        # first (deepest first): they are the reliable winners, so
        # they survive aggressive ``max_candidates`` truncation
        # (e.g. the CI smoke job's 2-candidate grid).
        for div in (4, 2):
            eff_bs = feats["block_size"] // div
            if eff_bs < 32 or feats["n_nodes"] // eff_bs < 2:
                continue
            sub_epb = epb / div
            for t in sorted({_pow2_clamp(sub_epb / 2, _MIN_TILE, 1024),
                             _pow2_clamp(sub_epb, _MIN_TILE, 1024)}):
                add(tile_e=t, block_div=div)
        if epb > 4 * DEFAULT_PLAN.tile_e:
            add(tile_e=_pow2_clamp(epb / 2, _MIN_TILE, _MAX_TILE))
        return tuple(plans[:max_candidates])

    # owned order: binned only at base-block granularity, so the grid
    # sweeps tile_e (mean/2 .. p95 per coarse block) x coarsening
    mults = [1]
    best_mult = _coarsening(feats)
    if best_mult > 1:
        mults.append(best_mult)
    lo = max(epb / 2, _MIN_TILE)
    hi = max(feats["p95_edges_per_block"], lo)
    for mult in mults:
        t = _pow2_clamp(lo * mult, _MIN_TILE, _MAX_TILE)
        t_hi = _pow2_clamp(hi * mult, _MIN_TILE, _MAX_TILE)
        while True:
            add(tile_e=t, block_mult=mult)
            if t >= t_hi:
                break
            t *= 2
    return tuple(plans[:max_candidates])


def suggest_plan(features: Dict[str, float],
                 order: str = "owned") -> TilingPlan:
    """Zero-measurement heuristic plan from degree features.

    Used by ``autotune="heuristic"`` runs (and as the tuner's fallback
    when measurement is disabled).  Owned order: size one edge tile to
    cover a typical (coarse) block, stretched toward the p95 block on
    heavy-tailed graphs so hub blocks take few grid steps.  Pull/CSC
    order: refine output blocks to the smallest size with healthy
    per-block edge counts — a sorted order pays nothing for finer
    blocks, and every tile's scatter footprint shrinks with them.  The
    gathered path has no degree model; it keeps its default.
    """
    if order == "gathered":
        return DEFAULT_PLAN
    epb = max(features["mean_edges_per_block"], 1.0)
    if order == "pull":
        div = 1
        while (div < 4 and features["block_size"] // (div * 2) >= 64
               and features["n_nodes"] // (features["block_size"]
                                           // (div * 2)) >= 2):
            div *= 2
        if div == 1:
            return dataclasses.replace(DEFAULT_PLAN, source="heuristic")
        return TilingPlan(
            tile_e=_pow2_clamp(epb / div, _MIN_TILE, 1024),
            block_div=div, source="heuristic")
    mult = _coarsening(features)
    target = epb * mult
    if features["degree_skew"] > 1.0:
        target = max(target, features["p95_edges_per_block"] * mult / 2)
    return TilingPlan(tile_e=_pow2_clamp(target, _MIN_TILE, _MAX_TILE),
                      block_mult=mult, source="heuristic")


# ---------------------------------------------------------------------------
# reducer construction + measurement
# ---------------------------------------------------------------------------
def build_reducer(graph, order: str,
                  plan: Optional[TilingPlan] = None) -> BlockedSegmentReducer:
    """Build the blocked reducer for one edge order under ``plan``.

    The single construction path shared by the executor and the tuner,
    so a tuned plan is realised identically in both.  ``order`` is
    "owned" (dst-block-binned by-src order — the DeNovo push path) or
    "pull" (CSC order, trivially dst-block-binned).
    """
    v = int(graph.n_nodes)
    if order == "owned":
        dst_owned = np.asarray(graph.dst)[np.asarray(graph.perm_owned)]
        return BlockedSegmentReducer.from_plan(
            dst_owned, np.asarray(graph.block_ptr), v, graph.block_size,
            plan)
    if order == "pull":
        # The CSC order is fully dst-sorted, so it is binned under ANY
        # block partition — the plan's effective block size (coarsened
        # or refined) is realised directly by sampling the per-vertex
        # row offsets at its block bounds.
        plan = plan if plan is not None else DEFAULT_PLAN
        eff_bs = plan.block_size(graph.block_size)
        n_blocks = -(-v // eff_bs)
        bounds = np.minimum(np.arange(n_blocks + 1) * eff_bs, v)
        pull_ptr = np.asarray(graph.row_ptr_in)[bounds]
        return BlockedSegmentReducer(
            np.asarray(graph.dst_in), pull_ptr, v, eff_bs,
            tile_e=plan.tile_e, plan=plan)
    raise ValueError(f"unknown blocked order {order!r}")


def _bench(fn, args, repeats: int) -> float:
    jax.block_until_ready(fn(*args))  # warmup/compile outside the timing
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_plan(graph, plan: TilingPlan, order: str = "owned",
                 kind: str = "mixed", dtype=jnp.float32, d: int = 1,
                 repeats: int = 3, cap_e: Optional[int] = None) -> float:
    """Best-of-``repeats`` seconds for one reduction under ``plan``.

    Values are seeded random, identical across candidates of one sweep
    (same shape/dtype), so measured deltas are tiling deltas.

    ``kind="mixed"`` times one sum **plus** one min per call — the
    balanced objective the executor tunes with, since a bound reducer
    serves whatever monoids the program's phases use (BFS/SSSP pull
    mins through the same instance BC/PR push sums through) and the
    MXU sum kernel and VPU min/max kernel scale differently with the
    tiling.
    """
    rng = np.random.default_rng(0)
    dtype = jnp.dtype(dtype)
    kinds = ("sum", "min") if kind == "mixed" else (kind,)
    if order == "gathered":
        cap = int(cap_e) if cap_e else _default_cap_e(int(graph.n_edges))
        ids_np = np.asarray(graph.dst)[
            rng.integers(0, max(graph.n_edges, 1), cap)].astype(np.int32)
        ids_np[rng.random(cap) < 0.1] = -1  # padding/masked slots
        shape = (cap,) if d == 1 else (cap, d)
        vals = jnp.asarray(rng.standard_normal(shape).astype(dtype))
        ids = jnp.asarray(ids_np)
        fn = jax.jit(lambda v, i: tuple(
            gathered_segment_reduce(v, i, graph.n_nodes, k, plan=plan)
            for k in kinds))
        return _bench(fn, (vals, ids), repeats)
    red = build_reducer(graph, order, plan)
    shape = (graph.n_edges,) if d == 1 else (graph.n_edges, d)
    vals = jnp.asarray(rng.standard_normal(shape).astype(dtype))
    # jitted like the executor's step: the value gather/mask fuse with
    # the kernel call, so candidates are ranked under the execution
    # semantics production actually runs (eager per-op dispatch would
    # overweight grid-step count)
    fn = jax.jit(lambda v: tuple(red.reduce(v, k) for k in kinds))
    return _bench(fn, (vals,), repeats)


# ---------------------------------------------------------------------------
# disk persistence (degree-signature keyed)
# ---------------------------------------------------------------------------
def _disk_key(sig: str, order: str, kind: str, dtype, d: int,
              cap_e: Optional[int] = None) -> str:
    # cap_e participates for the gathered order: its split winner is
    # measured against a specific slice capacity, so a plan tuned at
    # one capacity must not serve a different one (0 = blocked orders,
    # which have no capacity axis)
    return (f"{sig}|{order}|{kind}|{jnp.dtype(dtype).name}|{int(d)}"
            f"|c{int(cap_e or 0)}")


def load_disk_cache(path=DEFAULT_CACHE_PATH) -> Dict[str, dict]:
    """The persisted ``{disk_key: plan-entry}`` map ({} if absent/bad)."""
    try:
        data = json.loads(Path(path).read_text())
    except (OSError, ValueError):
        return {}
    entries = data.get("entries") if isinstance(data, dict) else None
    return entries if isinstance(entries, dict) else {}


def store_disk_entry(key: str, entry: dict,
                     path=DEFAULT_CACHE_PATH) -> None:
    """Merge one tuned entry into the JSON cache (atomic replace)."""
    path = Path(path)
    entries = load_disk_cache(path)
    entries[key] = entry
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(
        {"version": 1, "entries": entries}, indent=2, sort_keys=True))
    os.replace(tmp, path)


def persist_tune_result(result: "TuneResult", dtype=jnp.float32, d: int = 1,
                        cap_e: Optional[int] = None,
                        cache_path=...) -> str:
    """Persist one sweep's winner as the disk entry ``autotune_plan``
    recalls (same key derivation), returning that key.

    Lets a caller that already ran :func:`tune` (e.g. the benchmark,
    which records the sweep's raw measurements) seed the cache instead
    of paying a second identical sweep inside :func:`autotune_plan`.
    """
    if cache_path is ...:
        cache_path = DEFAULT_CACHE_PATH
    dkey = _disk_key(result.signature, result.order, result.kind, dtype, d,
                     cap_e)
    if cache_path is None:
        return dkey
    tile_e, block_mult, block_div, gather_splits = result.plan.astuple()
    store_disk_entry(dkey, {
        "tile_e": tile_e, "block_mult": block_mult,
        "block_div": block_div, "gather_splits": gather_splits,
        "order": result.order, "kind": result.kind,
        "signature": result.signature,
        "best_us": (result.best_seconds or 0.0) * 1e6,
        "default_us": (result.default_seconds or 0.0) * 1e6,
        "n_candidates": len(result.measurements),
    }, path=cache_path)
    return dkey


def _plan_from_entry(entry: dict) -> Optional[TilingPlan]:
    try:
        return TilingPlan(tile_e=int(entry["tile_e"]),
                          block_mult=int(entry["block_mult"]),
                          block_div=int(entry.get("block_div", 1)),
                          gather_splits=int(entry["gather_splits"]),
                          source="disk")
    except (KeyError, TypeError, ValueError):
        return None


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TuneResult:
    """What one empirical sweep measured (or recalled)."""
    plan: TilingPlan
    order: str
    kind: str
    signature: str
    #: ``[(plan, best_seconds)]`` per candidate; empty on a disk hit.
    measurements: Tuple[Tuple[TilingPlan, float], ...] = ()
    from_disk: bool = False

    @property
    def default_seconds(self) -> Optional[float]:
        for p, s in self.measurements:
            if p.astuple() == DEFAULT_PLAN.astuple():
                return s
        return None

    @property
    def best_seconds(self) -> Optional[float]:
        return min((s for _, s in self.measurements), default=None)

    @property
    def plan_seconds(self) -> Optional[float]:
        """Measured seconds of the *chosen* plan (the margin rule may
        keep the default even when a candidate measured faster)."""
        for p, s in self.measurements:
            if p.astuple() == self.plan.astuple():
                return s
        return None

    @property
    def speedup_vs_default(self) -> Optional[float]:
        """default/chosen — what binding this result's plan actually
        buys, exactly 1.0 when the margin rule kept the default (a
        within-noise raw best would otherwise overclaim)."""
        d, c = self.default_seconds, self.plan_seconds
        return d / c if d and c else None


def tune(graph, order: str = "owned", kind: str = "mixed", dtype=jnp.float32,
         d: int = 1, repeats: int = 3, max_candidates: int = 6,
         cap_e: Optional[int] = None,
         candidates: Optional[Sequence[TilingPlan]] = None,
         margin: float = 0.02) -> TuneResult:
    """Empirically sweep the candidate grid; fastest measured plan wins.

    The default plan is always swept, so on the tuner's own
    measurements the winner is never slower than the static tiling.
    A non-default candidate must additionally beat the default by more
    than ``margin`` (relative) to displace it — measurement-noise ties
    stay on the default plan rather than churning the cached/persisted
    plan for a within-noise "win".
    """
    feats = degree_features(graph)
    cands = tuple(candidates) if candidates is not None else candidate_plans(
        features=feats, order=order, max_candidates=max_candidates,
        cap_e=cap_e)
    measured = []
    for plan in cands:
        secs = measure_plan(graph, plan, order=order, kind=kind, dtype=dtype,
                            d=d, repeats=repeats, cap_e=cap_e)
        measured.append((plan, secs))
    best_plan, best_secs = min(measured, key=lambda ps: ps[1])
    default_secs = next((s for p, s in measured
                         if p.astuple() == DEFAULT_PLAN.astuple()), None)
    if (default_secs is not None
            and default_secs <= best_secs * (1.0 + margin)):
        best_plan = DEFAULT_PLAN
    if best_plan.astuple() != DEFAULT_PLAN.astuple():
        best_plan = dataclasses.replace(best_plan, source="tuned")
    return TuneResult(plan=best_plan, order=order, kind=kind,
                      signature=degree_signature(feats),
                      measurements=tuple(measured))


def autotune_plan(graph, order: str = "owned", kind: str = "mixed",
                  dtype=jnp.float32, d: int = 1, mode: str = "measure",
                  repeats: int = 3, max_candidates: int = 6,
                  cap_e: Optional[int] = None,
                  cache_path=...) -> TilingPlan:
    """The cached tuner the executor calls.

    Resolution order: process-wide ``PLAN_CACHE`` (``tuned_tiling``
    entry keyed by graph identity + (order, kind, dtype, d, mode)) →
    disk cache (``cache_path``, keyed by :func:`degree_signature`) →
    empirical :func:`tune` sweep, whose winner is persisted to disk.
    ``mode="heuristic"`` skips both measurement and disk and returns
    :func:`suggest_plan` (still process-cached).

    ``cache_path`` defaults to the *current* :data:`DEFAULT_CACHE_PATH`
    (resolved at call time, so tests can repoint it); pass ``None`` to
    disable disk persistence entirely.
    """
    if cache_path is ...:
        cache_path = DEFAULT_CACHE_PATH
    if mode not in ("heuristic", "measure"):
        raise ValueError(f"unknown autotune mode {mode!r}; "
                         "expected 'heuristic' or 'measure'")
    # deferred: repro.core's package __init__ imports the executor,
    # which imports this module — a module-level import would be cyclic
    from repro.core.plan_cache import PLAN_CACHE
    # cache_path participates in the key so alternate caches (tests,
    # ad-hoc sweeps) can't serve each other's plans for one live graph;
    # cap_e because a gathered plan is only valid for the capacity it
    # was measured at
    key = (order, kind, jnp.dtype(dtype).name, int(d), mode,
           str(cache_path), int(cap_e or 0))

    def build() -> TilingPlan:
        if mode == "heuristic":
            return suggest_plan(degree_features(graph), order=order)
        sig = degree_signature(graph)
        dkey = _disk_key(sig, order, kind, dtype, d, cap_e)
        if cache_path is not None:
            plan = _plan_from_entry(load_disk_cache(cache_path).get(dkey, {}))
            if plan is not None:
                return plan
        result = tune(graph, order=order, kind=kind, dtype=dtype, d=d,
                      repeats=repeats, max_candidates=max_candidates,
                      cap_e=cap_e)
        try:
            persist_tune_result(result, dtype=dtype, d=d, cap_e=cap_e,
                                cache_path=cache_path)
        except OSError:
            # The disk cache is an optimization: a fresh checkout
            # creates results/ on first write (store_disk_entry mkdirs
            # defensively), but an unwritable path — e.g. "results"
            # existing as a plain file, or a read-only serving image —
            # must cost the persistence, never the run.
            pass
        return result.plan

    return PLAN_CACHE.get(graph, "tuned_tiling", key, build)
