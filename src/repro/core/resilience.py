"""Execution-core resilience: checkpointed runs, sentinels, recovery.

The fused engine (PR 3) buys its speed by putting the *entire*
convergence loop inside one ``lax.while_loop`` dispatch — which also
means a NaN, a divergent iteration or a runner exception loses the
whole run.  This module segments that loop into bounded fused
dispatches and wraps them in the recovery machinery the ROADMAP's
"handles as many scenarios as you can imagine" leg asks for:

- **Checkpointed execution** — ``run(..., checkpoint_every=K)`` drives
  the *same* compiled loop body in K-iteration fused segments (the
  segment end is a traced operand, so ONE compiled executable serves
  every segment) and snapshots the carry into a bounded host-side
  :class:`CheckpointRing` at each boundary.  Segmenting never changes
  the per-iteration math, so checkpointed runs are bit-identical to
  the unsegmented fused engine.
- **Invariant sentinels** — evaluated on-device inside the segment
  dispatch, comparing the segment's end state against its start
  (= the last checkpoint): a NaN guard over float state, monotonicity
  monitors for MIN/MAX-monoid fixpoints (the exact property DRFrlx's
  reorderable combine relies on — and transitive, so a K-iteration
  boundary check is as strong as per-iteration), program-declared
  custom sentinels (:attr:`VertexProgram.sentinels`), and a
  frontier-occupancy sanity check over the segment's trace window.
  ``max_iters`` exhaustion becomes the structured ``"iter_limit"``
  outcome rather than a silent non-answer.
- **Fixpoint certificates** — a converged state is additionally proved
  with one O(E) :attr:`VertexProgram.certificate` propagate.  This is
  what catches dropped-update staleness: a vertex reverted to the
  value it already had at the last checkpoint is invisible to every
  boundary sentinel, but cannot satisfy the fixpoint equations.
- **Recovery** — :class:`RetryPolicy` rolls back to a clean checkpoint
  and re-executes; each retry rolls back one checkpoint deeper (a
  corruption that slipped past the boundary checks is healed by
  resuming from an older snapshot) and walks a degradation chain:
  retry-as-is → autotuned tiling → default plans → sparse frontier →
  dense → fused engine → host engine.  Exhausted attempts return a
  structured ``outcome="faulted"`` :class:`~repro.core.executor.
  RunResult` carrying the fault history — never a silently wrong
  state.

The gateway (:mod:`repro.launch.serve`) reuses the host-side pieces:
:func:`check_state_host` between scheduling slices and
:func:`check_certificate` at convergence, quarantining only the
offending slot.  :mod:`repro.testing.faults` subclasses
:class:`FaultInjector` to drive all of this under seeded fault
injection.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config_space import SystemConfig, UpdateProp
from repro.core.executor import (EdgeContext, RunResult, STATS,
                                 _cached_exec_fn, _jit_hoisted,
                                 _normalize_autotune, _trace_flags)
from repro.core.vertex_program import (DENSE_OCC, FRONTIER_DIR_KEY,
                                       FRONTIER_OCC_KEY, VertexProgram,
                                       dense_occupancy)
from repro.graph.structure import Graph

__all__ = ["Checkpoint", "CheckpointRing", "RetryPolicy", "ExecutionFault",
           "FaultInjector", "run_resilient", "build_sentinels",
           "check_state_host", "check_certificate",
           "DEFAULT_CHECKPOINT_EVERY", "DEFAULT_RING_CAPACITY"]

#: Default segment length for ``checkpoint_every=True``-style callers
#: (benchmarks, gateway).  Most pinned workloads converge in a couple
#: of segments at this interval, so the boundary cost (one host
#: snapshot + one sentinel reduction per segment) stays <5% of run
#: time while still bounding the work a fault can lose.
DEFAULT_CHECKPOINT_EVERY = 32

#: Default :class:`CheckpointRing` capacity: the pinned initial
#: snapshot plus the three newest boundaries.
DEFAULT_RING_CAPACITY = 4


class ExecutionFault(RuntimeError):
    """Structured execution failure: ``code`` plus a detail dict.

    Raised from :meth:`repro.launch.serve.Ticket.result` for
    quarantined gateway slots and carried in ``RunResult.fault`` for
    ``outcome="faulted"`` runs.
    """

    def __init__(self, code: str, detail: Optional[dict] = None):
        self.code = code
        self.detail = dict(detail or {})
        super().__init__(f"{code}: {self.detail}" if self.detail else code)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Recovery policy for :func:`run_resilient`.

    ``max_attempts`` counts total executions (the first try included);
    ``backoff_s`` sleeps ``backoff_s * attempt`` seconds before retry
    ``attempt`` (0 disables).  Retry ``a`` rolls back ``a`` checkpoints
    (clamped to the ring's pinned initial snapshot) and runs the
    ``a``-th rung of the degradation chain, so repeated failures both
    resume from progressively older clean state *and* shed the
    specializations most likely to be implicated.
    """
    max_attempts: int = 3
    backoff_s: float = 0.0


@dataclasses.dataclass
class Checkpoint:
    """One carry snapshot: host-side state plus loop/trace position."""
    it: int
    done: bool
    state: Any                          # host numpy pytree
    dir_buf: Optional[np.ndarray]       # [limit] bool, traced programs
    occ_buf: Optional[np.ndarray]       # [limit] float32, occ-traced


class CheckpointRing:
    """Bounded checkpoint store: the pinned *initial* snapshot plus the
    ``capacity - 1`` newest segment boundaries.

    Pinning the first snapshot means recovery can always fall back to a
    full restart even after the ring has wrapped — ``capacity=1``
    degenerates to exactly cold-restart semantics (the benchmark's
    recovery baseline).
    """

    def __init__(self, capacity: int = DEFAULT_RING_CAPACITY):
        if capacity < 1:
            raise ValueError(
                f"CheckpointRing capacity must be >= 1, got "
                f"capacity={capacity}")
        self.capacity = capacity
        self._first: Optional[Checkpoint] = None
        self._ring: deque = deque(maxlen=capacity - 1)

    def push(self, cp: Checkpoint) -> None:
        if self._first is None:
            self._first = cp
        else:
            self._ring.append(cp)

    def latest(self) -> Checkpoint:
        if self._first is None:
            raise IndexError("empty CheckpointRing")
        return self._ring[-1] if self._ring else self._first

    def rollback(self, depth: int) -> Checkpoint:
        """Discard the ``depth`` newest snapshots (they are suspect) and
        return the new latest; clamps at the pinned initial snapshot."""
        for _ in range(depth):
            if self._ring:
                self._ring.pop()
        return self.latest()

    def __len__(self) -> int:
        return (0 if self._first is None else 1) + len(self._ring)


class FaultInjector:
    """Injection points :func:`run_resilient` exposes for the seeded
    fault harness (:mod:`repro.testing.faults`).  The base class is a
    no-op; ``knob_overrides`` lets a mode force execution knobs (e.g.
    a one-element sparse capacity to force gather overflow).
    """
    knob_overrides: dict = {}

    def on_compile(self, knobs: dict) -> None:
        """Before an attempt builds/fetches its compiled runner."""

    def before_segment(self, it: int) -> None:
        """Before each segment dispatch; raise to emulate a runner
        exception."""

    def perturb(self, it: int, state, checkpoint_state) -> Optional[Any]:
        """After a segment: return a corrupted copy of the host state
        (or None to leave it alone)."""
        return None

    # gateway-side hooks (see repro.launch.serve)
    def before_slice(self, ticket_ids: List[str]) -> None:
        """Before a gateway slice dispatch; raise to fail the slice."""

    def perturb_slot(self, ticket_id: str, state) -> Optional[Any]:
        """After a gateway slice: corrupt one slot's unpacked host
        state (or None)."""
        return None


# ----------------------------------------------------------------------
# sentinels


def build_sentinels(program: VertexProgram) -> List[tuple]:
    """The program's sentinel battery as ``[(name, (prev, cur) -> ok)]``.

    Always includes the NaN guard over float state leaves (NaN only —
    +inf is legitimate state, e.g. SSSP's unreached distance), then the
    declared monotonicity monitors, then the program's custom
    sentinels.  Every predicate is written in jnp so the same callable
    runs inside the segmented fused dispatch and eagerly on host
    snapshots.
    """
    fns: List[tuple] = []

    def nan_guard(prev, cur):
        bad = [jnp.any(jnp.isnan(leaf)) for leaf in jax.tree.leaves(cur)
               if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)]
        if not bad:
            return jnp.asarray(True)
        return ~jnp.any(jnp.stack(bad))

    fns.append(("nan", nan_guard))
    for key, order in sorted((program.monotone or {}).items()):
        if order == "non_increasing":
            fn = lambda p, c, k=key: jnp.all(c[k] <= p[k])
        elif order == "non_decreasing":
            fn = lambda p, c, k=key: jnp.all(c[k] >= p[k])
        else:
            raise ValueError(f"unknown monotone order {order!r} for "
                             f"state key {key!r}")
        fns.append((f"monotone:{key}", fn))
    for name in sorted(program.sentinels or {}):
        fns.append((name, program.sentinels[name]))
    return fns


def _sentinel_flags(sentinel_fns, prev_st, cur_st, ob, lo, hi, limit,
                    occ_traced):
    """Stacked per-sentinel health flags (True = healthy), including the
    occupancy-window check when the program traces occupancy."""
    flags = [jnp.asarray(fn(prev_st, cur_st), bool).reshape(())
             for _, fn in sentinel_fns]
    if occ_traced and ob is not None:
        idx = jnp.arange(limit)
        window = (idx >= lo) & (idx < hi)
        # a traced occupancy is either the dense sentinel or a gather
        # fill fraction in [0, 1]; NaN fails both comparisons
        valid = (ob == DENSE_OCC) | ((ob >= 0.0) & (ob <= 1.0 + 1e-5))
        flags.append(jnp.all(jnp.where(window, valid, True)))
    if not flags:
        return jnp.ones((0,), bool)
    return jnp.stack(flags)


def _sentinel_names(sentinel_fns, occ_traced) -> List[str]:
    return [n for n, _ in sentinel_fns] + (["occupancy"] if occ_traced
                                           else [])


def check_state_host(program: VertexProgram, prev, cur) -> List[str]:
    """Pure-numpy evaluation of the built-in guards (NaN + declared
    monotonicity) on host state snapshots; returns tripped names.

    This is the gateway's per-slice fast path — no device dispatch, so
    it can run per slot per slice without perturbing serving latency.
    Custom jnp sentinels and certificates run at segment boundaries /
    convergence instead.
    """
    tripped: List[str] = []
    leaves = (list(cur.values()) if isinstance(cur, dict)
              else jax.tree.leaves(cur))
    for leaf in leaves:
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating) and np.isnan(a).any():
            tripped.append("nan")
            break
    for key, order in sorted((program.monotone or {}).items()):
        p, c = np.asarray(prev[key]), np.asarray(cur[key])
        if order == "non_increasing":
            if np.any(c > p):
                tripped.append(f"monotone:{key}")
        elif np.any(c < p):
            tripped.append(f"monotone:{key}")
    return tripped


def check_certificate(program: VertexProgram, ctx: EdgeContext,
                      state) -> Optional[bool]:
    """Evaluate the program's converged-state fixpoint certificate.

    Returns None when the program declares no certificate, else the
    proof's verdict.  The jitted evaluator is plan-cached per
    (program instance, context): a certificate may read parameters
    outside the program's ``runner_key`` (BFS's and SSSP's ``source``),
    so it is never shared between instances.
    """
    if program.certificate is None:
        return None

    state = jax.tree.map(jnp.asarray, state)

    def build():
        return program, _jit_hoisted(lambda st: jnp.asarray(
            program.certificate(ctx, st), bool).reshape(()), (state,))

    fn = _cached_exec_fn(program, ctx, ("certificate", id(program)), build)
    return bool(fn(state))


# ----------------------------------------------------------------------
# segmented execution


class _SentinelTrip(Exception):
    """Internal: a sentinel (or certificate) rejected a segment."""

    def __init__(self, sentinels: List[str], lo: int, hi: int,
                 attempt: int, engine: str):
        self.detail = {"kind": "sentinel", "sentinels": list(sentinels),
                       "segment": [int(lo), int(hi)], "iteration": int(hi),
                       "attempt": int(attempt), "engine": engine}
        super().__init__(f"sentinel trip {sentinels} in segment "
                         f"[{lo}, {hi})")


@dataclasses.dataclass
class _Accounting:
    seconds: float = 0.0
    dispatches: int = 0


def _to_host(state):
    """Deep-copied host snapshot of a device pytree.  The explicit copy
    matters: the segment dispatch donates its carry, and a zero-copy
    numpy view of a donated buffer would be corrupted by the next
    segment."""
    return jax.tree.map(lambda x: np.asarray(x).copy(), state)


def _fused_segment_fn(program, ctx, state, limit, traced, occ_traced,
                      sentinel_fns, warmup, dir_buf, occ_buf):
    """The compiled K-iteration fused segment.

    Identical loop body to the unsegmented fused engine — only the
    ``cond`` bound changes, and the segment end is a *traced* operand,
    so one compiled executable serves every segment of every attempt
    (and the per-iteration math, hence the results, are bit-identical
    to ``engine="fused"``).  Sentinel flags are computed inside the
    same dispatch against the carry the segment started from (= the
    last checkpoint), costing no extra host round trip.
    """

    def fused_seg(st, it0, done0, db, ob, seg_end):
        def cond(carry):
            _, it, done, _, _ = carry
            return (it < seg_end) & ~done

        def body(carry):
            st, it, done, db, ob = carry
            new = program.step(ctx, st, it)
            done = program.converged(st, new)
            if traced:
                db = jax.lax.dynamic_update_index_in_dim(
                    db, jnp.asarray(new[FRONTIER_DIR_KEY], bool), it, 0)
            if occ_traced:
                ob = jax.lax.dynamic_update_index_in_dim(
                    ob, jnp.asarray(new[FRONTIER_OCC_KEY], jnp.float32),
                    it, 0)
            return new, it + jnp.int32(1), done, db, ob

        st2, it2, done2, db2, ob2 = jax.lax.while_loop(
            cond, body, (st, it0, done0, db, ob))
        flags = _sentinel_flags(sentinel_fns, st, st2, ob2, it0, it2,
                                limit, occ_traced)
        return st2, it2, done2, db2, ob2, flags

    def build():
        return program, _jit_hoisted(
            fused_seg, (state, jnp.int32(0), jnp.asarray(False), dir_buf,
                        occ_buf, jnp.int32(0)),
            donate_argnums=(0, 3, 4), compile=warmup)

    names = tuple(n for n, _ in sentinel_fns)
    return _cached_exec_fn(
        program, ctx, ("fused_seg", limit, traced, occ_traced, names),
        build)


def _sentinel_eval_fn(program, ctx, limit, occ_traced, sentinel_fns):
    """Standalone jitted sentinel evaluation — used by the host engine's
    segment boundaries and to re-check fault-injected (perturbed)
    states, whose in-dispatch flags describe the pre-perturbation
    carry."""

    def eval_(prev, cur, ob, lo, hi):
        return _sentinel_flags(sentinel_fns, prev, cur, ob, lo, hi,
                               limit, occ_traced)

    def build():
        return program, jax.jit(eval_)

    names = tuple(n for n, _ in sentinel_fns)
    return _cached_exec_fn(
        program, ctx, ("sentinel_eval", limit, occ_traced, names), build)


def _host_step_fn(program, ctx, state, warmup):
    """The host engine's cached per-iteration step (same cache entry as
    :func:`repro.core.executor._run_host` builds)."""
    def build():
        def step_fn(st, it):
            new = program.step(ctx, st, it)
            done = program.converged(st, new)
            return new, done
        step = _jit_hoisted(step_fn, (state, jnp.int32(0)),
                            donate_argnums=(0,))
        if warmup:
            copy = jax.tree.map(lambda x: x.copy(), state)
            jax.block_until_ready(step(copy, jnp.int32(0)))
        return program, step

    return _cached_exec_fn(program, ctx, ("host",), build)


def _tripped(names: List[str], flags) -> List[str]:
    arr = np.asarray(flags)
    return [names[i] for i in np.where(~arr)[0]]


def _degradation_chain(knobs0: dict, config: SystemConfig) -> List[dict]:
    """Rung ``a`` of the chain is the knob set retry attempt ``a+1``
    runs: retry-as-is first, then shed autotuned tiling, then the
    sparse frontier path (dynamic configs), then the fused engine
    itself.  Rungs that would not change anything are skipped."""
    chain = [dict(knobs0)]

    def add(**delta):
        cand = {**chain[-1], **delta}
        if cand not in chain:
            chain.append(cand)

    if knobs0["autotune"] != "off":
        add(autotune="off")
    if (config.prop is UpdateProp.PUSH_PULL
            and knobs0["sparse_edge_capacity"] != 0):
        add(sparse_edge_capacity=0)
    if chain[-1]["engine"] == "fused":
        add(engine="host")
    return chain


def _decode_traces(db, ob, it, traced, occ_traced):
    trace = None
    occ_trace = None
    if traced and db is not None:
        trace = "".join("T" if b else "S" for b in np.asarray(db)[:it])
    if occ_traced and ob is not None:
        occ_trace = [float(o) for o in np.asarray(ob)[:it]]
    return trace, occ_trace


def _segment_loop(program, ctx, cp, limit, K, ring, sentinel_fns, injector,
                  warmup, acct, attempt, traced, occ_traced, engine,
                  store=None):
    """Drive segments from checkpoint ``cp`` to convergence/limit,
    snapshotting each boundary into ``ring`` (and, when ``store`` is a
    :class:`~repro.core.durability.CheckpointStore`, spilling it to
    disk so a process death resumes from here); raises
    :class:`_SentinelTrip` (or whatever the injector raises) on
    failure."""
    names = _sentinel_names(sentinel_fns, occ_traced)
    check = bool(names)
    state = jax.tree.map(jnp.asarray, cp.state)
    it, done = cp.it, cp.done
    prev_host = cp.state
    eval_fn = (_sentinel_eval_fn(program, ctx, limit, occ_traced,
                                 sentinel_fns) if check else None)
    if engine == "fused":
        db = jnp.asarray(cp.dir_buf) if traced else None
        ob = jnp.asarray(cp.occ_buf) if occ_traced else None
        seg_fn = _fused_segment_fn(program, ctx, state, limit, traced,
                                   occ_traced, sentinel_fns, warmup, db, ob)
    else:
        db = cp.dir_buf.copy() if traced else None
        ob = cp.occ_buf.copy() if occ_traced else None
        step = _host_step_fn(program, ctx, state, warmup)

    while it < limit and not done:
        lo = it
        seg_end = min(it + K, limit)
        if injector is not None:
            injector.before_segment(it)
        t0 = time.perf_counter()
        if engine == "fused":
            STATS.dispatches += 1
            acct.dispatches += 1
            state, it_dev, done_dev, db, ob, flags = seg_fn(
                state, jnp.int32(it), jnp.asarray(done), db, ob,
                jnp.int32(seg_end))
            jax.block_until_ready((state, it_dev, done_dev, flags))
            acct.seconds += time.perf_counter() - t0
            it, done = int(it_dev), bool(done_dev)
        else:
            flags = None
            while it < seg_end:
                STATS.dispatches += 1
                acct.dispatches += 1
                state, done_dev = step(state, jnp.int32(it))
                it += 1
                if traced:
                    db[it - 1] = bool(state[FRONTIER_DIR_KEY])
                if occ_traced:
                    ob[it - 1] = float(state[FRONTIER_OCC_KEY])
                done = bool(done_dev)
                if done:
                    break
            jax.block_until_ready(state)
            acct.seconds += time.perf_counter() - t0

        host_state = _to_host(state)
        if injector is not None:
            p = injector.perturb(it, host_state, prev_host)
            if p is not None:
                host_state = p
                state = jax.tree.map(jnp.asarray, host_state)
                flags = None  # in-dispatch flags predate the perturbation
        if check and flags is None and eval_fn is not None:
            ob_dev = ob if engine == "fused" else (
                jnp.asarray(ob) if occ_traced else None)
            flags = eval_fn(jax.tree.map(jnp.asarray, prev_host), state,
                            ob_dev, jnp.int32(lo), jnp.int32(it))
        if check:
            bad = _tripped(names, flags)
            if bad:
                raise _SentinelTrip(bad, lo, it, attempt, engine)
        boundary = Checkpoint(
            it=it, done=done, state=host_state,
            dir_buf=(np.asarray(db).copy() if traced else None),
            occ_buf=(np.asarray(ob).copy() if occ_traced else None))
        ring.push(boundary)
        if store is not None:
            store.save(boundary)
        prev_host = host_state

    if done and check and program.certificate is not None:
        if check_certificate(program, ctx, state) is False:
            raise _SentinelTrip(["certificate"], it, it, attempt, engine)
    trace, occ_trace = _decode_traces(db, ob, it, traced, occ_traced)
    return RunResult(state=state, iterations=it, seconds=acct.seconds,
                     converged=done, direction_trace=trace,
                     occupancy_trace=occ_trace, engine=engine,
                     dispatches=acct.dispatches, attempts=attempt + 1)


def run_resilient(program: VertexProgram, graph: Graph,
                  config: SystemConfig,
                  key: Optional[jax.Array] = None,
                  max_iters: Optional[int] = None,
                  use_pallas: bool = False, warmup: bool = True,
                  sparse_edge_capacity: Optional[int] = None,
                  engine: str = "fused", autotune=None,
                  checkpoint_every: int = 0,
                  retry: Optional[RetryPolicy] = None,
                  sentinels: bool = True,
                  ring_capacity: Optional[int] = None,
                  fault_injector: Optional[FaultInjector] = None,
                  checkpoint_dir: Optional[str] = None
                  ) -> RunResult:
    """Checkpointed, sentinel-guarded, retrying counterpart of
    :func:`repro.core.executor.run` (which delegates here whenever any
    resilience knob is set).  Results are bit-identical to the plain
    engines; ``RunResult.outcome`` reports ``"converged"``,
    ``"iter_limit"`` or ``"faulted"`` (with the fault history attached
    under ``RunResult.fault``).

    ``checkpoint_dir`` makes the run *crash-durable*: every ring
    boundary is also spilled to a :class:`~repro.core.durability.
    CheckpointStore` under that directory, and a fresh call pointed at
    the same directory resumes from the newest intact on-disk boundary
    instead of iteration 0 — bit-identical to an uninterrupted run,
    since segment boundaries fall on the same iteration multiples
    either way.  Corrupt or foreign generations are rejected at load
    (structured ``corrupt_checkpoint`` / ``checkpoint_mismatch``
    records in the fault history) and recovery falls back generation by
    generation, ultimately to a cold restart."""
    if engine not in ("fused", "host"):
        raise ValueError(f"unknown engine {engine!r}; "
                         "expected 'fused' or 'host'")
    limit = max_iters or program.max_iters
    K = int(checkpoint_every) if checkpoint_every else \
        DEFAULT_CHECKPOINT_EVERY
    if K < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {K}")
    knobs0 = {"engine": engine,
              "autotune": _normalize_autotune(autotune),
              "sparse_edge_capacity": sparse_edge_capacity,
              "use_pallas": bool(use_pallas)}
    injector = fault_injector
    if injector is not None and getattr(injector, "knob_overrides", None):
        knobs0.update(injector.knob_overrides)
    chain = _degradation_chain(knobs0, config)
    max_attempts = retry.max_attempts if retry is not None else 1
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")

    capacity = ring_capacity or DEFAULT_RING_CAPACITY
    store = None
    faults: List[dict] = []
    ring = CheckpointRing(capacity)
    if checkpoint_dir is not None:
        from repro.core.durability import CheckpointStore
        from repro.launch.journal import _serialize_key, graph_fingerprint
        # the fingerprint must pin everything the resumed state depends
        # on: names and shapes alone let a same-shape graph with
        # different edges/weights (or a rerun under a different PRNG
        # key) silently adopt the wrong run's checkpoints, so the graph
        # is identified by content hash and the key rides along verbatim
        store = CheckpointStore(
            checkpoint_dir, keep=capacity,
            fingerprint={"program": program.name, "config": config.name,
                         "n_nodes": int(graph.n_nodes),
                         "n_edges": int(graph.n_edges),
                         "graph_sha256": graph_fingerprint(graph),
                         "key": _serialize_key(key),
                         "limit": int(limit), "k": int(K)})
        disk_cps, disk_faults = store.load_all()
        faults.extend(disk_faults)
        for disk_cp in disk_cps:
            ring.push(disk_cp)
    if len(ring):
        # resumed: the newest intact on-disk boundary replaces
        # program.init — segment boundaries are deterministic multiples
        # of K, so the remaining segments are bit-identical to what the
        # killed run would have executed
        seed_cp = ring.latest()
        traced = seed_cp.dir_buf is not None
        occ_traced = seed_cp.occ_buf is not None
    else:
        state0 = program.init(graph, key) if key is not None \
            else program.init(graph)
        state0 = jax.tree.map(jnp.asarray, state0)
        traced, occ_traced = _trace_flags(program, state0)
        initial = Checkpoint(
            it=0, done=False, state=_to_host(state0),
            dir_buf=np.zeros((limit,), bool) if traced else None,
            occ_buf=(np.full((limit,), DENSE_OCC, np.float32)
                     if occ_traced else None))
        ring.push(initial)
        if store is not None:
            store.save(initial)
    sentinel_fns = build_sentinels(program) if sentinels else []
    acct = _Accounting()
    attempt = 0
    while True:
        knobs = knobs0 if attempt == 0 \
            else chain[min(attempt - 1, len(chain) - 1)]
        # each retry rolls back one checkpoint deeper: snapshots taken
        # during the failed attempt passed the boundary checks but may
        # still carry a corruption only the certificate would see
        cp = ring.rollback(attempt) if attempt else ring.latest()
        try:
            ctx = EdgeContext.create(
                graph, config, use_pallas=knobs["use_pallas"],
                sparse_edge_capacity=knobs["sparse_edge_capacity"],
                autotune=knobs["autotune"])
            if injector is not None:
                injector.on_compile(knobs)
            res = _segment_loop(program, ctx, cp, limit, K, ring,
                                sentinel_fns, injector, warmup, acct,
                                attempt, traced, occ_traced,
                                knobs["engine"], store=store)
            if faults:
                res.fault = {"history": faults, "recovered": True}
            return res
        except _SentinelTrip as trip:
            faults.append(trip.detail)
        except Exception as err:  # noqa: BLE001 — recovery is the point
            faults.append({"kind": "exception", "error": repr(err),
                           "attempt": attempt,
                           "engine": knobs["engine"]})
        attempt += 1
        if attempt >= max_attempts:
            cp = ring.latest()
            trace, occ_trace = _decode_traces(
                cp.dir_buf, cp.occ_buf, cp.it, traced, occ_traced)
            return RunResult(
                state=jax.tree.map(jnp.asarray, cp.state),
                iterations=cp.it, seconds=acct.seconds, converged=False,
                direction_trace=trace, occupancy_trace=occ_trace,
                engine=knobs["engine"], dispatches=acct.dispatches,
                outcome="faulted",
                fault={"history": faults, "final": faults[-1],
                       "recovered": False},
                attempts=attempt)
        if retry is not None and retry.backoff_s:
            time.sleep(retry.backoff_s * attempt)
