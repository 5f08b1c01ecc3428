"""Breadth-First Search — the canonical direction-optimizing traversal.

Level-synchronous BFS with the full frontier protocol: each iteration the
frontier (vertices discovered last level) and the unvisited set feed
``EdgeContext.choose_direction`` — push (source-outer scatter from the
frontier) while the frontier is sparse, pull (target-outer scan of
undiscovered vertices) once the frontier's out-edges outnumber the
unexplored region's (Beamer's alpha test), and back to push for the
shrinking tail (beta test).  Under static configs the flag constant-folds
to the config's direction, so one program covers all 12 cells.

Sparse push iterations go through ``ctx.propagate_sparse``: when the
frontier's gathered edge list fits the context's static capacity, the
reduction runs over exactly those O(m_f) edges instead of scanning all E
under a mask; the per-iteration occupancy lands in the state under
``FRONTIER_OCC_KEY`` (-1 marks a dense iteration).

Depths use int32 with -1 for "unvisited"; the MIN monoid over
``depth[src] + 1`` makes the reduction direction-agnostic (the edge set
is symmetric and both orders carry the same predicates).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.vertex_program import (FRONTIER_DIR_KEY, FRONTIER_OCC_KEY,
                                       MIN, EdgePhase, VertexProgram,
                                       dense_occupancy)

__all__ = ["bfs"]

_UNSEEN = -1


def bfs(source: int = 0, max_iters: int = 4096) -> VertexProgram:
    phase = EdgePhase(
        monoid=MIN,
        vprop=lambda st, src, w: st["depth"][src] + 1,
        spred=lambda st, src: st["active"][src],          # frontier only
        tpred=lambda st, dst: st["depth"][dst] == _UNSEEN,
        frontier=lambda st: st["active"],
        gatherable=True,  # spred == frontier membership
    )

    def init(graph, key=None):
        v = graph.n_nodes
        depth = jnp.full((v,), _UNSEEN, jnp.int32).at[source].set(0)
        active = jnp.zeros((v,), bool).at[source].set(True)
        return {"depth": depth, "active": active,
                FRONTIER_DIR_KEY: jnp.asarray(False),
                FRONTIER_OCC_KEY: dense_occupancy()}

    def step(ctx, st, it):
        unvisited = st["depth"] == _UNSEEN
        pull = ctx.choose_direction(phase.frontier(st), st[FRONTIER_DIR_KEY],
                                    unvisited=unvisited)
        cand, occ = ctx.propagate_sparse(st, phase, pull, dtype=jnp.int32)
        newly = unvisited & (cand < jnp.iinfo(jnp.int32).max)
        depth = jnp.where(newly, cand, st["depth"]).astype(jnp.int32)
        return {"depth": depth, "active": newly, FRONTIER_DIR_KEY: pull,
                FRONTIER_OCC_KEY: occ}

    def converged(prev, cur):
        return ~jnp.any(cur["active"])

    # Resilience protocol.  Depths are not raw-monotone (-1 -> level), so
    # instead of a monotone decl BFS pins the two invariants the level-
    # synchronous traversal does maintain between checkpoints: visited
    # depths never change, and every depth is -1 or a valid level.
    sentinels = {
        "depth_frozen": lambda p, c: jnp.all(jnp.where(
            p["depth"] != _UNSEEN, c["depth"] == p["depth"], True)),
        "depth_range": lambda p, c: jnp.all(
            (c["depth"] == _UNSEEN)
            | ((c["depth"] >= 0) & (c["depth"] < c["depth"].shape[0]))),
    }

    # Certificate: one dense O(E) relaxation from the visited set.  At a
    # true BFS fixpoint every reached vertex's depth equals
    # min(depth[parent]) + 1 and every vertex with a visited neighbour
    # is itself visited — a dropped update (vertex reverted to unseen)
    # or an inflated/deflated depth cannot satisfy both.
    cert_phase = EdgePhase(
        monoid=MIN,
        vprop=lambda st, src, w: st["depth"][src] + 1,
        spred=lambda st, src: st["depth"][src] != _UNSEEN,
    )

    def certificate(ctx, st):
        d = st["depth"]
        cand = ctx.propagate(st, cert_phase, dtype=jnp.int32)
        reach = cand < jnp.iinfo(jnp.int32).max
        is_src = jnp.arange(d.shape[0]) == source
        ok_reached = jnp.where(reach, (d == cand) | is_src, True)
        ok_unreached = jnp.where(reach, True, (d == _UNSEEN) | is_src)
        return jnp.all(ok_reached & ok_unreached) & ~jnp.any(st["active"])

    return VertexProgram(
        name="BFS", init=init, step=step, converged=converged,
        extract=lambda st: st["depth"], weighted=False, max_iters=max_iters,
        frontier_init=lambda g: jnp.zeros((g.n_nodes,), bool)
        .at[source].set(True),
        frontier_update=lambda st: st["active"],
        sentinels=sentinels,
        certificate=certificate,
        # source is read by init, frontier_init and certificate alone
        runner_key=(max_iters,),
    )
