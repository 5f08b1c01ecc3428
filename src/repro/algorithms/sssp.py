"""Single-Source Shortest Path (SSSP) — Table III: static, source control
(push elides all non-frontier sources in the outer loop), source info.
Frontier-based Bellman-Ford relaxation with a min monoid.

The frontier (vertices whose distance improved last iteration) drives the
dynamic configs' per-iteration direction: no monotone "unvisited" set
exists (re-relaxations can reactivate settled vertices), so the push->pull
trigger is the frontier-edge-density fallback of
:func:`repro.core.frontier.choose_direction`.

The state counts vertex activations (``activations``: the source, then
every vertex whose distance improved, once per improvement), which the
program declares as a counter.  Over the vertices reached, it says how
often label-correcting relaxation re-activates a settled vertex.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.vertex_program import (FRONTIER_DIR_KEY, FRONTIER_OCC_KEY,
                                       MIN, EdgePhase, VertexProgram,
                                       dense_occupancy)

__all__ = ["sssp"]


def sssp(source: int = 0, max_iters: int = 4096) -> VertexProgram:
    phase = EdgePhase(
        monoid=MIN,
        vprop=lambda st, src, w: st["dist"][src] + w,
        spred=lambda st, src: st["active"][src],  # frontier only
        frontier=lambda st: st["active"],
        gatherable=True,  # spred == frontier membership
    )

    def init(graph, key=None):
        v = graph.n_nodes
        dist = jnp.full((v,), jnp.inf, jnp.float32).at[source].set(0.0)
        active = jnp.zeros((v,), bool).at[source].set(True)
        return {"dist": dist, "active": active,
                "activations": jnp.int32(1),
                FRONTIER_DIR_KEY: jnp.asarray(False),
                FRONTIER_OCC_KEY: dense_occupancy()}

    def step(ctx, st, it):
        pull = ctx.choose_direction(phase.frontier(st), st[FRONTIER_DIR_KEY])
        cand, occ = ctx.propagate_sparse(st, phase, pull)
        dist = jnp.minimum(st["dist"], cand)
        active = dist < st["dist"]
        activations = (st["activations"]
                       + ctx.per_graph_sum(active).astype(jnp.int32))
        return {"dist": dist, "active": active, "activations": activations,
                FRONTIER_DIR_KEY: pull, FRONTIER_OCC_KEY: occ}

    def converged(prev, cur):
        return ~jnp.any(cur["active"])

    # Certificate: one dense O(E) relaxation over all finite-distance
    # sources.  At a Bellman-Ford fixpoint every reached non-source
    # vertex's distance equals min(dist[u] + w) exactly (each candidate
    # is the same single f32 add the run performed, and MIN is an exact
    # reduction, so the equality is bitwise); an unreached vertex with a
    # reached neighbour, or a distance above/below the relaxation bound,
    # fails the proof.
    cert_phase = EdgePhase(
        monoid=MIN,
        vprop=lambda st, src, w: st["dist"][src] + w,
        spred=lambda st, src: jnp.isfinite(st["dist"][src]),
    )

    def certificate(ctx, st):
        d = st["dist"]
        cand = ctx.propagate(st, cert_phase)
        reach = jnp.isfinite(cand)
        is_src = jnp.arange(d.shape[0]) == source
        ok = jnp.where(reach, (d == cand) | is_src, jnp.isinf(d) | is_src)
        return jnp.all(ok) & ~jnp.any(st["active"])

    return VertexProgram(
        name="SSSP", init=init, step=step, converged=converged,
        extract=lambda st: st["dist"], weighted=True, max_iters=max_iters,
        frontier_init=lambda g: jnp.zeros((g.n_nodes,), bool)
        .at[source].set(True),
        frontier_update=lambda st: st["active"],
        # the MIN-monoid fixpoint only ever improves distances — the
        # exact reorderable-combine property DRFrlx relies on
        monotone={"dist": "non_increasing"},
        sentinels={"dist_nonnegative":
                   lambda p, c: jnp.all(c["dist"] >= 0.0)},
        certificate=certificate,
        # source is read by init, frontier_init and certificate alone
        runner_key=(max_iters,),
        counters={"activations": lambda st: st["activations"]},
    )
