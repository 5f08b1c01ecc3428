"""PageRank (PR) — Table III: static traversal, symmetric control,
source information (rank/out-degree are source-side loads push can hoist).
Topology-driven: every vertex active every iteration (trivial
predicates), so the frontier protocol runs with a dense all-ones mask —
the direction heuristic sees a saturated frontier and dynamic configs
settle on pull, and the per-iteration direction lands in
``RunResult.direction_trace`` like every other app.

Normalization is deliberately *stateful*: ``inv_v`` carries ``1/V`` of
the graph the program was initialised on as a per-graph scalar
(``[B]`` under ``run_batch``), so the teleport and dangling terms
never read the context's vertex count.  Reading ``ctx.n_nodes`` here —
the old code — normalized by the *packed* vertex count, padding
included: every batched rank was silently scaled down.  The scalar is
aligned against vertex arrays via ``ctx.align_per_graph``, which is
the identity sequentially: the rank update stays in the scalar*vector
HLO shape that rounds identically under the host and fused engines
(materializing ``1/V`` as a ``[V]`` operand makes the fma contraction
of ``(1-d)*inv_v + d*(...)`` diverge between the two compilations).
Padding rows are masked to exactly 0 through ``active`` (packed
``False``), so batched PR normalizes by each graph's *true* V,
padding stays inert, and unbatching recovers the sequential result.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.vertex_program import (FRONTIER_DIR_KEY, FRONTIER_OCC_KEY,
                                       SUM, EdgePhase, VertexProgram,
                                       dense_occupancy)

__all__ = ["pagerank"]


def pagerank(damping: float = 0.85, tol: float = 1e-6,
             max_iters: int = 256) -> VertexProgram:
    phase = EdgePhase(
        monoid=SUM,
        vprop=lambda st, src, w: st["rank"][src] * st["inv_out"][src],
        frontier=lambda st: st["active"],
        # every source contributes every iteration — the frontier only
        # steers the direction heuristic, so the sparse gather is unsound
        gatherable=False,
    )

    def init(graph, key=None):
        v = graph.n_nodes
        out_deg = jnp.asarray(graph.out_degree)
        return {
            "rank": jnp.full((v,), 1.0 / v, jnp.float32),
            "inv_out": (1.0 / jnp.maximum(out_deg, 1)).astype(jnp.float32),
            "dangling": (out_deg == 0),
            "inv_v": jnp.float32(1.0 / v),
            "active": jnp.ones((v,), bool),
            FRONTIER_DIR_KEY: jnp.asarray(False),
            FRONTIER_OCC_KEY: dense_occupancy(),
        }

    def step(ctx, st, it):
        pull = ctx.choose_direction(st["active"], st[FRONTIER_DIR_KEY])
        reduced, occ = ctx.propagate_sparse(st, phase, pull)
        inv_v = ctx.align_per_graph(st["inv_v"])
        dangling_mass = ctx.align_per_graph(
            ctx.per_graph_sum(jnp.where(st["dangling"], st["rank"], 0.0)))
        rank = jnp.where(
            st["active"],
            (1.0 - damping) * inv_v
            + damping * (reduced + dangling_mass * inv_v),
            0.0)
        return {**st, "rank": rank, FRONTIER_DIR_KEY: pull,
                FRONTIER_OCC_KEY: occ}

    def converged(prev, cur):
        return jnp.sum(jnp.abs(prev["rank"] - cur["rank"])) < tol

    return VertexProgram(
        name="PR", init=init, step=step, converged=converged,
        extract=lambda st: st["rank"], weighted=False, max_iters=max_iters,
        frontier_init=lambda g: jnp.ones((g.n_nodes,), bool),
        frontier_update=lambda st: st["active"],
        # total mass is conserved at 1, so no rank can exceed it; a
        # corrupted rank/inv_out explodes past the bound within one
        # iteration.  No certificate: the damped iteration is an
        # attractive fixpoint, so the convergence residual itself is
        # the proof (perturbations are re-absorbed, not frozen in).
        sentinels={"rank_range": lambda p, c: jnp.all(
            (c["rank"] >= 0.0) & (c["rank"] <= 1.0 + 1e-3))},
        runner_key=(damping, tol, max_iters),
    )
