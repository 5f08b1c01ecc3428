"""Jit-safe wrappers around the blocked segment-reduce kernels.

The tiling plan depends only on the (static) binned segment ids, so it is
built once on host (numpy) and the returned reducer is safe to call inside
jit — values are gathered with a static index array at runtime.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce.kernel import (plan_tiles, seg_minmax_pallas,
                                                 seg_sum_pallas, tile_rows)

__all__ = ["BlockedSegmentReducer", "TilingPlan", "DEFAULT_PLAN",
           "coarsen_block_ptr", "bin_edges_by_block"]


@dataclasses.dataclass(frozen=True)
class TilingPlan:
    """One point of the blocked-reducer tuning space.

    Hashable (frozen, scalar fields) so it can key plan-cache entries
    directly.  The defaults reproduce the pre-autotuner static tiling
    exactly — :data:`DEFAULT_PLAN` is always one candidate of any
    autotune sweep, so tuning can never do worse than the old
    hard-coded configuration on the tuner's own measurements.

    - ``tile_e`` — edges per grid step of the blocked kernels (one
      VMEM-resident gather tile).
    - ``block_mult`` — output-block coarsening factor: the reducer's
      segment block covers ``block_mult`` consecutive base blocks
      (``Graph.block_size`` vertices each).  Coarsening is always sound
      on block-binned edge orders: a coarse block is a union of
      consecutive base blocks, so edges sorted by base block are also
      sorted by coarse block (see :func:`coarsen_block_ptr`).
    - ``block_div`` — output-block *refinement* factor (blocks of
      ``base // block_div`` vertices).  Sound only for edge orders
      sorted by destination (the pull/CSC order): a fully sorted order
      stays binned under any block partition, whereas the owned order
      is binned only at base-block granularity.  Mutually exclusive
      with coarsening.
    - ``gather_splits`` — how many partial scatters the sparse
      frontier-gathered reduction splits its ``[cap_e]`` slice into
      (1 = today's single scatter).
    - ``source`` — provenance tag ("default" | "heuristic" | "tuned" |
      "disk"), carried for observability only; excluded from equality
      so a disk-warmed plan compares equal to the freshly measured one.
    """

    tile_e: int = 512
    block_mult: int = 1
    block_div: int = 1
    gather_splits: int = 1
    source: str = dataclasses.field(default="default", compare=False)

    def __post_init__(self):
        if self.block_mult > 1 and self.block_div > 1:
            raise ValueError("TilingPlan: block_mult and block_div are "
                             "mutually exclusive")
        if min(self.tile_e, self.block_mult, self.block_div,
               self.gather_splits) < 1:
            raise ValueError("TilingPlan fields must be >= 1")

    def astuple(self):
        """The identity-relevant fields (cache/JSON key material)."""
        return (self.tile_e, self.block_mult, self.block_div,
                self.gather_splits)

    def block_size(self, base_block_size: int) -> int:
        """Effective output-block size on a base blocking."""
        if self.block_div > 1:
            return max(1, base_block_size * self.block_mult
                       // self.block_div)
        return base_block_size * self.block_mult


#: The pre-autotuner static tiling every call site used to hard-code.
DEFAULT_PLAN = TilingPlan()


def coarsen_block_ptr(block_ptr: np.ndarray, mult: int) -> np.ndarray:
    """Per-block edge offsets after merging ``mult`` consecutive blocks.

    Edges binned by base block stay binned under the coarser blocking
    (each coarse block is a contiguous run of base blocks), so the
    coarse plan is just the base ``block_ptr`` sampled every ``mult``
    entries (the final boundary is always kept).
    """
    block_ptr = np.asarray(block_ptr)
    if mult <= 1:
        return block_ptr
    n_blocks = block_ptr.shape[0] - 1
    n_coarse = -(-n_blocks // mult)
    idx = np.minimum(np.arange(n_coarse + 1) * mult, n_blocks)
    return block_ptr[idx]


def bin_edges_by_block(dst: np.ndarray, n_nodes: int,
                       block_size: int) -> tuple:
    """Bin an edge list by destination block: ``(perm, block_ptr)``.

    ``perm`` stable-sorts edges by ``dst // block_size`` (preserving the
    input order inside each block — the property the owned/DeNovo path
    relies on for dense source reads) and ``block_ptr`` gives per-block
    edge offsets.  This is the host-side construction behind
    :class:`~repro.graph.structure.Graph`'s owned order; the batched
    executor also uses it to re-bin a block-diagonal packed edge list
    whose per-graph vertex offsets don't align with block boundaries.
    """
    dst = np.asarray(dst, np.int64)
    n_blocks = (int(n_nodes) + block_size - 1) // block_size
    blk = dst // block_size
    perm = np.argsort(blk, kind="stable")
    block_ptr = np.zeros(n_blocks + 1, dtype=np.int64)
    np.add.at(block_ptr, blk + 1, 1)
    return perm.astype(np.int32), np.cumsum(block_ptr).astype(np.int32)


class BlockedSegmentReducer:
    """Plan once (host), reduce many times (device, inside jit).

    ``segment_ids`` must arrive binned by target block (``Graph.perm_owned``
    order) with ``block_ptr`` giving per-block edge offsets — exactly what
    :class:`repro.graph.Graph` maintains.

    Construction is the expensive part (the vectorized
    :func:`plan_tiles` plus an O(n_tiles * tile_e) local-id rewrite);
    ``repro.core.plan_cache.PLAN_CACHE`` therefore caches built reducer
    instances per graph so a design-space sweep pays the plan exactly
    once.  ``n_tiles`` exposes the plan size for benchmarks and tests.
    """

    def __init__(self, segment_ids: np.ndarray, block_ptr: np.ndarray,
                 num_segments: int, block_size: int, tile_e: int = 512,
                 plan: "TilingPlan | None" = None):
        self.plan = plan if plan is not None else TilingPlan(tile_e=tile_e)
        # int32 end to end: the kernels index with int32, and the plan's
        # [n_tiles, tile_e] arrays are the dominant host/device index
        # traffic — int64 intermediates would double it (plan_tiles
        # guards the edge-count range).
        ids = np.asarray(segment_ids, np.int32)
        self.gather_idx, self.tile_block_id, self.tile_first = plan_tiles(
            block_ptr, tile_e)
        self.n_tiles = int(self.gather_idx.shape[0])
        self.tile_e = int(tile_e)
        self.rows, self.lanes = tile_rows(self.tile_e)
        pad = self.gather_idx < 0
        safe = np.where(pad, np.int32(0), self.gather_idx)
        lids = ids[safe] - self.tile_block_id[:, None] * np.int32(block_size)
        # lane-dense [n_tiles, rows, lanes]: the edge axis on lanes
        self.lids = jnp.asarray(np.where(pad, np.int32(-1), lids).reshape(
            self.n_tiles, self.rows, self.lanes))
        self.gather = jnp.asarray(safe.reshape(-1))
        self.pad_mask = jnp.asarray(pad.reshape(-1))
        self.tbid = jnp.asarray(self.tile_block_id)
        self.tfirst = jnp.asarray(self.tile_first)
        self.num_segments = int(num_segments)
        self.block_size = int(block_size)
        self.num_out_blocks = -(-int(num_segments) // int(block_size))

    @classmethod
    def from_plan(cls, segment_ids: np.ndarray, block_ptr: np.ndarray,
                  num_segments: int, base_block_size: int,
                  plan: "TilingPlan | None" = None) -> "BlockedSegmentReducer":
        """Plan-parameterized constructor (the autotuner entry point).

        ``block_ptr``/``base_block_size`` describe the edge order's
        *base* blocking (``Graph.block_size``); the plan's
        ``block_mult`` coarsens both consistently before the tiling
        plan is built, and ``tile_e`` sizes the edge tiles.
        ``plan=None`` (or :data:`DEFAULT_PLAN`) reproduces the
        pre-autotuner construction bit for bit.  Refinement
        (``block_div > 1``) cannot be expressed from a base
        ``block_ptr`` alone — refined reducers are built from the
        per-vertex row offsets instead (see
        :func:`repro.kernels.autotune.build_reducer`).
        """
        plan = plan if plan is not None else DEFAULT_PLAN
        if plan.block_div > 1:
            raise ValueError("from_plan cannot refine blocks (block_div "
                             "> 1) from a base block_ptr; build from "
                             "per-vertex row offsets instead")
        return cls(segment_ids, coarsen_block_ptr(block_ptr, plan.block_mult),
                   num_segments, base_block_size * plan.block_mult,
                   tile_e=plan.tile_e, plan=plan)

    def _tile_values(self, values: jnp.ndarray, fill):
        """Gather ``[E]`` or ``[E, D]`` values into the kernels'
        lane-dense ``[D, n_tiles, rows, lanes]`` tiles; padding slots
        hold ``fill``."""
        squeeze = values.ndim == 1
        rows_t = values[None, :] if squeeze else values.T      # [D, E]
        tiled = jnp.take(rows_t, self.gather, axis=1)
        tiled = jnp.where(self.pad_mask[None, :], fill, tiled)
        tiled = tiled.reshape(rows_t.shape[0], self.n_tiles, self.rows,
                              self.lanes)
        return tiled, squeeze

    def _finish(self, out, squeeze):
        out = out[:, 0, :self.num_segments]                    # [D, V]
        return out[0] if squeeze else out.T

    def sum(self, values: jnp.ndarray) -> jnp.ndarray:
        tiled, squeeze = self._tile_values(values, 0)
        out = seg_sum_pallas(tiled, self.lids, self.tbid, self.tfirst,
                             block_size=self.block_size,
                             num_out_blocks=self.num_out_blocks)
        return self._finish(out, squeeze)

    def _minmax(self, values, is_min):
        dtype = values.dtype
        if jnp.issubdtype(dtype, jnp.floating):
            # match jax.ops.segment_min/max: empty segments hold +/-inf
            ident = float("inf") if is_min else float("-inf")
        else:
            ident = int(jnp.iinfo(dtype).max if is_min
                        else jnp.iinfo(dtype).min)
        tiled, squeeze = self._tile_values(values, ident)
        out = seg_minmax_pallas(tiled, self.lids, self.tbid, self.tfirst,
                                block_size=self.block_size,
                                num_out_blocks=self.num_out_blocks,
                                is_min=is_min)
        return self._finish(out, squeeze)

    def min(self, values: jnp.ndarray) -> jnp.ndarray:
        return self._minmax(values, True)

    def max(self, values: jnp.ndarray) -> jnp.ndarray:
        return self._minmax(values, False)

    def reduce(self, values: jnp.ndarray, kind: str) -> jnp.ndarray:
        return getattr(self, kind)(values)

    @staticmethod
    def identity(kind: str, dtype) -> jnp.ndarray:
        """The monoid identity this reducer assumes for ``kind``."""
        dtype = jnp.dtype(dtype)
        if kind == "sum":
            return jnp.zeros((), dtype)
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.array(jnp.inf if kind == "min" else -jnp.inf, dtype)
        info = jnp.iinfo(dtype)
        return jnp.array(info.max if kind == "min" else info.min, dtype)

    def masked(self, values: jnp.ndarray, mask: jnp.ndarray,
               kind: str, ident=None) -> jnp.ndarray:
        """Reduce with an [E] edge mask: masked-out edges contribute the
        identity.  This is the predicate (``spred``/``tpred``) entry
        point for both the push/owned and the pull/CSC fast paths.
        Callers already holding their monoid's identity (the executor's
        ``Monoid.identity``) pass it via ``ident`` so the two
        definitions can't drift."""
        if ident is None:
            ident = self.identity(kind, values.dtype)
        if values.ndim == mask.ndim + 1:
            mask = mask[..., None]
        return self.reduce(jnp.where(mask, values, ident), kind)
