"""Blocked segment reduction — the DeNovo-coherence analogue on TPU.

The target-vertex range is tiled into blocks of ``block_size`` segments;
edges arrive binned by target block (``Graph.perm_owned`` order).  Each
output block is "owned" in VMEM across the consecutive grid steps that feed
it ("ownership registration at L1"), accumulated locally, and written back
to HBM exactly once — versus the LLC-analogue global XLA scatter that
resolves every update at HBM.

Every array is lane-dense: an edge tile is ``rows`` rows of 128 edges
(:func:`tile_rows`), and the output keeps the segments of a block on
lanes.  Sum uses the canonical TPU trick: scatter-within-block ==
one-hot matmul on the MXU (contrib = values_row @ onehot^T, one row of
128 edges at a time).  Min/max mask a ``[block, 128]`` tile per row on
the VPU, fold the rows elementwise, transpose, and finish across
sublanes.

Grid: one step per (feature, edge tile); ``tile_block_id``
(scalar-prefetched) steers the output BlockSpec so Pallas keeps the same
VMEM block resident across consecutive tiles of one block.
``tile_first`` initialises the accumulator when a new block begins.
Interpret mode follows the backend: interpreted on the CPU, compiled
by Mosaic on the TPU.  Output blocks must be a multiple of 128 segments
to compile for the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["seg_sum_pallas", "seg_minmax_pallas", "plan_tiles", "tile_rows"]


def plan_tiles(block_ptr: np.ndarray, tile_e: int):
    """Host-side tiling plan over block-binned edges.

    Returns (gather_idx [n_tiles, tile_e] int32 into the binned edge order,
    -1 = padding; tile_block_id [n_tiles]; tile_first [n_tiles]).  Every
    output block gets at least one tile so it is always initialised.

    Fully vectorized numpy bucket arithmetic (no per-block Python loop):
    this sits on the plan cache's cold path, so an O(n_blocks)
    interpreted loop would dominate first-touch latency on large graphs.
    Tile *t* of block *b* gathers edges ``block_ptr[b] + t*tile_e ..``,
    clipped to the block's edge range with -1 padding.

    All index arithmetic — including the [n_tiles, tile_e] ``gather``
    intermediate, the plan's largest array — runs in int32: edge ids fit
    (the kernels and :class:`~repro.graph.structure.Graph` are int32
    throughout), and an int64 intermediate would double the plan's host
    memory traffic exactly when a tuned large-``tile_e`` plan makes the
    array widest.
    """
    block_ptr = np.asarray(block_ptr)
    if block_ptr.size and int(block_ptr[-1]) >= np.iinfo(np.int32).max:
        raise ValueError("plan_tiles: edge count exceeds int32 index range")
    block_ptr = block_ptr.astype(np.int32)
    n_blocks = block_ptr.shape[0] - 1
    counts = np.diff(block_ptr)
    # ceil(counts / tile_e), but empty blocks still get one (all-padding)
    # tile so their output block is initialised
    tiles_per_block = np.maximum(1, -(-counts // tile_e)).astype(np.int32)
    n_tiles = int(tiles_per_block.sum())
    tbid = np.repeat(np.arange(n_blocks, dtype=np.int32), tiles_per_block)
    first_tile = (np.cumsum(tiles_per_block, dtype=np.int32)
                  - tiles_per_block)
    tfirst = np.zeros(n_tiles, np.int32)
    tfirst[first_tile] = 1
    # within-block tile ordinal of every tile
    local = np.arange(n_tiles, dtype=np.int32) - first_tile[tbid]
    offs = (block_ptr[tbid][:, None]
            + local[:, None] * np.int32(tile_e)
            + np.arange(tile_e, dtype=np.int32)[None, :])
    gather = np.where(offs < block_ptr[tbid + 1][:, None], offs,
                      np.int32(-1))
    return (gather, tbid, tfirst)


#: TPU vector lane count: the minor dimension of every kernel block.
LANES = 128


def tile_rows(tile_e: int) -> tuple:
    """``(rows, lanes)`` of one edge tile in the lane-dense layout.

    A tile of ``tile_e`` edges is laid out as ``rows`` rows of ``lanes``
    edges, the edge axis on the 128-lane minor dimension.  Tiles whose
    width is not a multiple of 128 (tiny test plans) are one row of the
    whole tile, still a legal TPU block because it spans the array.
    """
    if tile_e % LANES == 0:
        return tile_e // LANES, LANES
    return 1, tile_e


def _interpret(interpret):
    """Pallas interpret mode chosen by the backend: interpreted on the
    CPU, compiled by Mosaic on the TPU, refused elsewhere."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise NotImplementedError(
        f"the blocked segment reducers run on 'cpu' (interpreted) or "
        f"'tpu' (compiled), not on {backend!r}")


def _blocked_call(kernel, vals_tiled, lids_tiled, tile_block_id, tile_first,
                  block_size, num_out_blocks, interpret):
    """One grid step per (feature, edge tile); returns ``[D, 1, V_pad]``.

    Feature j is the OUTER grid axis and edge tile i the INNER one, so
    revisits of one output block happen on consecutive grid steps (the
    Pallas revisit contract).  Ids are ``[n_tiles, rows, lanes]`` and
    values ``[D, n_tiles, rows, lanes]``; the output keeps segments on
    lanes, so no array of the call has a minor dimension of 1.
    """
    d, n_tiles, rows, lanes = vals_tiled.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d, n_tiles),
        in_specs=[
            pl.BlockSpec((1, rows, lanes),
                         lambda j, i, tbid, tfirst: (i, 0, 0)),
            pl.BlockSpec((1, 1, rows, lanes),
                         lambda j, i, tbid, tfirst: (j, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_size),
                               lambda j, i, tbid, tfirst: (j, 0, tbid[i])),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((d, 1, num_out_blocks * block_size),
                                       vals_tiled.dtype),
        interpret=_interpret(interpret),
    )(tile_block_id, tile_first, lids_tiled, vals_tiled)


# ---------------------------------------------------------------------------
# sum kernel (MXU one-hot matmul)
# ---------------------------------------------------------------------------
def _sum_kernel(tbid_ref, tfirst_ref, lid_ref, vals_ref, out_ref):
    i = pl.program_id(1)

    @pl.when(tfirst_ref[i] == 1)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lids = lid_ref[0]                          # [rows, lanes], -1 pad
    vals = vals_ref[0, 0]                      # [rows, lanes]
    rows, lanes = lids.shape
    block = out_ref.shape[-1]
    seg = jax.lax.broadcasted_iota(jnp.int32, (block, lanes), 0)
    acc = jnp.zeros((1, block), jnp.float32)
    for r in range(rows):
        onehot = (seg == lids[r:r + 1, :]).astype(vals.dtype)
        acc += jax.lax.dot_general(            # vals_r @ onehot^T
            vals[r:r + 1, :], onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    out_ref[0] += acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "num_out_blocks",
                                             "interpret"))
def seg_sum_pallas(vals_tiled: jnp.ndarray,   # [D, n_tiles, rows, lanes]
                   lids_tiled: jnp.ndarray,   # [n_tiles, rows, lanes]
                   tile_block_id: jnp.ndarray,
                   tile_first: jnp.ndarray,
                   *, block_size: int, num_out_blocks: int,
                   interpret=None) -> jnp.ndarray:
    """Blocked segment sum, ``[D, 1, num_out_blocks * block_size]``.

    ``interpret=None`` lets the backend decide (see :func:`_interpret`).
    """
    return _blocked_call(_sum_kernel, vals_tiled, lids_tiled, tile_block_id,
                         tile_first, block_size, num_out_blocks, interpret)


# ---------------------------------------------------------------------------
# min/max kernel (masked VPU reduce)
# ---------------------------------------------------------------------------
def _minmax_kernel(tbid_ref, tfirst_ref, lid_ref, vals_ref, out_ref, *,
                   is_min: bool, ident):
    i = pl.program_id(1)
    comb = jnp.minimum if is_min else jnp.maximum

    @pl.when(tfirst_ref[i] == 1)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, ident)

    lids = lid_ref[0]
    vals = vals_ref[0, 0]
    rows, lanes = lids.shape
    block = out_ref.shape[-1]
    seg = jax.lax.broadcasted_iota(jnp.int32, (block, lanes), 0)
    # [block, lanes] partials: segment on sublanes, edge lane on lanes
    part = None
    for r in range(rows):
        m = jnp.where(seg == lids[r:r + 1, :], vals[r:r + 1, :], ident)
        part = m if part is None else comb(part, m)
    # transpose so segments lie on lanes, then finish across sublanes
    red = part.T
    red = (red.min(axis=0, keepdims=True) if is_min
           else red.max(axis=0, keepdims=True))
    out_ref[0] = comb(out_ref[0], red)


@functools.partial(jax.jit, static_argnames=("block_size", "num_out_blocks",
                                             "is_min", "interpret"))
def seg_minmax_pallas(vals_tiled, lids_tiled, tile_block_id, tile_first, *,
                      block_size: int, num_out_blocks: int, is_min: bool,
                      interpret=None) -> jnp.ndarray:
    """Blocked segment min/max; layouts as :func:`seg_sum_pallas`."""
    dtype = vals_tiled.dtype
    if jnp.issubdtype(dtype, jnp.floating):
        ident = float("inf") if is_min else float("-inf")
    else:
        ident = int(jnp.iinfo(dtype).max if is_min else jnp.iinfo(dtype).min)
    kernel = functools.partial(_minmax_kernel, is_min=is_min, ident=ident)
    return _blocked_call(kernel, vals_tiled, lids_tiled, tile_block_id,
                         tile_first, block_size, num_out_blocks, interpret)
