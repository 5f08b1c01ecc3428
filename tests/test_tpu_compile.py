"""The blocked segment reducers compile for a TPU v5e chip at real size.

No chip is attached: the topology is described, and each kernel entry
point is compiled with ``interpret=False`` at the tile counts of the
Graph500 R-MAT scale-20 input (edge factor 16, seed 1: 1,048,576
vertices, 31,404,412 directed edges, 63,384 tiles of the default
512-edge plan over 256-vertex blocks).  The topology is described only
inside the fixture below: describing it loads the TPU library, which
one process at a time may hold.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.segment_reduce.kernel import (seg_minmax_pallas,
                                                 seg_sum_pallas, tile_rows)

N_TILES = 63_384
TILE_E = 512
BLOCK = 256
N_OUT_BLOCKS = (1 << 20) // BLOCK
HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kind,dtype", [
    ("sum", jnp.float32), ("min", jnp.float32), ("min", jnp.int32),
    ("max", jnp.float32)])
def test_reducer_compiles_for_v5e(one_chip, kind, dtype):
    rows, lanes = tile_rows(TILE_E)

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    vals = shape((1, N_TILES, rows, lanes), dtype)
    lids = shape((N_TILES, rows, lanes), jnp.int32)
    per_tile = shape((N_TILES,), jnp.int32)
    kw = dict(block_size=BLOCK, num_out_blocks=N_OUT_BLOCKS, interpret=False)
    if kind == "sum":
        lowered = seg_sum_pallas.lower(vals, lids, per_tile, per_tile, **kw)
    else:
        lowered = seg_minmax_pallas.lower(vals, lids, per_tile, per_tile,
                                          is_min=kind == "min", **kw)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
