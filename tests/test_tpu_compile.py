"""The bitmap SpMM kernels compile for a TPU v5e that is described, not
attached.

Interpret mode cannot show what the chip's compiler refuses (casts Mosaic
has no rule for, value slicing it cannot lower, SMEM overflow), so every
kernel variant the engine dispatches is compiled here at real widths:
a 128-wide feature block, and slot tables as large as the engine's SMEM
guard admits (``kernels/pack.py``).  The topology is described inside a
fixture, never at import: only one process at a time may load the TPU
compiler library, and the test workers all import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitmap_spmm import (
    bitmap_spmm_fused_pallas,
    bitmap_spmm_pallas,
)
from repro.kernels.pack import (
    _SMEM_BUDGET,
    TILE,
    WORDS,
    fits_vmem,
    fused_fits_vmem,
)

FB = 128
N_ROW_TILES = 256          # 32,768 destination rows
N_SRC = 32_768
# the most slots the engine's SMEM guard admits: four int32 slot/run
# tables for the plain kernel, eight for the fused one
PLAIN_SLOTS = _SMEM_BUDGET // (4 * 4)
FUSED_SLOTS = _SMEM_BUDGET // (8 * 4)
N_PLANES = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, bitmap_bytes):
    assert "tpu_custom_call" in compiled.as_text()
    # the kernel reads the packed bitmaps in place: a relayout copy of
    # them (a minor axis padded to 128 lanes) would show as temp memory
    assert compiled.memory_analysis().temp_size_in_bytes < bitmap_bytes


@pytest.mark.parametrize(
    "op, row_window",
    [("sum", 128), ("sum", 256), ("min", 128), ("max", 128)],
    ids=["sum-w128", "sum-w256", "min", "max"],
)
def test_plain_kernel_compiles_for_v5e(one_chip, op, row_window):
    assert fits_vmem(FB, FB, 4, n_slots=PLAIN_SLOTS, row_window=row_window)
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    args = (
        i32((PLAIN_SLOTS,)),
        i32((PLAIN_SLOTS,)),
        i32((N_ROW_TILES,)),
        i32((N_ROW_TILES,)),
        _shape(one_chip, (PLAIN_SLOTS, WORDS, TILE), jnp.uint32),
        _shape(one_chip, (N_SRC, FB), jnp.float32),
    )
    zero = {"sum": 0.0, "min": float("inf"), "max": 0.0}[op]
    fn = functools.partial(
        bitmap_spmm_pallas,
        n_dst_pad=N_ROW_TILES * TILE,
        feature_block=FB,
        op=op,
        zero=zero,
        interpret=False,
        row_window=row_window,
    )
    _assert_kernel(jax.jit(fn).lower(*args).compile(), args[4].size * 4)


def test_fused_kernel_compiles_for_v5e(one_chip):
    assert fused_fits_vmem(FB, FB, 4, n_planes=N_PLANES, n_slots=FUSED_SLOTS)
    i32 = functools.partial(_shape, one_chip, dtype=jnp.int32)
    args = (
        *(i32((FUSED_SLOTS,)) for _ in range(6)),
        i32((N_ROW_TILES,)),
        i32((N_ROW_TILES,)),
        _shape(one_chip, (FUSED_SLOTS, WORDS, TILE), jnp.uint32),
        _shape(one_chip, (FUSED_SLOTS, N_PLANES, WORDS, TILE), jnp.uint32),
        _shape(one_chip, (N_SRC, FB), jnp.float32),
        _shape(one_chip, (N_ROW_TILES * TILE, FB), jnp.float32),
    )
    fn = functools.partial(
        bitmap_spmm_fused_pallas,
        n_dst_pad=N_ROW_TILES * TILE,
        plane_weights=tuple(float(2**k) for k in range(N_PLANES)),
        feature_block=FB,
        interpret=False,
    )
    _assert_kernel(jax.jit(fn).lower(*args).compile(), args[9].size * 4)
