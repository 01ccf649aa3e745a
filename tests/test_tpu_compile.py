"""Compile the partitioner's Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) proves the arithmetic; only the
TPU compiler proves the kernels lower — block shapes that break the
(8, 128) tiling rule, or gathers Mosaic cannot lower, pass every
interpret-mode test and are refused here.  No chip is needed: the
topology is described, not attached, and nothing runs.

Widths are those of the main path on a 64³ box mesh in 128 parts: the
level-0 packed operator is n = 262,144 rows at ELL width 32, the level-1
bucket is 2 × 131,072, and the sharded refinement's frontier is 128
shards × 1,167 rows (padded to 1,168, in row blocks of 16) at width 26,
the dual graph's max frontier degree, into 128 parts; the unbatched
table takes a 4,096-row block of it.  The Lanczos restart program is
compiled too, over the hub-split operator of the R-MAT cell (2,048 packed
rows, a 64-slot head, 512 overflow rows, 16 segments, a 100-step window).

The Pallas entry points are called with ``interpret=False`` directly:
under ``JAX_PLATFORMS=cpu`` the ops' ``on_tpu()`` sees the CPU and would
pick interpret mode.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ell_spmv.kernel import ell_spmv_batched_pallas, ell_spmv_pallas
from repro.kernels.segment_sum.kernel import (
    segment_sum_batched_pallas,
    segment_sum_pallas,
)

N_LEVEL0, ELL_W = 262_144, 32
SHARDS, HALO, FRONTIER_W, NPARTS_PAD = 128, 1168, 26, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_ell_spmv_compiles(one_chip):
    _compile(functools.partial(ell_spmv_pallas, block_n=1024,
                               interpret=False), one_chip,
             ((ELL_W, N_LEVEL0), jnp.int32), ((ELL_W, N_LEVEL0), jnp.float32),
             ((N_LEVEL0,), jnp.float32))


def test_ell_spmv_batched_compiles(one_chip):
    b, n = 2, N_LEVEL0 // 2
    _compile(functools.partial(ell_spmv_batched_pallas, block_n=1024,
                               interpret=False), one_chip,
             ((b, ELL_W, n), jnp.int32), ((b, ELL_W, n), jnp.float32),
             ((b, n), jnp.float32))


def test_connection_table_compiles(one_chip):
    rows, m = 4096, 2048 + SHARDS * HALO
    _compile(functools.partial(segment_sum_pallas, nparts_pad=NPARTS_PAD,
                               block_b=256, interpret=False), one_chip,
             ((m,), jnp.int32), ((rows, FRONTIER_W), jnp.int32),
             ((rows, FRONTIER_W), jnp.float32))


def test_connection_table_batched_compiles(one_chip):
    m = 2048 + SHARDS * HALO
    _compile(functools.partial(segment_sum_batched_pallas,
                               nparts_pad=NPARTS_PAD, block_b=16,
                               interpret=False), one_chip,
             ((SHARDS, m), jnp.int32), ((SHARDS, HALO, FRONTIER_W), jnp.int32),
             ((SHARDS, HALO, FRONTIER_W), jnp.float32))


def test_packed_restart_over_a_hub_split_operator_compiles(one_chip):
    from repro.core.lanczos import _packed_restart
    from repro.core.laplacian import EllLaplacian

    n, w, r = 2048, 64, 512

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    op = EllLaplacian(
        cols=shape((n, w), jnp.int32), vals=shape((n, w), jnp.float32),
        diag=shape((n,), jnp.float32), n=n,
        over_cols=shape((r, w), jnp.int32), over_vals=shape((r, w), jnp.float32),
        over_owner=shape((r,), jnp.int32))
    vec = shape((n,), jnp.float32)
    text = _packed_restart.lower(op, vec, vec, shape((n,), jnp.int32),
                                 n_seg=16, window=100).compile().as_text()
    assert "indices_are_sorted=true" in text
