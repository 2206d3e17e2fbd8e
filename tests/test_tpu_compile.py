"""The main-path Pallas kernels compile for a TPU v5e, at the widths the
one-chip smoke runs them (`chip_smoke.py`): the TPU compiler is installed
here and compiles for a described chip with none attached, so every refusal
it would raise on the chip (unaligned DMAs, block shapes, VMEM/SMEM
overflow) fails here first. Nothing runs; these say nothing of results or
speed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and a test worker that described it at
collection would hand the other workers a different test list.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gs_sweep import gs_multisweep_pallas
from repro.kernels.push_scatter import EDGE_CHUNK, push_scatter_pallas

# the one-chip smoke's megakernel operands: grid_2d(1024, 1024) at bs = 128
# has 8,192 row-blocks and ~41k tiles (~5 per row-block)
SMOKE_NB, SMOKE_NNZ = 8192, 41000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _struct(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("bs,d,nb,nnz", [
    (128, 128, SMOKE_NB, SMOKE_NNZ),   # the smoke's megakernel
    (256, 128, 64, 320),               # the widest block that fits VMEM
    (128, 256, 64, 320),               # two lanes of query columns
])
@pytest.mark.parametrize("semiring,combine", [
    ("plus_times", "replace"), ("min_plus", "min_old"),
])
def test_megakernel_lowers_for_v5e(one_chip, semiring, combine, bs, d, nb, nnz):
    n = nb * bs
    i32 = [_struct(one_chip, s, jnp.int32)
           for s in ((nb + 1,), (nnz,), (nb + 1,), (nnz,), (nb,))]
    f32 = [_struct(one_chip, (nnz, bs, bs), jnp.float32)] + [
        _struct(one_chip, (n, d), jnp.float32) for _ in range(4)]

    def run(*a):
        return gs_multisweep_pallas(
            *a, semiring=semiring, combine=combine, bs=bs, sweeps=16,
            eps=1e-6, interpret=False,
        )

    compiled = jax.jit(run).lower(*i32, *f32).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("semiring", [
    "plus_times", "min_plus", "max_min", "max_times",
])
def test_push_scatter_lowers_for_v5e(one_chip, semiring):
    # the smoke's SSSP delta absorption: a 2^20-vertex graph, one lane
    n, d, m_pad, buckets, cap = 1 << 20, 128, 2 * (1 << 20), 4, 64
    slots = [_struct(one_chip, (buckets * cap,), jnp.int32) for _ in range(3)]
    edges = [_struct(one_chip, (m_pad,), jnp.int32),
             _struct(one_chip, (m_pad,), jnp.float32)]
    state = [_struct(one_chip, (n, d), jnp.float32) for _ in range(2)]

    def run(*a):
        return push_scatter_pallas(
            *a, semiring=semiring, buckets=buckets, cap=cap, ecap=EDGE_CHUNK,
            interpret=False,
        )

    compiled = jax.jit(run).lower(*slots, *edges, *state).compile()
    assert "tpu_custom_call" in compiled.as_text()
