"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

Each case lowers a kernel at the widths a real deployment uses and
compiles it against a described ``v5e:2x2`` topology: Mosaic then refuses
what interpret mode accepts (unaligned blocks, dynamic vector indexing,
SMEM/VMEM overflow). Nothing runs, so these say nothing about results;
``tests/test_kernels.py`` checks those in interpret mode.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.lru_scan import lru_scan
from repro.kernels.ops import LOOP_BRANCHES
from repro.kernels.ready_queue import MAX_DEPS, MAX_TASKS, ready_queue_call


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


_BRANCHES = tuple((lambda x, y, z, _f=f: _f(x, y))
                  for f in LOOP_BRANCHES.values())


@pytest.mark.parametrize("n,m,rows,width", [
    (512, 1, 64, 512),                 # chip_smoke's chain stream
    (MAX_TASKS, MAX_DEPS, 512, 128),   # the stated cap: SMEM tables, VMEM slab
])
def test_ready_queue_compiles(one_chip, n, m, rows, width):
    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda slab, t, d, r, rem, tail: ready_queue_call(
                 slab, t, d, r, rem, tail, branches=_BRANCHES,
                 interpret=False),
             s((rows, width), jnp.float32), s((n, 5)), s((n, m)),
             s((n + 1,)), s((n + 1,)), s((1,)))


def test_flash_attention_compiles_at_h2o_prefill_widths(one_chip):
    # h2o-danube-3-4b prefill: 32 query heads over 8 KV heads of 120
    # (not a multiple of 128: legal only as the block's whole last dim)
    def s(h):
        return jax.ShapeDtypeStruct((1, h, 256, 120), jnp.bfloat16,
                                    sharding=one_chip)

    _compile(lambda q, k, v: flash_attention(q, k, v, window=4096,
                                             interpret=False),
             s(32), s(8), s(8))


def test_lru_scan_compiles_at_recurrentgemma_widths(one_chip):
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    _compile(lambda a, b, h0: lru_scan(a, b, h0, interpret=False),
             s(2, 512, 2560), s(2, 512, 2560), s(2, 2560))


def test_grouped_matmul_compiles_at_granite_moe_widths(one_chip):
    g, k, n, block_m = 40, 1536, 512, 128
    _compile(lambda x, w, t: grouped_matmul(x, w, t, block_m=block_m,
                                            interpret=False),
             jax.ShapeDtypeStruct((g * block_m, k), jnp.bfloat16,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16,
                                  sharding=one_chip),
             jax.ShapeDtypeStruct((g,), jnp.int32, sharding=one_chip))
