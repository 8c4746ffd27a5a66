"""The main path's Pallas kernels compile for a TPU v5e (no chip needed).

Interpret mode accepts kernels the chip's compiler refuses (block shapes
off the (8, 128) tiling, primitives Mosaic does not lower, scalar stores to
VMEM), so every other kernel test can pass while the device executor
cannot run at all.  These tests hand both kernels to the TPU compiler for
a described, unattached ``v5e:2x2`` topology at ``chip_smoke.py``'s shapes
(9 profile rows, 7,813 pages of 256 items from 2M keys, 12 cells per row,
200k point queries), and ``price_grid`` also at the write cell's launch.
Nothing runs; a refusal raises.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import price_grid as pg
from repro.kernels import profile_grid as prg

K, PAGES, CELLS, QUERIES = 9, 7_813, 12, 200_000


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
    # cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


#: The write cell's launch (``bench/configs/osm_pgm.json``): the read row
#: and the merge burst's row over 31,250 pages, two cells on the first.
#: The cell launches ``("lfu", True, False)``; ``("lfu", True, True)`` is
#: the same launch with a write stream.
WRITE_CELL = (2, 31_250, 2)


@pytest.mark.parametrize("policy,has_sorted,has_write,shape", [
    pytest.param(p, s, w, (K, PAGES, CELLS), id=f"{p}-{s}-{w}")
    for p, s, w in [
        ("lru", False, False), ("lru", True, False),
        ("fifo", False, False), ("fifo", True, False),
        ("lfu", False, False), ("lfu", True, False),
        ("multi", False, False), ("multi", True, False),
        ("lru", False, True)]
] + [pytest.param("lfu", True, True, WRITE_CELL, id="lfu-True-True"),
      pytest.param("lfu", True, False, WRITE_CELL,
                    id="lfu-True-False-write-cell")])
def test_price_grid_compiles_for_v5e(one_chip, policy, has_sorted,
                                     has_write, shape):
    lfu = policy in ("lfu", "multi")
    k, pages, cells = shape
    rows = _shape(one_chip, (k, pages))
    unused = _shape(one_chip, (k, 1))
    args = [
        rows,
        rows if lfu else unused,                          # sorted_probs
        rows if lfu and has_sorted else unused,           # cov_desc
        _shape(one_chip, (k, pg._F32_COLS)),
        _shape(one_chip, (k, pg._I32_COLS), jnp.int32),
        _shape(one_chip, (k, cells)),
        _shape(one_chip, (k, cells), jnp.int32),
        _shape(one_chip, (k, cells), jnp.int32),
        rows if has_write else None,                      # wprobs
        rows if has_write and lfu else None,              # wprobs_q
    ]

    def solve(*a):
        return pg.price_grid(policy, *a, has_sorted=has_sorted,
                             has_write=has_write, interpret=False)

    compiled = jax.jit(solve).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,radius,classes", [
    # the smoke's RMI branch grid: 11 candidates whose leaves mix 12 pow2
    # eps classes, the widest a 39-page band
    pytest.param(11, 19, 12, id="39-page-band"),
    # the published books grid's branches 64..512: leaf errors quantized
    # to 4,096, a 65-page band, over 13 eps classes
    pytest.param(11, 32, 13, id="65-page-band"),
])
def test_profile_grid_compiles_for_v5e(one_chip, rows, radius, classes):
    c_ipp = 256
    width = 2 * radius + 1

    def occupancy(keys, pages, lut):
        return prg.profile_grid(keys, pages, lut, width=width,
                                pad=PAGES + 2 * radius, interpret=False)

    compiled = jax.jit(occupancy).lower(
        _shape(one_chip, (rows, QUERIES), jnp.int32),
        _shape(one_chip, (1, QUERIES), jnp.int32),
        _shape(one_chip, (-(-width // 8) * 8, classes * c_ipp))).compile()
    assert "tpu_custom_call" in compiled.as_text()
