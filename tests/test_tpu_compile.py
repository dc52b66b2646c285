"""The main-path Pallas kernels compile for TPU v5e at dlrm-m1 widths.

Interpret mode (``tests/test_kernels.py``) accepts blocks, slices and memory
use that the chip's compiler refuses. These tests compile ``gather_pool``
and ``cache_probe`` ahead of time for one chip of a described ``v5e:2x2``
topology (no chip needed) at D=128, B=32, T=61, P=64, and check that the
kernel is in the compiled program. The topology is described inside a
fixture, never at import, so every xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cache_probe import cache_probe
from repro.kernels.gather_pool import gather_pool

B, T, P, D = 32, 61, 64, 128      # chunk, M1 user tables, pooling, row width
ROWS = 8_148_824                  # the smoke store: ~8.1M rows, 1 GiB
# M1's item side (dlrm-m1-rank): 50 candidates a query over 30 item
# tables, bags padded to 32, an FM-direct store of 3.7M rows
ITEM_BAGS, ITEM_P, ITEM_ROWS = B * 50 * 30, 32, 3_719_192
SETS, WAYS = 3640, 8              # a 4 MiB row cache


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one; keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.int8])
def test_gather_pool_compiles_for_v5e(one_chip, dtype):
    compiled = _compile(
        lambda pay, sc, bi, idx: gather_pool(pay, sc, bi, idx,
                                             interpret=False),
        [((ROWS, D), dtype), ((ROWS,), jnp.float32), ((ROWS,), jnp.float32),
         ((B * T, P), jnp.int32)], one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == B * T * D * 4


def test_cache_probe_compiles_for_v5e(one_chip):
    n = B * T * P
    compiled = _compile(
        lambda tt, tr, data, qt, qr, sets: cache_probe(
            tt, tr, data, qt, qr, sets, interpret=False),
        [((SETS, WAYS), jnp.int32), ((SETS, WAYS), jnp.int32),
         ((SETS, WAYS, D), jnp.float32), ((n,), jnp.int32),
         ((n,), jnp.int32), ((n,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= n * (D + 1) * 4


def test_item_gather_pool_compiles_for_v5e(one_chip):
    """The ranking step's item block through the same kernel."""
    compiled = _compile(
        lambda pay, sc, bi, idx: gather_pool(pay, sc, bi, idx,
                                             interpret=False),
        [((ITEM_ROWS, D), jnp.uint8), ((ITEM_ROWS,), jnp.float32),
         ((ITEM_ROWS,), jnp.float32), ((ITEM_BAGS, ITEM_P), jnp.int32)],
        one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == (
        ITEM_BAGS * D * 4)
