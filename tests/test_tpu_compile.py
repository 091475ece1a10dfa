"""The main path's kernels compile for a TPU v5e at real sizes.

Nothing runs: the TPU compiler, which is installed, compiles for a chip
that is described, not attached, and refuses what Mosaic or the chip's
memory would refuse (unsupported gathers, scoped-VMEM overruns). The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import hash_join as hj
from repro.kernels import segment_reduce as sr
from repro.kernels import session_window as sw

FRAGMENT_ROWS = 1_500_000          # TPC-H SF1 lineitem in 4 partitions
SESSION_ROWS = 1 << 19             # TPCx-BB SF1 clicks in 8 partitions


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("probe", [hj._sorted_probe_call,
                                   hj._sorted_probe_range_call],
                         ids=["sorted_probe", "sorted_probe_range"])
def test_probe_compiles_at_fragment_size(one_chip, probe):
    n = FRAGMENT_ROWS
    compiled = probe.lower(
        _spec((2,), jnp.int32, one_chip),
        _spec((hj.NB + 1,), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        iters=hj._DEPTH_STEPS[-1]).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("groups", [6, 32_768])
def test_segment_reduce_compiles_to_mosaic(one_chip, groups):
    n = FRAGMENT_ROWS
    block_n, seg_block = sr._tiling(n, groups, interpret=False)
    fn = functools.partial(sr._segment_reduce_2d, num_segments=groups,
                           mode="sum", block_n=block_n, seg_block=seg_block,
                           interpret=False)
    compiled = jax.jit(fn).lower(_spec((6, n), jnp.float32, one_chip),
                                 _spec((n,), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_session_window_compiles_at_fragment_size(one_chip):
    n = SESSION_ROWS
    cols = tuple(_spec((n,), dt, one_chip)
                 for dt in (jnp.int32, jnp.int32, jnp.int32, jnp.int8))
    scalar = _spec((), jnp.int32, one_chip)
    compiled = sw.session_window.lower(
        cols, scalar, _spec((31_250,), jnp.int32, one_chip), scalar,
        scalar, names=sw.COLUMNS, views=5,
        max_purchases=SESSION_ROWS >> 5).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_flash_attention_compiles_at_internlm2_widths(one_chip):
    b, s, d = 1, 2048, 128                  # head_dim 128, 16 / 8 heads
    fn = functools.partial(fa.flash_attention_hmajor, causal=True,
                           block_q=128, block_k=128, interpret=False)
    compiled = jax.jit(fn).lower(
        _spec((b, 16, s, d), jnp.bfloat16, one_chip),
        _spec((b, 8, s, d), jnp.bfloat16, one_chip),
        _spec((b, 8, s, d), jnp.bfloat16, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
