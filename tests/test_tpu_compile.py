"""Compile the serving path's Pallas kernels for a TPU v5e at qwen2.5-3b
widths, with no chip attached: the TPU compiler is installed here and
compiles for a described topology, so it refuses what Mosaic would refuse
on the chip (tiling, VMEM limits, unaligned slices) at no chip time.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and pytest-xdist imports
this file in every worker.  All such compiles live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_transit import (gather_quantize_crc_pallas,
                                         scatter_dequantize_crc_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas

# qwen2.5-3b: 16 heads, 2 KV heads, head_dim 128 -> a KV page row of 256
PAGES, PAGE, ROW = 256, 16, 256
B, H, HKV, HD, MAX_PAGES = 4, 16, 2, 128, 64


@pytest.fixture(scope="module")
def sds():
    """Shapes placed on one described v5e chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("n_ids", [1, 36])
@pytest.mark.parametrize("direction", ["spill", "restore"])
def test_codec_compiles_for_v5e(sds, direction, n_ids):
    pool = sds((PAGES, PAGE, ROW), jnp.bfloat16)
    ids = sds((n_ids,), jnp.int32)
    if direction == "spill":
        _compile(gather_quantize_crc_pallas, pool, ids)
    else:
        _compile(scatter_dequantize_crc_pallas, pool, ids,
                 sds((n_ids, PAGE, ROW), jnp.int8),
                 sds((n_ids, PAGE), jnp.float32))


def test_paged_attention_compiles_for_v5e(sds):
    kv = sds((PAGES, PAGE, HKV, HD), jnp.bfloat16)
    _compile(paged_attention_pallas, sds((B, H, HD), jnp.bfloat16), kv, kv,
             sds((B, MAX_PAGES), jnp.int32), sds((B,), jnp.int32))


def test_flash_attention_compiles_for_v5e(sds):
    kv = sds((1, 512, HKV, HD), jnp.bfloat16)
    _compile(flash_attention_pallas, sds((1, 512, H, HD), jnp.bfloat16),
             kv, kv)


@pytest.mark.parametrize("n_pools,n_slots", [(36, 1), (1, B)])
def test_kv_slot_writer_compiles_in_place_for_v5e(sds, n_pools, n_slots):
    """The KV slot writer at prefill's shape (every layer, one slot) and
    decode's (one layer, B slots): the donated pools alias its outputs,
    so the chip updates them in place rather than copying them."""
    from repro.serve.kvcache import _write_slots
    pools = tuple(sds((PAGES, PAGE, HKV, HD), jnp.bfloat16)
                  for _ in range(n_pools))
    rows = sds((n_pools, n_slots, HKV, HD), jnp.bfloat16)
    idx = sds((n_slots,), jnp.int32)
    compiled = _write_slots.lower(lambda name: None, pools, pools, idx, idx,
                                  rows, rows).compile()
    pool_bytes = 2 * n_pools * PAGES * PAGE * HKV * HD * 2
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
