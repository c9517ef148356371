"""Compile the serving path's Pallas kernels for a TPU v5e at qwen2.5-3b
widths, with no chip attached: the TPU compiler is installed here and
compiles for a described topology, so it refuses what Mosaic would refuse
on the chip (tiling, VMEM limits, unaligned slices) at no chip time.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and pytest-xdist imports
this file in every worker.  All such compiles live in this one file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.block_transit import (gather_quantize_crc_pallas,
                                         scatter_dequantize_crc_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_attention_pallas

# qwen2.5-3b: 36 layers, 16 heads, 2 KV heads, head_dim 128 -> a KV page
# row of 256; the cache stacks every layer's pages as row blocks
L, PAGES, PAGE, ROW = 36, 256, 16, 256
B, H, HKV, HD, MAX_PAGES = 4, 16, 2, 128, 64
POOL = (L * PAGES, PAGE, ROW)
POOL_BYTES = L * PAGES * PAGE * ROW * 2          # one bf16 pool


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


@pytest.fixture
def ops(monkeypatch):
    """The public kernel wrappers, traced as on a TPU (Mosaic kernels,
    not interpret mode)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    return ops


def _pool_users(hlo: str) -> list[str]:
    """The instructions of a compiled module that take the ``k_pool`` or
    ``v_pool`` parameter as an operand."""
    params = re.findall(r"(%[kv]_pool[.\d]*) = \S+ parameter\(", hlo)
    assert len(params) == 2, params
    users = []
    for line in hlo.splitlines():
        _, eq, rhs = line.partition(" = ")
        if eq and any(re.search(re.escape(p) + r"[,)]", rhs) for p in params):
            users.append(rhs)
    return users


@pytest.mark.parametrize("n_ids", [1, 36])
@pytest.mark.parametrize("direction", ["spill", "restore"])
def test_codec_compiles_for_v5e(sds, direction, n_ids):
    pool = sds(POOL, jnp.bfloat16)
    ids = sds((n_ids,), jnp.int32)
    if direction == "spill":
        _compile(gather_quantize_crc_pallas, pool, ids)
    else:
        _compile(scatter_dequantize_crc_pallas, pool, ids,
                 sds((n_ids, PAGE, ROW), jnp.int8),
                 sds((n_ids, PAGE), jnp.float32))


def test_paged_attention_compiles_for_v5e(sds):
    kv = sds(POOL, jnp.bfloat16)
    _compile(paged_attention_pallas, sds((B, H, HD), jnp.bfloat16), kv, kv,
             sds((B, MAX_PAGES), jnp.int32), sds((B,), jnp.int32),
             sds((1,), jnp.int32))


def test_paged_attention_reads_row_pools_as_they_are(sds, ops):
    """Decode attention as the cache calls it: every layer's row pool and
    a traced layer offset.  The kernel takes both pools straight from
    the parameters, with no relayout (reshape) or copy of either."""
    kv = sds(POOL, jnp.bfloat16)
    lowered = ops.paged_attention.lower(
        sds((B, H, HD), jnp.bfloat16), kv, kv,
        sds((B, MAX_PAGES), jnp.int32), sds((B,), jnp.int32),
        sds((1,), jnp.int32))
    users = _pool_users(lowered.compile().as_text())
    assert len(users) == 1 and "tpu_custom_call" in users[0], users


def test_flash_attention_compiles_for_v5e(sds):
    kv = sds((1, 512, HKV, HD), jnp.bfloat16)
    _compile(flash_attention_pallas, sds((1, 512, H, HD), jnp.bfloat16),
             kv, kv)


@pytest.mark.parametrize("n_layers,n_slots", [(L, 1), (1, B)])
def test_kv_slot_writer_compiles_in_place_for_v5e(sds, n_layers, n_slots):
    """The KV slot writer at prefill's shape (every layer, one slot) and
    decode's (one layer, B slots): the donated K and V pools alias its
    outputs whole, so the chip updates them in place rather than copying
    them."""
    from repro.serve.kvcache import _write_slots
    pool = sds(POOL, jnp.bfloat16)
    rows = sds((n_layers, n_slots, HKV, HD), jnp.bfloat16)
    idx = sds((n_slots,), jnp.int32)
    compiled = _write_slots.lower(lambda name: None, pool, pool,
                                  sds((n_layers,), jnp.int32), idx, idx,
                                  rows, rows).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * POOL_BYTES


@pytest.mark.parametrize("n_ids", [1, L])
def test_restore_codec_compiles_in_place_for_v5e(sds, ops, n_ids):
    """The public restore codec donates its pool: the compiled call
    aliases the whole pool to its output, so a page-in writes its rows
    in place instead of copying every layer's."""
    compiled = ops.scatter_dequantize_crc.lower(
        sds(POOL, jnp.bfloat16), sds((n_ids,), jnp.int32),
        sds((n_ids, PAGE, ROW), jnp.int8),
        sds((n_ids, PAGE), jnp.float32)).compile()
    assert compiled.memory_analysis().alias_size_in_bytes == POOL_BYTES
