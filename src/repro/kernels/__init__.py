"""Pallas TPU kernels for the perf-critical compute of the serving/transit
path: blocked flash attention, paged (block-table) decode attention, and the
transit gather/scatter+int8 codec.  See ops.py for the jit'd public API and
ref.py for the pure-jnp oracles every kernel is validated against."""
from .ops import (flash_attention, gather_quantize_crc, paged_attention,
                  scatter_dequantize_crc)

__all__ = ["flash_attention", "paged_attention", "gather_quantize_crc",
           "scatter_dequantize_crc"]
