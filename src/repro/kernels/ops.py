"""jit'd public wrappers for the Pallas kernels.

On a TPU (``jax.default_backend() == 'tpu'``) every pallas_call lowers to
Mosaic and runs as a ``tpu_custom_call``; anywhere else the *same kernel
body* runs in Pallas interpret mode, which is how the CPU tests validate
logic and tiling.  ``interpret`` is chosen here by platform only, so on the
chip it is never True.
"""
from __future__ import annotations

from functools import partial

import jax

from .block_transit import (gather_quantize_crc_pallas,
                            scatter_dequantize_crc_pallas)
from .flash_attention import flash_attention_pallas
from .paged_attention import paged_attention_pallas


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU: kernels lower to Mosaic
    there, and run in interpret mode everywhere else."""
    return jax.default_backend() == "tpu"


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, window, bq, bk):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bk=bk, interpret=not on_tpu())


def _flash_fwd(q, k, v, causal, window, bq, bk):
    return _flash_attention(q, k, v, causal, window, bq, bk), (q, k, v)


def _flash_bwd(causal, window, bq, bk, res, g):
    # backward through the jnp oracle (XLA recompute — the standard
    # fwd-kernel/bwd-recompute split; no training cell needs a dedicated
    # bwd kernel yet)
    from . import ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=causal,
                                                window=window), q, k, v)
    return vjp(g)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    return _flash_attention(q, k, v, causal, window, bq, bk)


@jax.jit
def paged_attention(q, k_pool, v_pool, block_table, seq_lens, row_base=0):
    """Paged decode attention over row pools; ``row_base`` (traced) is
    where the table's page 0 starts, so one executable serves every
    layer of a pool that stacks them."""
    return paged_attention_pallas(q, k_pool, v_pool, block_table, seq_lens,
                                  row_base, interpret=not on_tpu())


@jax.jit
def gather_quantize_crc(pool, page_ids):
    """Fused spill codec: one VMEM pass per page producing the int8
    payload, the f32 scales, AND the Adler-32 wire checksum (vs the
    three-pass quantize / host-checksum / copy composition)."""
    return gather_quantize_crc_pallas(pool, page_ids,
                                      interpret=not on_tpu())


@partial(jax.jit, donate_argnums=0)
def scatter_dequantize_crc(pool, page_ids, q, scales):
    """Fused restore codec: dequantize+scatter plus the checksum of the
    payload as received, for the caller to verify against spill time.
    The pool is donated: the kernel writes its pages in place, and the
    array passed in is dead once the call returns."""
    return scatter_dequantize_crc_pallas(pool, page_ids, q, scales,
                                         interpret=not on_tpu())
