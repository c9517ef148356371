"""Block transit engine — Caiti's eager-eviction copy as a Pallas kernel.

Two fused primitives the serving/checkpoint tiers use when *transiting*
pages/blocks between memory tiers:

  * ``gather_quantize_crc``  — gather a set of pages from a pool and pack
    them int8 with one f32 scale per page row: the eviction DMA payload
    (2x smaller than bf16 — the compression codec of the KV spill path and
    the gradient/checkpoint compressor).
  * ``scatter_dequantize_crc`` — the reverse: unpack int8 pages and scatter
    them back into pool rows (page-in / restore).

Both resolve the page indirection through scalar prefetch: the page ids
land in SMEM before the grid runs and the BlockSpec ``index_map`` picks
page ``ids[i]`` of the pool (BTT-style mapping walk), so the pipeline DMAs
exactly the transited page through VMEM and no (n, page, ...)
intermediate ever exists in HBM at full precision.  Grid = one program
per transited page.  The scatter writes its output block ``ids[i]`` of a
pool aliased to its input, so untouched pages keep their contents.

Each direction FUSES the transit checksum into the same VMEM traversal as
the int8 pack: the checksum is computed over the exact wire payload (the
int8 bytes, row-major) while it is already resident in VMEM, so the data
is touched ONCE per direction.  The checksum is Adler-32 (zlib's second
checksum): unlike CRC32's bitwise recurrence it reduces to two modular
sums, which vectorize on the VPU in one pass, and ``zlib.adler32`` is the
host-side oracle (``ref.transit_crc_ref`` — bit-identical,
property-tested).  All in-kernel integer math is int32 (the VPU's word);
the checksum word is bitcast to uint32 outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ADLER_MOD = 65521


def _mod(x):
    """x mod 65521 for 0 <= x < 2^31, without vector integer division:
    the f32 quotient is off by at most one, and one correction step each
    way makes the remainder exact."""
    q = (x.astype(jnp.float32) * (1.0 / _ADLER_MOD)).astype(jnp.int32)
    r = x - q * _ADLER_MOD
    r = jnp.where(r < 0, r + _ADLER_MOD, r)
    return jnp.where(r >= _ADLER_MOD, r - _ADLER_MOD, r)


def _page_adler32(qi):
    """Adler-32 of one page's int8 payload, inside the kernel: ``qi`` is
    the (page_sz, F) payload widened to int32, already in VMEM from the
    pack/unpack — the checksum rides the same traversal.  Bit-identical
    to ``zlib.adler32(q.tobytes())`` (row-major two's-complement bytes).

    Adler-32 is two modular sums, so it reduces on the VPU: S1 = 1 +
    sum(d), S2 = n + sum((n - i) * d_i), checksum = S2 << 16 | S1.
    int32 is safe up to page_sz, F <= 32767: per-term (n - i) % M * d <=
    65520 * 255 < 2^31, per-row sums of mod-reduced terms <= F * 65520,
    and the cross-row sum of mod-reduced rows <= page_sz * 65520.
    Returns a (1, 1) int32 holding the checksum's bits."""
    d = jnp.where(qi < 0, qi + 256, qi)                     # byte value
    page_sz, F = d.shape
    n = page_sz * F
    r = jax.lax.broadcasted_iota(jnp.int32, (page_sz, F), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (page_sz, F), 1)
    w = _mod(n - (r * F + c))
    t = _mod(w * d)
    s2 = _mod(jnp.sum(_mod(jnp.sum(t, axis=1, keepdims=True)),
                      axis=0, keepdims=True) + n)           # (1, 1)
    s1 = _mod(1 + jnp.sum(_mod(jnp.sum(d, axis=1, keepdims=True)),
                          axis=0, keepdims=True))
    return (s2 << 16) | s1


def _gather_q_crc_kernel(ids_ref, page_ref, q_ref, scale_ref, crc_ref, *,
                         eps: float):
    """Fused spill pass for one page: int8 pack + per-row scale + wire
    checksum, one VMEM traversal (the BlockSpec already fetched page
    ``ids[i]``)."""
    del ids_ref                                             # used by index_map
    x = page_ref[...].astype(jnp.float32)                   # (page_sz, F)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)      # (page_sz, 1)
    scale = amax / 127.0 + eps
    qi = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int32)
    q_ref[...] = qi.astype(jnp.int8)
    scale_ref[...] = scale
    crc_ref[...] = _page_adler32(qi)


def gather_quantize_crc_pallas(pool, page_ids, *, interpret: bool = False,
                               eps: float = 1e-12):
    """Fused gather+quantize+checksum: pool (P, page_sz, F); page_ids
    (n,) int32 -> (q (n, page_sz, F) int8, scales (n, page_sz) f32,
    crcs (n,) uint32) — crcs are Adler-32 of each page's packed int8
    bytes (the DMA wire payload), checked on page-in/restore."""
    P, page_sz, F = pool.shape
    n = page_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[pl.BlockSpec((None, page_sz, F),
                               lambda i, ids: (ids[i], 0, 0))],
        out_specs=[
            pl.BlockSpec((None, page_sz, F), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((None, page_sz, 1), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ids: (i, 0, 0)),
        ],
    )
    q, scales, crcs = pl.pallas_call(
        functools.partial(_gather_q_crc_kernel, eps=eps),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, page_sz, F), jnp.int8),
            jax.ShapeDtypeStruct((n, page_sz, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), pool)
    return (q, scales[..., 0],
            jax.lax.bitcast_convert_type(crcs[:, 0, 0], jnp.uint32))


def _scatter_dq_crc_kernel(ids_ref, q_ref, scale_ref, pool_hbm_ref,
                           page_ref, crc_ref):
    """Restore pass for one page: the incoming int8 payload is checksummed
    WHILE it is in VMEM for the dequantize — the caller compares against
    the crc stored at spill time (a mismatch means the page tore in
    transit).  The output block is pool page ``ids[i]``."""
    del ids_ref, pool_hbm_ref          # index_map / aliased, never read
    qi = q_ref[...].astype(jnp.int32)
    x = qi.astype(jnp.float32) * scale_ref[...]
    page_ref[...] = x.astype(page_ref.dtype)
    crc_ref[...] = _page_adler32(qi)


def scatter_dequantize_crc_pallas(pool, page_ids, q, scales, *,
                                  interpret: bool = False):
    """Fused scatter+dequantize+checksum: the inverse transit pass.
    pool (P, page_sz, F) is aliased to the returned pool.  Returns
    ``(pool, crcs)`` — crcs are Adler-32 of the int8 payload as RECEIVED;
    the caller verifies them against the spill-time values (one pass over
    the data, no separate host checksum walk)."""
    P, page_sz, F = pool.shape
    n = page_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, page_sz, F), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec((None, page_sz, 1), lambda i, ids: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # aliased pool
        ],
        out_specs=[
            pl.BlockSpec((None, page_sz, F), lambda i, ids: (ids[i], 0, 0)),
            pl.BlockSpec((None, 1, 1), lambda i, ids: (i, 0, 0)),
        ],
    )
    new_pool, crcs = pl.pallas_call(
        _scatter_dq_crc_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((P, page_sz, F), pool.dtype),
            jax.ShapeDtypeStruct((n, 1, 1), jnp.int32),
        ],
        # operand 0 is the prefetched ids, so the pool is operand 3
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_ids.astype(jnp.int32), q, scales.astype(jnp.float32)[..., None],
      pool)
    return (new_pool,
            jax.lax.bitcast_convert_type(crcs[:, 0, 0], jnp.uint32))
