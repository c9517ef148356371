"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B, T, H, hd); k, v: (B, S, Hkv, hd) -> (B, T, H, hd). f32 math."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    k = jnp.repeat(k, n_rep, axis=2)
    v = jnp.repeat(v, n_rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(T)[:, None]
    k_pos = jnp.arange(S)[None, :]
    valid = jnp.full((T, S), True)
    if causal:
        valid = valid & (k_pos <= q_pos)
    if window:
        valid = valid & (q_pos - k_pos < window)
    s = jnp.where(valid[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[None, None], p, 0.0)
    out = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_ref(q, k_pool, v_pool, block_table, seq_lens,
                        row_base=0):
    """q: (B, H, hd); pools: (N, page, Hkv*hd) rows; block_table:
    (B, max_pages), page p at row block ``row_base + p``; seq_lens: (B,)
    -> (B, H, hd)."""
    B, H, hd = q.shape
    _, page, F = k_pool.shape
    Hkv = F // hd
    n_rep = H // Hkv
    max_pages = block_table.shape[1]
    scale = 1.0 / math.sqrt(hd)

    # materialize (B, max_pages*page, Hkv, hd) views via the table
    rows = block_table + row_base
    k = k_pool[rows].reshape(B, max_pages * page, Hkv, hd)
    v = v_pool[rows].reshape(B, max_pages * page, Hkv, hd)
    k = jnp.repeat(k, n_rep, axis=2).astype(jnp.float32)
    v = jnp.repeat(v, n_rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), k) * scale
    tok = jnp.arange(max_pages * page)[None, :]
    valid = tok < seq_lens[:, None]
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, :], p, 0.0)
    out = jnp.einsum("bhs,bshd->bhd", p, v)
    return out.astype(q.dtype)


def gather_quantize_ref(pool, page_ids, eps: float = 1e-12):
    x = pool[page_ids].astype(jnp.float32)          # (n, page, F)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = amax / 127.0 + eps
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def scatter_dequantize_ref(pool, page_ids, q, scales):
    x = q.astype(jnp.float32) * scales[..., None]
    return pool.at[page_ids].set(x.astype(pool.dtype))


def transit_crc_ref(q):
    """Host oracle for the fused transit checksum: per-page Adler-32 of
    the packed int8 payload (row-major two's-complement bytes).  Exact
    int64 numpy math — bit-identical to ``zlib.adler32(page.tobytes())``
    and to the in-kernel ``_page_adler32``.  q: (n, page, F) int8 ->
    (n,) uint32."""
    import numpy as np
    mod = 65521
    qn = np.asarray(q, dtype=np.int8)
    n_pages = qn.shape[0]
    d = qn.view(np.uint8).astype(np.int64).reshape(n_pages, -1)
    n = d.shape[1]
    w = np.arange(n, 0, -1, dtype=np.int64)          # weight n - i
    s2 = (d @ w + n) % mod
    s1 = (1 + d.sum(axis=1)) % mod
    return ((s2 << 16) | s1).astype(np.uint32)
