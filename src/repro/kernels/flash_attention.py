"""Blocked flash attention (causal / sliding-window) as a Pallas TPU kernel.

TPU-native tiling: the grid is (batch, q_heads, Q_blocks); each program
holds one (BQ, hd) query tile in VMEM and streams (BK, hd) key/value tiles
through the MXU with an online-softmax carry (m, l, acc) kept in VMEM
scratch.  Block sizes are MXU-aligned (multiples of 128 on the lane dim,
8/16 on the sublane dim for f32/bf16).

GQA is handled by indexing the KV head as q_head // (H // Hkv) in the
BlockSpec index_map — no KV duplication in HBM or VMEM.

Causality is exploited at the *block* level: KV blocks strictly above the
diagonal are skipped (the kernel's KV loop bound depends on the Q block
index), so the causal kernel does ~half the FLOPs of a dense one — the same
work-skipping idea Caiti applies to I/O (never touch what you can avoid).

On the CPU the kernel runs in Pallas interpret mode (the tests check it
against kernels/ref.py there); on a TPU the same pallas_call lowers to
Mosaic, and tests/test_tpu_compile.py compiles it for a v5e.  It is off
the serving path: prefill uses ``ref.flash_attention_ref``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float, causal: bool,
                 window: int, bq: int, bk: int, seq_k: int):
    """One (batch, q_head, q_block) program.

    q_ref: (BQ, hd) VMEM tile;  k_ref/v_ref: (S, hd) full rows for the
    program's kv head (streamed in BK chunks below);  o_ref: (BQ, hd).
    """
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale
    hd = q.shape[-1]

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def body(ki, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(pl.multiple_of(ki * bk, bk), bk), :
                  ].astype(jnp.float32)
        v = v_ref[pl.ds(pl.multiple_of(ki * bk, bk), bk), :
                  ].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        valid = jnp.full((bq, bk), True)
        if causal:
            valid = valid & (k_pos <= q_pos)
        if window:
            valid = valid & (q_pos - k_pos < window)
        s = jnp.where(valid, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd), jnp.float32)

    n_kv = seq_k // bk
    if causal:
        # block-level causal skip: only blocks with k_start <= q_end
        hi = jnp.minimum(n_kv, (qi * bq + bq + bk - 1) // bk)
    else:
        hi = n_kv
    if window:
        lo = jnp.maximum(0, (qi * bq - window) // bk)
    else:
        lo = 0
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False):
    """q: (B, T, H, hd);  k, v: (B, S, Hkv, hd)  ->  (B, T, H, hd).

    T and S must be multiples of bq / bk (callers pad); hd is the lane dim
    and should be a multiple of 128 for MXU efficiency (64 works, half-lane).
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert T % bq == 0 and S % bk == 0, (T, S, bq, bk)
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(hd)

    # layout: (B, H, T, hd) so the head dim is a grid axis
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, T // bq)

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, seq_k=S),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, S, hd),
                         lambda b, h, i, n_rep=n_rep: (b, h // n_rep, 0, 0)),
            pl.BlockSpec((None, None, S, hd),
                         lambda b, h, i, n_rep=n_rep: (b, h // n_rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
