"""Paged decode attention — the BTT mapping table fused into a Pallas kernel.

The serving engine stores KV in fixed-size *pages* of an HBM pool; a block
table maps (sequence, logical page) -> physical page, exactly as BTT maps
lba -> pba.  This kernel performs one decode step: for each sequence it
walks its block-table row, gathers the pages *inside the kernel* (the
lba->pba translation fused into the attention gather — no materialized
(B, S, ...) KV view in HBM), and computes online-softmax attention of the
single query token against every valid cached token.

Grid: (sequence, logical page).  The block table and the sequence lengths
are scalar-prefetched into SMEM, and the K/V BlockSpecs' ``index_map``
reads the physical page for (b, p) from the table, so the pipeline streams
one (page_size, Hkv*hd) page of K and V per step from HBM into VMEM.
Pages past a sequence's length map to its last valid page (the pipeline
skips a re-fetch of an unchanged block) and their compute is skipped.
The online-softmax carry (m, l, acc) lives in VMEM scratch across the
page axis; the output block is written on the last page.

GQA without expanding K/V: q is viewed as (Hkv, n_rep, hd) and each KV
head does one (n_rep, hd) x (hd, page) dot against its own lane slice of
the page.  This mirrors the paper's transit principle: the cache (VMEM)
holds only what is in flight.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, hd: int,
                  scale: float):
    """One (sequence b, page p) step.  q_ref: (Hkv, n_rep, hd);
    k_ref/v_ref: (page_size, Hkv*hd), physical page table[b, p];
    scratch m/l: (Hkv, n_rep, 128) lane-broadcast, acc: (Hkv, n_rep, hd)."""
    del table_ref                                   # used by index_map
    b, p = pl.program_id(0), pl.program_id(1)
    seq_len = lens_ref[b]
    Hkv = q_ref.shape[0]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(p * page_size < seq_len)
    def _step():
        tok = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = tok < seq_len                               # (1, page)
        k = k_ref[...]
        v = v_ref[...]
        for g in range(Hkv):                                # one dot per KV head
            q = q_ref[g]                                    # (n_rep, hd)
            kg = k[:, g * hd:(g + 1) * hd]                  # (page, hd)
            vg = v[:, g * hd:(g + 1) * hd]
            s = jax.lax.dot_general(
                q, kg, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (n_rep, page)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[g][:, :1]                        # (n_rep, 1)
            l_prev = l_ref[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(pr, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pr.astype(vg.dtype), vg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # (n_rep, hd)
            acc_ref[g] = acc_ref[g] * corr + pv
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...][..., :1], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pool, v_pool, block_table, seq_lens,
                           row_base=0, *, interpret: bool = False):
    """q: (B, H, hd);  pools: (N, page_size, Hkv*hd) rows;
    block_table: (B, max_pages) int32, page p at row block
    ``row_base + p``;  seq_lens: (B,) int32 -> (B, H, hd)."""
    B, H, hd = q.shape
    _, page_size, F = k_pool.shape
    Hkv = F // hd
    max_pages = block_table.shape[1]
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    table = (block_table.astype(jnp.int32) + row_base).reshape(-1)

    def page_map(b, p, table_ref, lens_ref):
        # pages past the length re-map to the last valid one: same block
        # index as the previous step, so the pipeline fetches nothing
        last = jnp.maximum((lens_ref[b] + page_size - 1) // page_size - 1, 0)
        return (table_ref[b * max_pages + jnp.minimum(p, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((None, Hkv, n_rep, hd),
                         lambda b, p, t, s: (b, 0, 0, 0)),
            pl.BlockSpec((None, page_size, Hkv * hd), page_map),
            pl.BlockSpec((None, page_size, Hkv * hd), page_map),
        ],
        out_specs=pl.BlockSpec((None, Hkv, n_rep, hd),
                               lambda b, p, t, s: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, n_rep, 128), jnp.float32),
            pltpu.VMEM((Hkv, n_rep, 128), jnp.float32),
            pltpu.VMEM((Hkv, n_rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, hd=hd,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, n_rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, seq_lens.astype(jnp.int32), q.reshape(B, Hkv, n_rep, hd),
      k_pool, v_pool)
    return out.reshape(B, H, hd)
