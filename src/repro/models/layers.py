"""Shared model primitives: norms, RoPE, flash-pattern chunked attention
(XLA path), GQA, SwiGLU/GELU MLPs, and the capacity-routed MoE block
(expert-parallel over the TP axis via shard_map).

Everything is pure-functional over explicit param pytrees; parameter layout
conventions (documented here because sharding rules key off them):

  attn:  wq (D, H*hd)   wk/wv (D, Hkv*hd)   wo (H*hd, D)   [+ optional biases]
  mlp:   wg/wu (D, F)   wd (F, D)
  moe:   router (D, E)  wg/wu (E, D, F)     wd (E, F, D)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



# --------------------------------------------------------------------- norms
def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale)).astype(x.dtype)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return (((x32 - mu) * jax.lax.rsqrt(var + eps)) * scale + bias).astype(x.dtype)


def apply_norm(x, p, kind: str):
    if kind == "ln":
        return layer_norm(x, p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


def init_norm(d: int, kind: str):
    if kind == "ln":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------- RoPE
def rope(x, positions, theta: float):
    """x: (..., T, H, hd); positions: broadcastable to (..., T)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., T, half)
    cos = jnp.cos(ang)[..., None, :]                                 # (..., T, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(positions, d: int, dtype):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) *
                    jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------- chunked attention
def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def chunked_attention(q, k, v, *, q_pos, k_pos, causal: bool, window: int = 0,
                      kv_mask=None, chunk: int = 512, dtype=jnp.bfloat16):
    """Online-softmax attention, scanning KV in chunks (flash pattern in XLA).

    q: (B, T, H, hd);  k, v: (B, S, Hkv, hd);  q_pos: (B, T);  k_pos: (B, S)
    kv_mask: optional (B, S) bool of valid kv entries.
    Memory is bounded by (B, T, H, chunk) — the TPU Pallas kernel in
    repro.kernels implements the same contract with VMEM tiles.

    GQA is computed GROUPED ("btgrd,bcgd->btgrc"): the KV is never
    repeated to H heads nor upcast to f32 in HBM — operands stay bf16 and
    the MXU accumulates in f32 (preferred_element_type).  The repeat+cast
    used to dominate the HBM roofline term of GQA archs.
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    if S <= max(chunk, 2048) or S % chunk != 0:
        return _dense_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                causal=causal, window=window, kv_mask=kv_mask,
                                dtype=dtype)
    n_chunks = S // chunk
    kc = k.reshape(B, n_chunks, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    kpc = k_pos.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    mc = (kv_mask.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
          if kv_mask is not None else jnp.ones((n_chunks, B, chunk), bool))
    qg = q.reshape(B, T, Hkv, n_rep, hd)

    def body(carry, xs):
        m, l, acc = carry                  # (B,T,g,r) / (B,T,g,r,hd)
        kch, vch, kp, msk = xs
        s = jnp.einsum("btgrd,bcgd->btgrc", qg, kch,
                       preferred_element_type=jnp.float32) * scale
        valid = msk[:, None, :]                              # (B, 1, C)
        if causal:
            valid = valid & (kp[:, None, :] <= q_pos[:, :, None])
        if window:
            valid = valid & (q_pos[:, :, None] - kp[:, None, :] < window)
        vmask = valid[:, :, None, None, :]                   # (B,T,1,1,C)
        s = jnp.where(vmask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(vmask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "btgrc,bcgd->btgrd", p.astype(dtype), vch,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, T, Hkv, n_rep), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, n_rep), jnp.float32)
    a0 = jnp.zeros((B, T, Hkv, n_rep, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kpc, mc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, T, H, hd).astype(dtype)


def sharded_attention(q, k, v, *, q_pos, k_pos, causal: bool,
                      window: int = 0, kv_mask=None, chunk: int = 512,
                      dtype=jnp.bfloat16, ctx=None):
    """chunked_attention with explicit Q-sequence sharding over the model
    axis when the head count does not divide TP.

    Why: GSPMD shards attention intermediates by head; with H % tp != 0
    (deepseek 56 heads on a 16-way axis) it *replicates* the (B,T,H,S)
    score tensors on every device — the dominant HBM term of the train_4k
    roofline.  Sharding the query/sequence axis instead keeps per-device
    scores at 1/tp and costs one all-gather of the (small) K/V plus one of
    the (B,T,hidden) output.
    """
    if (ctx is None or getattr(ctx, "mesh", None) is None
            or ctx.model_axis is None):
        return chunked_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                 causal=causal, window=window,
                                 kv_mask=kv_mask, chunk=chunk, dtype=dtype)
    tp = ctx.mesh.shape[ctx.model_axis]
    B, T, H, hd = q.shape
    if H % tp == 0 or T % tp != 0:
        return chunked_attention(q, k, v, q_pos=q_pos, k_pos=k_pos,
                                 causal=causal, window=window,
                                 kv_mask=kv_mask, chunk=chunk, dtype=dtype)
    axis = ctx.model_axis
    b = ctx.batch_axes if ctx.batch_axes else None
    msk = kv_mask if kv_mask is not None else \
        jnp.ones(k.shape[:2], dtype=bool)

    def f(q_l, qp_l, k_l, v_l, kp_l, m_l):
        S_l = k_l.shape[1]
        c = chunk if S_l % chunk == 0 else S_l
        return chunked_attention(q_l, k_l, v_l, q_pos=qp_l, k_pos=kp_l,
                                 causal=causal, window=window, kv_mask=m_l,
                                 chunk=c, dtype=dtype)

    return jax.shard_map(
        f, mesh=ctx.mesh,
        in_specs=(P(b, axis, None, None), P(b, axis),
                  P(b, None, None, None), P(b, None, None, None),
                  P(b, None), P(b, None)),
        out_specs=P(b, axis, None, None),
        check_vma=False,
    )(q, q_pos, k, v, k_pos, msk)


def _dense_attention(q, k, v, *, q_pos, k_pos, causal, window, kv_mask, dtype):
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, T, Hkv, n_rep, hd)
    s = jnp.einsum("btgrd,bsgd->btgrs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.ones((B, T, S), bool)
    if kv_mask is not None:
        valid = valid & kv_mask[:, None, :]
    if causal:
        valid = valid & (k_pos[:, None, :] <= q_pos[:, :, None])
    if window:
        valid = valid & (q_pos[:, :, None] - k_pos[:, None, :] < window)
    vmask = valid[:, :, None, None, :]
    s = jnp.where(vmask, s, -jnp.inf)
    # fully-masked rows (can happen for padded kv) -> uniform-zero output
    m = s.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(vmask, jnp.exp(s - m), 0.0)
    p = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("btgrs,bsgd->btgrd", p.astype(dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, H, hd).astype(dtype)


def decode_update_and_attend(q, cache_k, cache_v, cache_pos, new_k, new_v,
                             pos, *, window: int, ctx, chunk: int, dtype):
    """One decode step against an S-sharded KV cache, with the new token's
    K/V scattered INSIDE the shard_map.

    Why: the cache's S axis is sharded over 'model'; a batch-indexed
    ``.at[b, slot].set`` outside the shard_map is a dynamic scatter across a
    sharded axis — GSPMD falls back to 'involuntary full rematerialization'
    (replicate + repartition the whole multi-GB cache, per layer, per
    token).  Doing the write shard-locally (the owning shard applies it,
    the rest no-op) removes that traffic entirely; attention then merges
    per-shard online-softmax stats with one tiny psum, flash-decoding
    style.

    q: (B,1,H,hd); cache_k/v: (B,S,Hkv,hd); cache_pos: (B,S);
    new_k/v: (B,1,Hkv,hd); pos: (B,).
    Returns (attn_out (B,1,H,hd), ck, cv, cpos).
    """
    B, T, H, hd = q.shape
    S = cache_k.shape[1]
    if (ctx is None or ctx.mesh is None or ctx.model_axis is None
            or S % ctx.mesh.shape[ctx.model_axis] != 0):
        bidx = jnp.arange(B)
        slot = pos % S if window else pos
        ck = cache_k.at[bidx, slot].set(new_k[:, 0].astype(cache_k.dtype))
        cv = cache_v.at[bidx, slot].set(new_v[:, 0].astype(cache_v.dtype))
        cpos = cache_pos.at[bidx, slot].set(pos)
        out = decode_attention(q, ck, cv, k_pos=cpos, pos=pos, window=window,
                               kv_mask=cpos >= 0, ctx=ctx, chunk=chunk,
                               dtype=dtype)
        return out, ck, cv, cpos
    axis = ctx.model_axis
    tp = ctx.mesh.shape[axis]
    bspec = ctx.batch_axes if ctx.batch_axes else None
    Hkv = cache_k.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    S_l = S // tp

    def f(q_l, k_l, v_l, cp_l, nk_l, nv_l, pos_l):
        Bl = q_l.shape[0]
        bidx = jnp.arange(Bl)
        shard = jax.lax.axis_index(axis)
        slot = pos_l % S if window else pos_l
        local = slot - shard * S_l
        in_range = (local >= 0) & (local < S_l)
        idx = jnp.clip(local, 0, S_l - 1)
        cur_k = k_l[bidx, idx]
        cur_v = v_l[bidx, idx]
        cur_p = cp_l[bidx, idx]
        k_l = k_l.at[bidx, idx].set(jnp.where(
            in_range[:, None, None], nk_l[:, 0].astype(k_l.dtype), cur_k))
        v_l = v_l.at[bidx, idx].set(jnp.where(
            in_range[:, None, None], nv_l[:, 0].astype(v_l.dtype), cur_v))
        cp_l = cp_l.at[bidx, idx].set(jnp.where(in_range, pos_l, cur_p))
        # ---- local online-softmax stats over this shard's KV ------------
        # GQA grouped: KV never repeated/upcast (bf16 operands, f32 accum)
        qg = q_l.reshape(Bl, T, Hkv, n_rep, hd)
        s = jnp.einsum("btgrd,bcgd->btgrc", qg, k_l,
                       preferred_element_type=jnp.float32) * scale
        valid = (cp_l >= 0)[:, None, :] & \
            (cp_l[:, None, :] <= pos_l[:, None, None])
        if window:
            valid = valid & (pos_l[:, None, None] - cp_l[:, None, :] < window)
        vmask = valid[:, :, None, None, :]
        s = jnp.where(vmask, s, -jnp.inf)
        m = s.max(axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, -1e30)
        p = jnp.where(vmask, jnp.exp(s - m_safe[..., None]), 0.0)
        l = p.sum(axis=-1)
        acc = jnp.einsum("btgrc,bcgd->btgrd", p.astype(dtype), v_l,
                         preferred_element_type=jnp.float32)
        m_all = jax.lax.pmax(m_safe, axis)
        corr = jnp.exp(m_safe - m_all)
        l_all = jax.lax.psum(l * corr, axis)
        acc_all = jax.lax.psum(acc * corr[..., None], axis)
        out = acc_all / jnp.maximum(l_all, 1e-30)[..., None]
        return out.reshape(Bl, T, H, hd).astype(dtype), k_l, v_l, cp_l

    return jax.shard_map(
        f, mesh=ctx.mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, axis, None, None),
                  P(bspec, axis, None, None), P(bspec, axis),
                  P(bspec, None, None, None), P(bspec, None, None, None),
                  P(bspec)),
        out_specs=(P(bspec, None, None, None), P(bspec, axis, None, None),
                   P(bspec, axis, None, None), P(bspec, axis)),
        check_vma=False,
    )(q, cache_k, cache_v, cache_pos, new_k, new_v, pos)


def decode_attention(q, k, v, *, k_pos, pos, window: int, kv_mask, ctx,
                     chunk: int, dtype):
    """Single-token decode attention with a sequence-sharded KV cache.

    Flash-decoding style TP: the cache's S axis is sharded over the model
    axis; every shard computes partial online-softmax stats over its local
    KV chunk for ALL heads, then stats are merged with one tiny psum of
    (m, l, acc) — the collective is O(B·H·hd), not O(S).  Falls back to the
    plain chunked path off-mesh.
    """
    B, T, H, hd = q.shape
    S = k.shape[1]
    if (ctx is None or ctx.mesh is None or ctx.model_axis is None
            or S % ctx.mesh.shape[ctx.model_axis] != 0):
        return chunked_attention(q, k, v, q_pos=pos[:, None], k_pos=k_pos,
                                 causal=True, window=window, kv_mask=kv_mask,
                                 chunk=chunk, dtype=dtype)
    axis = ctx.model_axis
    bspec = ctx.batch_axes if ctx.batch_axes else None
    Hkv = k.shape[2]
    n_rep = H // Hkv
    scale = 1.0 / math.sqrt(hd)

    def f(q_l, k_l, v_l, kp_l, pos_l, msk_l):
        Bl, T = q_l.shape[0], q_l.shape[1]
        qg = q_l.reshape(Bl, T, Hkv, n_rep, hd)
        s = jnp.einsum("btgrd,bcgd->btgrc", qg, k_l,
                       preferred_element_type=jnp.float32) * scale
        valid = msk_l[:, None, :] & (kp_l[:, None, :] <= pos_l[:, None, None])
        if window:
            valid = valid & (pos_l[:, None, None] - kp_l[:, None, :] < window)
        vmask = valid[:, :, None, None, :]
        s = jnp.where(vmask, s, -jnp.inf)
        m = s.max(axis=-1)
        m_safe = jnp.where(jnp.isfinite(m), m, -1e30)
        p = jnp.where(vmask, jnp.exp(s - m_safe[..., None]), 0.0)
        l = p.sum(axis=-1)
        acc = jnp.einsum("btgrc,bcgd->btgrd", p.astype(dtype), v_l,
                         preferred_element_type=jnp.float32)
        # merge partial stats across the model axis
        m_all = jax.lax.pmax(m_safe, axis)
        corr = jnp.exp(m_safe - m_all)
        l_all = jax.lax.psum(l * corr, axis)
        acc_all = jax.lax.psum(acc * corr[..., None], axis)
        out = acc_all / jnp.maximum(l_all, 1e-30)[..., None]
        return out.reshape(Bl, T, H, hd).astype(dtype)

    return jax.shard_map(
        f, mesh=ctx.mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, axis, None, None),
                  P(bspec, axis, None, None), P(bspec, axis), P(bspec),
                  P(bspec, axis)),
        out_specs=P(bspec, None, None, None),
        check_vma=False,
    )(q, k, v, k_pos, pos, kv_mask)


# ---------------------------------------------------------------- MLP blocks
def mlp_apply(x, p, act: str):
    if act == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    else:
        h = jax.nn.gelu(x @ p["wi"])
    return h @ p["wd"]


def mlp_init(rng, d: int, f: int, act: str, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(f)
    if act == "swiglu":
        return {"wg": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
                "wu": (jax.random.normal(k2, (d, f)) * s_in).astype(dtype),
                "wd": (jax.random.normal(k3, (f, d)) * s_out).astype(dtype)}
    return {"wi": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
            "wd": (jax.random.normal(k3, (f, d)) * s_out).astype(dtype)}


# ----------------------------------------------------------------------- MoE
def moe_local(x, router, wg, wu, wd, *, top_k: int, capacity: int,
              n_experts: int, expert_offset):
    """Token-choice routing with per-expert top-C capacity, on LOCAL tokens
    and LOCAL experts. x: (T, D); wg/wu: (E_l, D, F); wd: (E_l, F, D).
    Returns the partial output (T, D) — caller psums across expert shards.
    """
    T, D = x.shape
    E_l = wg.shape[0]
    logits = (x @ router.astype(x.dtype)).astype(jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, top_k)                       # (T, k)
    topw = topw / (jnp.sum(topw, axis=-1, keepdims=True) + 1e-9)
    local_ids = expert_offset + jnp.arange(E_l)
    hit = (topi[:, :, None] == local_ids[None, None, :])           # (T, k, E_l)
    score = jnp.where(hit, topw[:, :, None], 0.0).sum(axis=1)      # (T, E_l)
    gate, idx = jax.lax.top_k(score.T, capacity)                   # (E_l, C)
    xe = jnp.take(x, idx, axis=0)                                  # (E_l, C, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) * \
        jnp.einsum("ecd,edf->ecf", xe, wu)
    ye = jnp.einsum("ecf,efd->ecd", h, wd)
    ye = ye * gate[..., None].astype(ye.dtype)
    out = jnp.zeros((T, D), ye.dtype).at[idx.reshape(-1)].add(
        ye.reshape(-1, D))
    return out


def moe_capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(math.ceil(n_tokens * top_k / n_experts * cf))
    c = max(c, min(4, n_tokens))       # decode floor: tiny T, skewed routing
    return max(1, min(n_tokens, c))


def moe_apply(x, p, moe_cfg, ctx):
    """x: (B, T, D). Experts sharded over the TP ('model') axis when a mesh
    context is present (EP-over-TP: activations are replicated across 'model'
    here, each shard computes its owned experts, outputs are psum-combined —
    the psum fuses with the usual TP output reduction).

    ZeRO-3 experts: when a 'data' axis exists and the per-expert FFN axis
    divides it, expert weights are additionally STORED sharded over 'data'
    and all-gathered per layer inside the shard_map (storage /dp, transient
    working set = one layer's experts).  A 235B MoE does not fit a 16 GB/
    chip pod otherwise — 29 GB/device of expert params at 16-way EP."""
    B, T, D = x.shape
    E, k, cf = moe_cfg.n_experts, moe_cfg.top_k, moe_cfg.capacity_factor
    if ctx is None or ctx.mesh is None or ctx.model_axis is None:
        cap = moe_capacity(B * T, k, E, cf)
        out = moe_local(x.reshape(-1, D), p["router"], p["wg"], p["wu"],
                        p["wd"], top_k=k, capacity=cap, n_experts=E,
                        expert_offset=0)
        return out.reshape(B, T, D)

    model_axis = ctx.model_axis
    tp = ctx.mesh.shape[model_axis]
    assert E % tp == 0, f"{E} experts not divisible by TP={tp}"
    batch_spec = ctx.batch_axes if ctx.batch_axes else None
    F = p["wg"].shape[-1]
    fsdp = None
    if "data" in ctx.mesh.shape and ctx.mesh.shape["data"] > 1 \
            and F % ctx.mesh.shape["data"] == 0:
        fsdp = "data"       # must mirror parallel.sharding's param rule
    wg_spec = P(model_axis, None, fsdp)
    wu_spec = P(model_axis, None, fsdp)
    wd_spec = P(model_axis, fsdp, None)

    def f(xl, router, wg, wu, wd):
        if fsdp is not None:
            # ZeRO-3 gather: materialize this layer's expert shard
            wg = jax.lax.all_gather(wg, fsdp, axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp, axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp, axis=1, tiled=True)
        Bl, Tl = xl.shape[0], xl.shape[1]
        cap = moe_capacity(Bl * Tl, k, E, cf)
        off = jax.lax.axis_index(model_axis) * (E // tp)
        out = moe_local(xl.reshape(-1, D), router, wg, wu, wd, top_k=k,
                        capacity=cap, n_experts=E, expert_offset=off)
        out = jax.lax.psum(out, model_axis)
        return out.reshape(Bl, Tl, D)

    return jax.shard_map(
        f, mesh=ctx.mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  wg_spec, wu_spec, wd_spec),
        out_specs=P(batch_spec, None, None),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])


def moe_init(rng, d: int, moe_cfg, dtype):
    k0, k1, k2, k3 = jax.random.split(rng, 4)
    E, F = moe_cfg.n_experts, moe_cfg.d_expert
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(F)
    return {
        "router": (jax.random.normal(k0, (d, E)) * s_in).astype(jnp.float32),
        "wg": (jax.random.normal(k1, (E, d, F)) * s_in).astype(dtype),
        "wu": (jax.random.normal(k2, (E, d, F)) * s_in).astype(dtype),
        "wd": (jax.random.normal(k3, (E, F, d)) * s_out).astype(dtype),
    }


# ------------------------------------------------------------ attn (proj) ---
def attn_init(rng, d: int, n_heads: int, n_kv: int, hd: int, bias: bool, dtype):
    ks = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    p = {"wq": (jax.random.normal(ks[0], (d, n_heads * hd)) * s).astype(dtype),
         "wk": (jax.random.normal(ks[1], (d, n_kv * hd)) * s).astype(dtype),
         "wv": (jax.random.normal(ks[2], (d, n_kv * hd)) * s).astype(dtype),
         "wo": (jax.random.normal(ks[3], (n_heads * hd, d))
                * (1.0 / math.sqrt(n_heads * hd))).astype(dtype)}
    if bias:
        p["bq"] = jnp.zeros((n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((n_kv * hd,), dtype)
        p["bv"] = jnp.zeros((n_kv * hd,), dtype)
    return p


def qkv_proj(x, p, n_heads: int, n_kv: int, hd: int):
    B, T, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return (q.reshape(B, T, n_heads, hd), k.reshape(B, T, n_kv, hd),
            v.reshape(B, T, n_kv, hd))


def out_proj(attn_out, p):
    B, T = attn_out.shape[:2]
    return attn_out.reshape(B, T, -1) @ p["wo"]
