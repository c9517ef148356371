"""Plain float32 reference of the dense decoder family (Qwen2, InternLM2).

Straight ``jax.numpy`` at ``Precision.HIGHEST``: RMSNorm, rotary
embeddings (half-split, as both models' modeling files), grouped-query
causal attention, SwiGLU, untied or tied LM head.  No kernel, cache,
page or batching of the program under test is used, and nothing of it is
imported.  It runs layer by layer, a block of sessions at a time, so it
fits beside nothing else on one chip.

The one part of the serving design it models: a session's keys and
values that were paged out at a suspension come back through the int8
codec (one absmax/127 scale per token row of all KV heads of a layer,
K and V apart).  At query position ``p`` the key ``j`` is seen through
the codec when some suspension at cache length ``n`` has ``j < n <= p``.

``precision="fp8"`` is the control: every weight matmul with its inputs
in float8 e4m3 (one scale per activation row and per weight column),
accumulated in float32; attention stays float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn


def _q8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX + 1e-30
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def _mm(a, w, precision):
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        a8, sa = _q8(a, -1)
        w8, sw = _q8(w, 0)
        return jnp.matmul(a8, w8, precision=HI) * sa * sw
    return jnp.matmul(a, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta):
    """x: (c, T, heads, hd) at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def codec(x):
    """int8 round trip of each token row: x (c, T, Hkv, hd)."""
    c, T = x.shape[:2]
    rows = x.reshape(c, T, -1)
    scale = jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(rows / scale), -127, 127)
    return (q * scale).reshape(x.shape)


@partial(jax.jit, static_argnames=("st", "precision"))
def _layer(x, blocks, li, qmask, *, st, precision):
    H, Hkv, hd, eps, theta = st
    lw = jax.tree.map(lambda a: a[li], blocks)
    c, T, D = x.shape
    mm = partial(_mm, precision=precision)
    h = _rms(x, lw["ln1"]["scale"], eps)
    a = lw["attn"]
    q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
    if "bq" in a:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    q = _rope(q.reshape(c, T, H, hd), theta)
    k = _rope(k.reshape(c, T, Hkv, hd), theta)
    v = v.reshape(c, T, Hkv, hd)
    kq, vq = codec(k), codec(v)
    rep = H // Hkv
    qg = q.reshape(c, T, Hkv, rep, hd) / np.sqrt(hd)
    s_raw = jnp.einsum("ctgrd,csgd->cgrts", qg, k, precision=HI)
    s_cod = jnp.einsum("ctgrd,csgd->cgrts", qg, kq, precision=HI)
    qm = qmask[:, None, None]                         # (c, 1, 1, T, S)
    causal = jnp.tril(jnp.ones((T, T), bool))[None, None, None]
    s = jnp.where(qm, s_cod, s_raw)
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = (jnp.einsum("cgrts,csgd->ctgrd", jnp.where(qm, 0.0, p), v,
                    precision=HI)
         + jnp.einsum("cgrts,csgd->ctgrd", jnp.where(qm, p, 0.0), vq,
                      precision=HI))
    x = x + mm(o.reshape(c, T, H * hd), a["wo"])
    h = _rms(x, lw["ln2"]["scale"], eps)
    m = lw["mlp"]
    return x + mm(jax.nn.silu(mm(h, m["wg"])) * mm(h, m["wu"]), m["wd"])


@partial(jax.jit, static_argnames=("eps", "tied", "precision"))
def _head(params, x, tokens, *, eps, tied, precision):
    """Per position: the largest logit, the logit of ``tokens`` and the
    argmax.  x: (c, T, D); tokens: (c, T)."""
    h = _rms(x, params["final_norm"]["scale"], eps)
    w = params["embed"].T if tied else params["head"]
    logits = _mm(h, w, precision)
    at = jnp.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return jnp.max(logits, -1), at, jnp.argmax(logits, -1).astype(jnp.int32)


def hidden(params, dm: dict, tokens, qmask, precision: str = "f32"):
    """Residual stream after the last layer: tokens (c, T) int32,
    qmask (c, T, T) bool -> (c, T, D) float32."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    st = (dm["H"], dm["Hkv"], dm["hd"], dm["eps"], dm["theta"])
    for li in range(dm["L"]):
        x = _layer(x, params["blocks"], jnp.int32(li), qmask, st=st,
                   precision=precision)
    return x


def head(params, dm: dict, x, tokens, precision: str = "f32"):
    return _head(params, x, tokens, eps=dm["eps"], tied=dm["tied"],
                 precision=precision)
