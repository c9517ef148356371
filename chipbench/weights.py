"""Random weights for a dense decoder, drawn on the device from the seed.

One jitted program makes the whole tree in the dtype it is served in,
laid out as the serving engine reads it (layers stacked on a leading
axis).  The reference draws the same tree again from the same seed, so
neither side takes anything the other has made.

Norm weights are stored as ``scale`` with the weight being ``1 + scale``
(the engine's convention); biases exist only where the configuration has
them.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

NORM_STD = 0.1
BIAS_STD = 0.1


def dims(cfg: dict) -> dict:
    """The sizes the weights and the reference need, read from a
    configuration file's published keys."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": d, "H": h,
        "Hkv": cfg["num_key_value_heads"], "hd": cfg.get("head_dim", d // h),
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "qkv_bias": bool(cfg.get("qkv_bias", cfg.get("bias", False))),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are whole numbers >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _tree(key, dm: dict):
    L, D, H, Hkv, hd, F, V = (dm[k] for k in ("L", "D", "H", "Hkv", "hd",
                                               "F", "V"))
    dt = dm["dtype"]
    shapes = {
        "wq": (L, D, H * hd), "wk": (L, D, Hkv * hd), "wv": (L, D, Hkv * hd),
        "wo": (L, H * hd, D), "wg": (L, D, F), "wu": (L, D, F),
        "wd": (L, F, D)}
    keys = iter(jax.random.split(key, 16))

    def mat(shape):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dt)

    def small(shape, std, dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    attn = {k: mat(shapes[k]) for k in ("wq", "wk", "wv", "wo")}
    if dm["qkv_bias"]:
        for k, w in (("bq", H * hd), ("bk", Hkv * hd), ("bv", Hkv * hd)):
            attn[k] = small((L, w), BIAS_STD, dt)
    params = {
        "embed": (jax.random.normal(next(keys), (V, D), jnp.float32)
                  * D ** -0.5).astype(dt),
        "final_norm": {"scale": small((D,), NORM_STD, jnp.float32)},
        "blocks": {
            "ln1": {"scale": small((L, D), NORM_STD, jnp.float32)},
            "ln2": {"scale": small((L, D), NORM_STD, jnp.float32)},
            "attn": attn,
            "mlp": {k: mat(shapes[k]) for k in ("wg", "wu", "wd")},
        },
    }
    if not dm["tied"]:
        params["head"] = mat((D, V))
    return params


@lru_cache(maxsize=None)
def _init_fn(items: tuple):
    return jax.jit(partial(_tree, dm=dict(items)))


def init(cfg: dict, seed: int):
    """The whole weight tree for ``cfg``, made on the default device in
    one jitted call."""
    return _init_fn(tuple(sorted(dims(cfg).items())))(seed_key(seed))
