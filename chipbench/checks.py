"""Decide ``correct``: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, the
reference runs once over each sampled request's prompt and served tokens
(teacher forcing) and reads, at every served position, how far the
served token's logit lies below the reference's best logit there.  The
widest such gap is the number compared.  A token served from the
prefill, from decode over HBM pages, and from decode after the
session's pages came back from the host tier or the volume all count.

The control (``control=True``) runs the same reference in float8 and
reads the gap of the token that the float8 forward puts first.
"""
from __future__ import annotations

import numpy as np

import spec
import weights

MAX_TOKENS = 4096          # served tokens compared per run, at most


def sample(records: list[dict], seed: int,
           max_tokens: int = MAX_TOKENS) -> list[dict]:
    """Every request when their served tokens fit in ``max_tokens``;
    else the longest and then others drawn from the seed until full."""
    if sum(len(r["tokens"]) for r in records) <= max_tokens:
        return records
    longest = max(records, key=lambda r: len(r["tokens"]))
    rest = [r for r in records if r is not longest]
    order = np.random.default_rng([int(seed), 2]).permutation(len(rest))
    out, n = [longest], len(longest["tokens"])
    for i in order:
        if n + len(rest[i]["tokens"]) > max_tokens:
            continue
        out.append(rest[i])
        n += len(rest[i]["tokens"])
    return out


def codec_mask(T: int, suspends: list[int]) -> np.ndarray:
    """(T, T) bool: query ``p`` sees key ``j`` through the int8 codec
    when a suspension at cache length ``n`` has ``j < n <= p``."""
    last = np.zeros(T, np.int64)             # latest suspension <= p
    for n in sorted(suspends):
        last[n:] = n
    return np.arange(T)[None, :] < last[:, None]


def _bucket(n: int) -> int:
    return max(128, 1 << (n - 1).bit_length())


def gaps(cfg: dict, seed: int, records: list[dict],
         control: bool = False) -> list[dict]:
    """Per record: ``gap`` at each served position, and with ``control``
    also ``control_gap``.  Draws the weights again from the seed."""
    import jax
    import jax.numpy as jnp

    ref = spec.reference(cfg["family"])
    dm = weights.dims(cfg)
    params = weights.init(cfg, seed)
    T = _bucket(max(len(r["prompt"]) + len(r["tokens"]) for r in records))
    c = max(1, min(len(records), (1 << 23) // (T * T)))
    out = []
    for i in range(0, len(records), c):
        chunk = records[i:i + c]
        toks = np.zeros((c, T), np.int32)
        nxt = np.zeros((c, T), np.int32)
        mask = np.zeros((c, T, T), bool)
        for j, r in enumerate(chunk):
            seq = r["prompt"] + r["tokens"]
            toks[j, :len(seq)] = seq
            nxt[j, :len(seq) - 1] = seq[1:]
            mask[j] = codec_mask(T, r["suspends"])
        h = ref.hidden(params, dm, jnp.asarray(toks), jnp.asarray(mask))
        mx, at, _ = ref.head(params, dm, h, jnp.asarray(nxt))
        gap = np.asarray(mx - at)
        if control:
            hc = ref.hidden(params, dm, jnp.asarray(toks), jnp.asarray(mask),
                            precision="fp8")
            _, _, first = ref.head(params, dm, hc, jnp.asarray(nxt),
                                   precision="fp8")
            _, at_c, _ = ref.head(params, dm, h, first)
            cgap = np.asarray(mx - at_c)
            del hc
        del h
        for j, r in enumerate(chunk):
            n_p, n_o = len(r["prompt"]), len(r["tokens"])
            pos = np.arange(n_p - 1, n_p - 1 + n_o)
            row = {"req_id": r["req_id"], "positions": pos,
                   "gap": gap[j, pos],
                   "after_round_trip": pos >= (min(r["suspends"])
                                               if r["suspends"] else 1 << 30)}
            if control:
                row["control_gap"] = cgap[j, pos]
            out.append(row)
    jax.tree.map(lambda a: a.delete(), params)
    return out


def summary(rows: list[dict], key: str = "gap") -> dict:
    """Widest gap overall and by where the token came from."""
    def widest(sel):
        vals = [float(np.max(r[key][m])) for r in rows
                for m in [sel(r)] if m.any()]
        return max(vals) if vals else None

    return {
        "max": widest(lambda r: np.ones(len(r[key]), bool)),
        "prefill": widest(lambda r: np.arange(len(r[key])) == 0),
        "resident": widest(lambda r: (np.arange(len(r[key])) > 0)
                           & ~r["after_round_trip"]),
        "after_round_trip": widest(lambda r: r["after_round_trip"]),
        "tokens": int(sum(len(r[key]) for r in rows)),
    }
