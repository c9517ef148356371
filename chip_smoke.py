#!/usr/bin/env python3
"""Bring-up check of the paged serving path on one TPU.

    python chip_smoke.py [--seed N]

Runs everything in this one process, on the chip JAX finds, and exits
nonzero without printing a result when JAX finds no TPU (there is no CPU
fallback).  Two phases, each of which raises on the first failed check:

  kernels  the fused spill/restore codec and paged decode attention at
           qwen2.5-3b widths against ``repro.kernels.ref``, the Adler-32
           checksums against ``zlib.adler32``, and proof that each jitted
           op lowered to a Mosaic ``tpu_custom_call`` (no interpret path);
  serve    qwen2.5-3b at its published widths (36 layers, d_model 2048,
           vocab 151936, bf16, weights from ``--seed``) through
           ``ServeEngine`` + ``PagedKVCache`` + ``KVPager`` on a 2-shard
           ``StripedVolume``: 8 requests of 128 prompt tokens and 32 new
           tokens, greedy, with a running request suspended every 6 ticks
           so pages spill to the volume and are prefetched and restored.
           One request's first decode step is compared with the dense
           reference ``model.prefill`` / ``model.decode_step``.

The last line of stdout is ``{"ok": true, "device": {...}}``.  The phase
functions take their sizes as arguments, so a CPU test runs them at smoke
widths (the kernels then run in interpret mode).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import zlib

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.jax_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_engine, init_params, serve  # noqa: E402
from repro.models import build_model  # noqa: E402

BF16_TOL = 2e-2          # tests/test_kernels.py's bf16 tolerance
LOGIT_ATOL = 0.25        # paged vs dense first-step logits, bf16 end to end


def _check(ok, what) -> None:
    """A failed check raises (``assert`` would vanish under ``-O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _timed(fn, *args, reps: int = 5):
    """(result, first-call seconds incl. compile, median steady seconds)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    steady = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        steady.append(time.perf_counter() - t0)
    return out, first, float(np.median(steady))


def _mosaic(fn, *args) -> bool:
    """True when the jitted op lowers to a Mosaic kernel call."""
    return "tpu_custom_call" in fn.lower(*args).as_text()


def kernel_phase(*, seed: int = 0, pool_pages: int = 256, page: int = 16,
                 row: int = 256, n_ids=(1, 36), batch: int = 4,
                 heads: int = 16, kv_heads: int = 2, head_dim: int = 128,
                 max_pages: int = 64, dtype=jnp.bfloat16) -> dict:
    """Codec and paged attention against the jnp oracles.  ``row`` is one
    page row of the codec's pool (Hkv * hd for a KV pool)."""
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((pool_pages, page, row)), dtype)
    out = {"codec": [], "mosaic": {}}
    for n in n_ids:
        ids = jnp.asarray(rng.permutation(pool_pages)[:n], jnp.int32)
        (q, sc, crc), c_gather, s_gather = _timed(
            ops.gather_quantize_crc, pool, ids)
        qr, sr = ref.gather_quantize_ref(pool, ids)
        lsb = int(np.abs(np.asarray(q, np.int32)
                         - np.asarray(qr, np.int32)).max())
        _check(lsb <= 1, f"int8 payload off by {lsb} LSB (n={n})")
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sr),
                                   rtol=1e-5, err_msg=f"scales (n={n})")
        qn = np.asarray(q)
        for i, c in enumerate(np.asarray(crc)):
            _check(int(c) == zlib.adler32(qn[i].tobytes()),
                   f"Adler-32 of page {i} (n={n}) != zlib.adler32")

        base = jnp.zeros_like(pool)
        (restored, rcrc), c_scatter, s_scatter = _timed(
            ops.scatter_dequantize_crc, base, ids, q, sc)
        _check(np.array_equal(np.asarray(rcrc), np.asarray(crc)),
               f"restore checksum != spill checksum (n={n})")
        got = np.asarray(restored, np.float32)
        orig = np.asarray(pool, np.float32)
        idn = np.asarray(ids)
        step = np.abs(orig[idn]).max(axis=-1, keepdims=True) / 127.0
        err = np.abs(got[idn] - orig[idn])
        _check((err <= step + 1e-6).all(),
               f"restored page off by {float((err / step).max()):.3f} steps")
        others = np.setdiff1d(np.arange(pool_pages), idn)
        _check(not got[others].any(),
               "restore touched pages it was not given")
        out["codec"].append({
            "n_ids": n, "max_lsb": lsb,
            "gather_first_s": c_gather, "gather_steady_s": s_gather,
            "scatter_first_s": c_scatter, "scatter_steady_s": s_scatter})
    out["mosaic"]["gather_quantize_crc"] = _mosaic(
        ops.gather_quantize_crc, pool, ids)
    out["mosaic"]["scatter_dequantize_crc"] = _mosaic(
        ops.scatter_dequantize_crc, base, ids, q, sc)

    qa = jnp.asarray(rng.standard_normal((batch, heads, head_dim)), dtype)
    kp = jnp.asarray(rng.standard_normal(
        (pool_pages, page, kv_heads, head_dim)), dtype)
    vp = jnp.asarray(rng.standard_normal(
        (pool_pages, page, kv_heads, head_dim)), dtype)
    table = jnp.asarray(rng.permutation(pool_pages)[:batch * max_pages]
                        .reshape(batch, max_pages), jnp.int32)
    lens = rng.integers(1, page * max_pages + 1, (batch,))
    lens[0] = page * max_pages                       # one full table
    lens = jnp.asarray(lens, jnp.int32)
    att, c_att, s_att = _timed(ops.paged_attention, qa, kp, vp, table, lens)
    exp = ref.paged_attention_ref(qa, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(att, np.float32),
                               np.asarray(exp, np.float32),
                               atol=BF16_TOL, rtol=BF16_TOL,
                               err_msg="paged attention vs ref")
    out["paged_attention"] = {
        "max_abs_err": float(np.abs(np.asarray(att, np.float32)
                                    - np.asarray(exp, np.float32)).max()),
        "first_s": c_att, "steady_s": s_att}
    out["mosaic"]["paged_attention"] = _mosaic(
        ops.paged_attention, qa, kp, vp, table, lens)
    return out


def _first_decode_logits(eng, model, params, prompt) -> dict:
    """One request's first decode step through the engine's paged path
    and through the dense reference, on a sequence released afterwards."""
    T = len(prompt)
    sid = eng.cache.new_sequence()
    first = int(np.argmax(np.asarray(
        eng.lm.prefill(np.asarray(prompt, np.int32), sid))))
    paged = np.asarray(eng.lm.decode_step(
        np.asarray([first]), [sid], np.asarray([T])))[0]
    eng.cache.release(sid)
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]},
        s_max=T + 8)
    dense_first = int(jnp.argmax(logits[0]))
    dense, _ = model.decode_step(params, cache,
                                 jnp.asarray([first], jnp.int32),
                                 jnp.asarray([T], jnp.int32))
    dense = np.asarray(dense[0], np.float32)
    return {"prefill_top1": first, "dense_prefill_top1": dense_first,
            "top1": int(np.argmax(paged)), "dense_top1": int(np.argmax(dense)),
            "max_abs_diff": float(np.abs(paged - dense).max()),
            "dense_max_abs": float(np.abs(dense).max())}


def serving_phase(cfg, *, seed: int = 0, n_requests: int = 8,
                  prompt_len: int = 128, max_new: int = 32,
                  max_batch: int = 4, pool_pages: int = 256,
                  page_size: int = 16, host_pages: int = 4,
                  suspend_every: int = 6,
                  logit_atol: float = LOGIT_ATOL) -> dict:
    """Serve ``n_requests`` greedy requests with KV spill through the
    launcher's engine and check the counters and the logits."""
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, seed))
    t_init = time.perf_counter() - t0
    eng = build_engine(cfg, params, n_requests=n_requests,
                       max_seq=prompt_len + max_new, max_batch=max_batch,
                       pool_pages=pool_pages, page_size=page_size,
                       spill_volume=True, host_pages=host_pages)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab, size=(prompt_len,)).tolist()
               for _ in range(n_requests)]

    t0 = time.perf_counter()
    logit = _first_decode_logits(eng, build_model(cfg), params, prompts[0])
    t_check = time.perf_counter() - t0
    _check(logit["prefill_top1"] == logit["dense_prefill_top1"], logit)
    _check(logit["top1"] == logit["dense_top1"], logit)
    _check(logit["max_abs_diff"] <= logit_atol, logit)

    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    t0 = time.perf_counter()
    try:
        done = serve(eng, suspend_every=suspend_every)
    finally:
        eng.cache.pager.vol.close()
    t_serve = time.perf_counter() - t0

    c = eng.metrics.count
    path = eng.metrics.kv_paging_path()
    res = {
        "t_init_s": t_init, "t_check_s": t_check, "t_serve_s": t_serve,
        "finished": len(done),
        "tokens": [len(r.out_tokens) for r in done],
        "decode_tokens": sum(len(r.out_tokens) - 1 for r in done),
        "suspends": c.get("suspends", 0), "resumes": c.get("resumes", 0),
        "pages_out": c.get("pages_out", 0), "pages_in": c.get("pages_in", 0),
        "kv_spills": path["kv_spills"], "kv_restores": path["kv_restores"],
        "kv_prefetch_issued": path["kv_prefetch_issued"],
        "kv_prefetch_hits": path["kv_prefetch_hits"],
        "kv_restore_crc_errors": path["kv_restore_crc_errors"],
        "transit_crc_errors": c.get("transit_crc_errors", 0),
        "hybrid_attention": c.get("hybrid_attention", 0),
        "bypass_pages": c.get("bypass_pages", 0),
        "logits": logit}
    _check(res["finished"] == n_requests, res)
    _check(all(n == max_new for n in res["tokens"]), res["tokens"])
    _check(res["kv_spills"] > 0 and res["kv_restores"] > 0, res)
    _check(res["kv_restore_crc_errors"] == 0, res)
    _check(res["transit_crc_errors"] == 0, res)
    _check(res["hybrid_attention"] == 0, res)
    return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's device is "
              f"{dev.platform!r}); this check runs on a TPU only",
              file=sys.stderr)
        return 2
    print(f"[cache] {enable_compile_cache()}")
    print(f"[device] {dev.platform} {dev.device_kind} "
          f"count={len(jax.devices())} jax={jax.__version__}")

    t0 = time.perf_counter()
    k = kernel_phase(seed=args.seed)
    print(f"[kernels] wall {time.perf_counter() - t0:.3f}s "
          f"mosaic={k['mosaic']}")
    for row in k["codec"]:
        print(f"[kernels] codec {json.dumps(row)}")
    print(f"[kernels] paged_attention {json.dumps(k['paged_attention'])}")
    _check(all(k["mosaic"].values()),
           f"a kernel did not lower to Mosaic: {k['mosaic']}")

    cfg = get_config("qwen2.5-3b")
    print(f"[serve] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={jnp.dtype(cfg.dtype).name}")
    s = serving_phase(cfg, seed=args.seed)
    print(f"[serve] init {s['t_init_s']:.3f}s | logit check (first "
          f"compiles) {s['t_check_s']:.3f}s | serve {s['t_serve_s']:.3f}s "
          f"({s['decode_tokens'] / s['t_serve_s']:.2f} decode tok/s)")
    print(f"[serve] logits {json.dumps(s['logits'])} atol={LOGIT_ATOL}")
    print(f"[serve] requests {s['finished']} tokens {s['tokens']}")
    print("[serve] counters " + json.dumps(
        {key: s[key] for key in (
            "suspends", "resumes", "pages_out", "pages_in", "kv_spills",
            "kv_restores", "kv_prefetch_issued", "kv_prefetch_hits",
            "kv_restore_crc_errors",
            "transit_crc_errors", "hybrid_attention", "bypass_pages")}))
    stats = dev.memory_stats() or {}
    print(f"[device] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
