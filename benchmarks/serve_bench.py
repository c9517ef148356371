"""Serving-tier benchmark: the paper's transit policies on the paged KV
cache (real engine, smoke model, CPU wall time — relative numbers).

Scenario: more concurrent requests than the HBM pool can hold.
  * transit (eager page-out of retired/preempted sequences + bypass):
    decode keeps running; finished sequences vacate pages immediately.
  * staging (no bypass): admission stalls on a full pool — the serving
    analogue of the paper's staging-cache stalls.  Both modes page out
    eagerly: the cache has no switch for it.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import build_model
from repro.serve import PagedCacheConfig, ServeEngine


def run(n_requests: int = 10, prompt_len: int = 24, max_new: int = 8,
        pool_pages: int = 8, page_size: int = 8) -> dict:
    cfg = get_config("qwen2.5-3b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    out = {}
    for mode in ("transit", "staging"):
        cache_cfg = PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd, page_size=page_size, n_pages=pool_pages,
            max_pages_per_seq=(prompt_len + max_new) // page_size + 2,
            conditional_bypass=(mode == "transit"))
        eng = ServeEngine(cfg, params, cache_cfg=cache_cfg, max_batch=3)
        rng = np.random.default_rng(0)
        for _ in range(n_requests):
            eng.submit(rng.integers(2, cfg.vocab, (prompt_len,)).tolist(),
                       max_new_tokens=max_new)
        t0 = time.perf_counter()
        try:
            done = eng.run(max_ticks=2000)
            err = ""
        except MemoryError as e:          # staging mode can exhaust the pool
            done = eng.finished
            err = str(e)
        dt = time.perf_counter() - t0
        toks = sum(len(r.out_tokens) for r in done)
        out[mode] = {
            "completed": len(done), "tokens": toks,
            "tok_per_s": round(toks / dt, 1),
            "pages_out": eng.metrics.count.get("pages_out", 0),
            "pages_in": eng.metrics.count.get("pages_in", 0),
            "bypass_pages": eng.metrics.count.get("bypass_pages", 0),
            "stall_error": err,
        }
        print(f"{mode:8s} completed={len(done)}/{n_requests} "
              f"tokens={toks} ({out[mode]['tok_per_s']} tok/s) "
              f"pages out/in={out[mode]['pages_out']}/"
              f"{out[mode]['pages_in']} bypass={out[mode]['bypass_pages']}"
              f"{' STALLED: ' + err if err else ''}")
    print("-> transit serving completes the backlog under pool pressure; "
          "staging admission stalls (the paper's contrast, serving-side)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    res = run()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
