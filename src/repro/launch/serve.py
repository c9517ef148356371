"""Serving launcher: batched requests against the paged-KV engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
        --requests 16 --max-new 24                 # smoke widths
    PYTHONPATH=src python -m repro.launch.serve --full --spill-volume

Demonstrates continuous batching, the BTT-style block table, eager
page-out of finished sequences, and conditional bypass under pool pressure
(shrink --pool-pages to force it).  ``--full`` serves the published
widths with random weights (no checkpoint is loaded).

Decode attention follows the platform: on a TPU it is the paged-attention
Pallas kernel lowered to Mosaic; on the CPU it is the jnp reference.

With ``--spill-volume`` the engine gets a volume-backed KV spill tier
(serve.kvpager.KVPager on a striped async volume): requests are
periodically suspended mid-decode, their packed pages descend past
``--host-pages`` onto the volume as content-deduplicated atomic records,
and decode-ahead prefetch restores them before resume.  The volume and
the pager region are sized from the page record's bytes, so every
request's pages fit on the volume at once.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.kernels.ops import on_tpu
from repro.launch.jax_cache import enable_compile_cache
from repro.models import build_model
from repro.serve import KVPager, PagedCacheConfig, ServeEngine
from repro.serve.kvpager import record_blocks
from repro.volume.volume import make_volume


def init_params(cfg, seed: int):
    """Random weights at the config's widths, made on the device in one
    jitted program (an eager init builds f32 temporaries per matrix)."""
    return jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))


def make_spill_pager(cache_cfg: PagedCacheConfig,
                     n_records: int) -> KVPager:
    """A KVPager on a 2-shard striped async volume that holds
    ``n_records`` page records of this cache's geometry.  The in-flight
    window holds one sequence's records, so a suspended request's whole
    decode-ahead prefetch (one linked read chain per record) can be in
    flight at once; a window shorter than one record's chain would make
    every prefetch back off."""
    block = 4096
    rec = record_blocks(cache_cfg.page_record_bytes, block)
    cap = n_records * rec
    vol = make_volume(n_lbas=cap, n_shards=2, aio_workers=2,
                      block_size=block, cache_bytes=1 << 22,
                      max_inflight=max(16, cache_cfg.max_pages_per_seq
                                       * rec))
    return KVPager(vol, capacity_blocks=cap)


def build_engine(cfg, params, *, n_requests: int, max_seq: int,
                 max_batch: int, pool_pages: int, page_size: int,
                 spill_volume: bool, host_pages: int) -> ServeEngine:
    """The engine the launcher serves with; ``max_seq`` is the longest
    prompt + generation, which bounds each block table."""
    pages_per_seq = -(-max_seq // page_size)
    cache_cfg = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        page_size=page_size, n_pages=pool_pages,
        host_pages=host_pages if spill_volume else 1 << 30,
        max_pages_per_seq=max(4, pages_per_seq + 2))
    pager = (make_spill_pager(cache_cfg, n_requests * pages_per_seq)
             if spill_volume else None)
    return ServeEngine(cfg, params, cache_cfg=cache_cfg,
                       max_batch=max_batch, pager=pager)


def serve(eng: ServeEngine, *, suspend_every: int = 0) -> list:
    """Run every submitted request to completion.  With ``suspend_every``
    the scheduler is driven by hand so a running request is preempted
    every that many ticks: its pages transit host -> volume, and the
    decode-ahead prefetch restores them before ``_admit`` resumes it."""
    if suspend_every <= 0:
        return eng.run()
    ticks = 0
    while eng.queue or eng.running or eng.suspended:
        eng.step()
        ticks += 1
        if eng.running and ticks % suspend_every == 0:
            eng.suspend(eng.running[0])
    return eng.finished


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--pool-pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--spill-volume", action="store_true",
                    help="attach a volume-backed KV spill tier and "
                         "suspend/resume requests through it")
    ap.add_argument("--host-pages", type=int, default=4,
                    help="host-tier budget before pages spill to the "
                         "volume (with --spill-volume)")
    ap.add_argument("--suspend-every", type=int, default=6,
                    help="scheduler ticks between preemptions "
                         "(with --spill-volume)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family != "dense":
        raise SystemExit("the paged engine serves the dense family; pick a "
                         "dense arch (qwen2.5-3b, phi3-mini-3.8b, ...)")
    params = init_params(cfg, seed=0)
    eng = build_engine(cfg, params, n_requests=args.requests,
                       max_seq=args.prompt_len + args.max_new,
                       max_batch=args.max_batch, pool_pages=args.pool_pages,
                       page_size=args.page_size,
                       spill_volume=args.spill_volume,
                       host_pages=args.host_pages)

    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        prompt = rng.integers(2, cfg.vocab, size=(args.prompt_len,)).tolist()
        eng.submit(prompt, max_new_tokens=args.max_new,
                   temperature=args.temperature)

    t0 = time.perf_counter()
    try:
        done = serve(eng, suspend_every=args.suspend_every
                     if args.spill_volume else 0)
    finally:
        if eng.cache.pager is not None:
            eng.cache.pager.vol.close()
    dt = time.perf_counter() - t0
    toks = sum(len(r.out_tokens) for r in done)
    lat = [r.t_done - r.t_submit for r in done]
    dev = jax.devices()[0]
    attention = "paged kernel" if on_tpu() else "jnp reference"
    print(f"[serve] {cfg.name} on {dev.platform}/{dev.device_kind}, "
          f"decode attention: {attention}: "
          f"{len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s) "
          f"| mean latency {np.mean(lat)*1e3:.0f}ms "
          f"| pool occupancy now {eng.cache.occupancy():.2f} "
          f"| pages out/in {eng.metrics.count.get('pages_out', 0)}/"
          f"{eng.metrics.count.get('pages_in', 0)} "
          f"| bypass pages {eng.metrics.count.get('bypass_pages', 0)} "
          f"| hybrid attention steps "
          f"{eng.metrics.count.get('hybrid_attention', 0)}")
    if args.spill_volume:
        path = eng.metrics.kv_paging_path()
        print(f"[spill] suspends {eng.metrics.count.get('suspends', 0)} "
              f"resumes {eng.metrics.count.get('resumes', 0)} "
              f"| spills {path['kv_spills']} "
              f"(dedup rate {path['dedup_rate']:.2f}) "
              f"| restores {path['kv_restores']} "
              f"(prefetch hit rate {path['prefetch_hit_rate']:.2f}) "
              f"| crc errors {path['kv_restore_crc_errors']}")


if __name__ == "__main__":
    main()
