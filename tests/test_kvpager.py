"""Volume-backed KV paging (serve/kvpager.py + the kvcache spill tier)
and the PR-10 bugfix sweep of the cache's concurrency/capacity edges.

The three regression tests (concurrent deactivate, max_pages_per_seq,
drain_evictions timeout) fail on the pre-fix cache: unlocked table/free
-list mutation double-frees pool pages, an over-long sequence either
got an HBM page the dense table cannot index or died deep in table_for,
and an expired eviction barrier silently proceeded mid-mutation."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.metrics import KV_PAGING_COUNTERS, Metrics
from repro.serve import KVPager, PagedCacheConfig, PagedKVCache
from repro.volume.volume import make_volume


def _vol(n_lbas=1024):
    return make_volume(n_lbas=n_lbas, n_shards=2, aio_workers=2,
                       cache_bytes=1 << 22)


def _cfg(**kw):
    base = dict(n_layers=2, n_kv_heads=2, head_dim=8, page_size=4,
                n_pages=8, host_pages=64, max_pages_per_seq=8,
                read_tier_pages=8)
    base.update(kw)
    return PagedCacheConfig(**base)


def _fill(cache, sid, n_tokens, rng):
    L = cache.cfg.n_layers
    H, hd = cache.cfg.n_kv_heads, cache.cfg.head_dim
    for _ in range(n_tokens):
        k = jnp.asarray(rng.normal(size=(H, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(H, hd)), jnp.float32)
        cache.append_token(sid, [k] * L, [v] * L)


# ------------------------------------------------------------------ pager
def test_pager_roundtrip_dedup_and_slot_reuse():
    m = Metrics()
    pager = KVPager(_vol(), capacity_blocks=64, metrics=m)
    payload = bytes(range(256)) * 20               # 5120 B -> 2 blocks
    h1 = pager.spill(payload)
    h2 = pager.spill(payload)                      # content-hash dedup
    assert h1 == h2
    assert m.count["kv_dedup_hits"] == 1
    assert m.count["kv_spills"] == 1
    assert pager.fetch(h1) == payload
    other = pager.spill(b"different" * 600)
    assert other != h1
    free0 = pager.free_slots()
    pager.release(h1)
    assert pager.free_slots() == free0             # one ref still live
    pager.release(h1)
    assert pager.free_slots() == free0 + 1         # slot freed
    assert m.count["kv_spill_frees"] == 1
    # freed slots are reusable; handles are NOT recycled
    h3 = pager.spill(payload)
    assert h3 != h1
    assert pager.fetch(h3) == payload
    path = m.kv_paging_path()
    assert path["kv_restore_crc_errors"] == 0
    assert path["dedup_rate"] == pytest.approx(0.25)   # 3 spills, 1 dedup


def test_pager_wire_crc_detects_torn_record():
    m = Metrics()
    vol = _vol()
    pager = KVPager(vol, capacity_blocks=64, metrics=m)
    payload = b"kvpage" * 900                      # 2 blocks
    h = pager.spill(payload)
    rec = pager._records[h]
    for t in rec.spill_tickets:
        vol.wait(t)
    # tear the record's second block behind the pager's back
    vol.write(rec.lba + 1, np.frombuffer(b"\xff" * vol.block_size,
                                         np.uint8))
    with pytest.raises(IOError):
        pager.fetch(h)
    assert m.count["kv_restore_crc_errors"] == 1
    assert m.count["kv_restores"] == 0


def test_pager_prefetch_hit_and_wasted_counters():
    m = Metrics()
    pager = KVPager(_vol(), capacity_blocks=64, metrics=m)
    h1 = pager.spill(b"a" * 5000)
    h2 = pager.spill(b"b" * 5000)
    assert pager.prefetch([h1, h2]) == 2
    assert pager.prefetch([h1]) == 0               # already in flight
    assert pager.fetch(h1) == b"a" * 5000
    pager.release(h2)                              # unconsumed prefetch
    assert m.count["kv_prefetch_issued"] == 2
    assert m.count["kv_prefetch_hits"] == 1
    assert m.count["kv_prefetch_wasted"] == 1


def test_pager_capacity_exhaustion_is_loud():
    pager = KVPager(_vol(), capacity_blocks=2, metrics=Metrics())
    pager.spill(b"a" * 100)                        # 1 block -> 2 slots
    pager.spill(b"b" * 100)
    with pytest.raises(MemoryError, match="spill tier exhausted"):
        pager.spill(b"c" * 100)


# ------------------------------------------------- cache <-> volume tier
def test_spill_restore_preserves_kv_exactly():
    """The volume roundtrip must carry the int8 payload bit-exactly:
    attention after restore-through-the-volume == attention after a
    plain host-tier roundtrip of the SAME tokens."""
    rng_tokens = np.random.default_rng(3).normal(
        size=(12, 2, 2, 8)).astype(np.float32)

    def build(pager, host_pages):
        m = Metrics()
        c = PagedKVCache(_cfg(host_pages=host_pages), metrics=m,
                         pager=pager)
        sid = c.new_sequence()
        for t in range(12):
            k = jnp.asarray(rng_tokens[t, 0])
            v = jnp.asarray(rng_tokens[t, 1])
            c.append_token(sid, [k] * 2, [v] * 2)
        c.deactivate(sid)
        c.activate(sid)
        q = jnp.ones((1, 2, 8), jnp.float32)
        return c, m, np.asarray(c.attention(0, q, [sid], use_kernel=False))

    _c1, _m1, ref = build(None, host_pages=64)      # host-only roundtrip
    pager = KVPager(_vol(), capacity_blocks=256)
    c2, m2, got = build(pager, host_pages=0)        # everything spills
    assert m2.count["kv_spills"] > 0
    assert m2.count["kv_restores"] > 0
    assert m2.count["kv_restore_crc_errors"] == 0
    assert m2.count["transit_crc_errors"] == 0
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_hybrid_attention_reads_spilled_pages_without_promotion():
    """A cold sequence's attention must serve straight off the volume
    (the bypass discipline): no page-in, values matching the host-tier
    dequantization."""
    rng = np.random.default_rng(4)
    toks = rng.normal(size=(8, 2, 2, 8)).astype(np.float32)

    def build(pager, host_pages):
        m = Metrics()
        c = PagedKVCache(_cfg(host_pages=host_pages, n_pages=4),
                         metrics=m, pager=pager)
        sid = c.new_sequence()
        for t in range(8):
            c.append_token(sid, [jnp.asarray(toks[t, 0])] * 2,
                           [jnp.asarray(toks[t, 1])] * 2)
        c.deactivate(sid)
        return c, m, sid

    c1, _m1, s1 = build(None, host_pages=64)
    pager = KVPager(_vol(), capacity_blocks=256)
    c2, m2, s2 = build(pager, host_pages=0)
    assert any(e[0] == "vol" for e in c2.seqs[s2].table)
    q = jnp.ones((1, 2, 8), jnp.float32)
    ref = np.asarray(c1.attention(1, q, [s1], use_kernel=False))
    got = np.asarray(c2.attention(1, q, [s2], use_kernel=False))
    np.testing.assert_allclose(got, ref, atol=1e-6)
    assert m2.count["hybrid_attention"] == 1
    assert all(e[0] == "vol" for e in c2.seqs[s2].table)   # still cold
    assert m2.count["pages_in"] == 0


def test_prefetch_then_activate_hits():
    m = Metrics()
    pager = KVPager(_vol(), capacity_blocks=256, metrics=m)
    c = PagedKVCache(_cfg(host_pages=0, read_tier_pages=0), metrics=m,
                     pager=pager)
    rng = np.random.default_rng(5)
    sid = c.new_sequence()
    _fill(c, sid, 8, rng)
    c.deactivate(sid)
    n_vol = sum(1 for e in c.seqs[sid].table if e[0] == "vol")
    assert n_vol == 2
    assert c.prefetch(sid) == n_vol
    c.activate(sid)
    path = m.kv_paging_path()
    assert path["kv_prefetch_hits"] == n_vol
    assert path["prefetch_hit_rate"] == 1.0
    assert all(e[0] == "hbm" for e in c.seqs[sid].table)
    c.release(sid)
    assert pager.stats()["records"] == 0


# --------------------------------------------- satellite 1: lock discipline
def test_concurrent_deactivate_never_double_frees():
    """Racing sync deactivates of the same sequences: pre-fix, two
    threads both saw an "hbm" entry and both paged it out — the pool
    page entered the free list twice and the host tier leaked a packed
    copy.  All table/free-list mutations now serialize on _tlock."""
    m = Metrics()
    c = PagedKVCache(_cfg(n_pages=32, read_tier_pages=0), metrics=m)
    rng = np.random.default_rng(0)
    sids = []
    for _ in range(6):
        sid = c.new_sequence()
        _fill(c, sid, 8, rng)                      # 2 pages each
        sids.append(sid)
    barrier = threading.Barrier(4)

    def deactivate_all():
        barrier.wait()
        for sid in sids:
            c.deactivate(sid)

    threads = [threading.Thread(target=deactivate_all) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(c._free) == len(set(c._free)), "pool page double-freed"
    resident = sum(1 for s in c.seqs.values()
                   for e in s.table if e[0] == "hbm")
    assert len(c._free) + resident == c.cfg.n_pages
    # each of the 12 pages packed to the host tier exactly once
    # (one k-handle + one v-handle per layer)
    assert len(c.host) == 12 * 2 * c.cfg.n_layers
    assert m.count["pages_out"] == 12


# ------------------------------------------ satellite 2: max_pages_per_seq
def test_max_pages_per_seq_enforced_without_bypass():
    c = PagedKVCache(_cfg(max_pages_per_seq=2, conditional_bypass=False,
                          n_pages=16), metrics=Metrics())
    sid = c.new_sequence()
    _fill(c, sid, 8, np.random.default_rng(0))     # exactly at the bound
    with pytest.raises(MemoryError, match="max_pages_per_seq"):
        _fill(c, sid, 1, np.random.default_rng(1))


def test_long_sequence_bypasses_and_decodes_via_hybrid_path():
    m = Metrics()
    c = PagedKVCache(_cfg(max_pages_per_seq=2, n_pages=16), metrics=m)
    sid = c.new_sequence()
    _fill(c, sid, 11, np.random.default_rng(0))    # 3 pages: 1 past bound
    assert m.count["long_seq_bypass"] > 0
    assert len(c.seqs[sid].table) == 3
    assert c.seqs[sid].table[2][0] == "host-fresh"  # never an HBM page
    # the dense table refuses loudly instead of writing out of bounds
    with pytest.raises(ValueError, match="max_pages_per_seq"):
        c.table_for([sid])
    # attention routes to the hybrid slow path and still works
    q = jnp.ones((1, 2, 8), jnp.float32)
    out = np.asarray(c.attention(0, q, [sid], use_kernel=False))
    assert np.all(np.isfinite(out))
    assert m.count["hybrid_attention"] == 1


# ------------------------------------- satellite 3: drain_evictions expiry
def test_drain_evictions_timeout_is_loud():
    c = PagedKVCache(_cfg(), metrics=Metrics())
    with c._evict_cv:
        c._inflight_evictions += 1                 # a stuck page-out
    with pytest.raises(TimeoutError, match="still in flight"):
        c.drain_evictions(timeout=0.05)
    assert c.drain_evictions(timeout=0.05, raise_on_timeout=False) is False
    with c._evict_cv:
        c._inflight_evictions -= 1
        c._evict_cv.notify_all()
    assert c.drain_evictions(timeout=1.0) is True


# --------------------------------- satellite 4: crc + release accounting
@pytest.mark.parametrize("layer", [0, 1])
def test_page_in_crc_mismatch_returns_pool_page(layer):
    """A corrupted host payload must surface as IOError + a counter bump
    WITHOUT leaking the pool page allocated for the restore, and without
    popping any host handle (the sequence stays consistently cold).  The
    restore writes in place, layer by layer, but only into that page's
    rows: every other row of K and V, another sequence's included, keeps
    its bits, and the page is back on the free list."""
    m = Metrics()
    c = PagedKVCache(_cfg(read_tier_pages=0), metrics=m)
    sid = c.new_sequence()
    _fill(c, sid, 4, np.random.default_rng(0))
    other = c.new_sequence()
    _fill(c, other, 8, np.random.default_rng(1))    # stays resident
    c.deactivate(sid)
    assert c.seqs[sid].table[0][0] == "host"
    hk, _hv = c.seqs[sid].table[0][1][layer]
    q, s, crc = c.host.get(layer, hk)
    q = q.copy()
    q[0, 0] ^= 0x5A                                # tear one byte
    c.host.pages[(layer, hk)] = (q, s, crc)
    free_before = sorted(c._free)
    host_before = len(c.host)
    page = c._free[-1]                             # the restore's page
    P = c.cfg.n_pages
    others = [r for r in range(c.k_pool.shape[0]) if r % P != page]
    k_before = np.array(c.k_pool)[others]
    v_before = np.array(c.v_pool)[others]
    with pytest.raises(IOError, match="tore in transit"):
        c.activate(sid)
    assert m.count["transit_crc_errors"] == 1
    assert c.free_pages() == len(free_before), "restore leaked a pool page"
    assert sorted(c._free) == free_before and page in c._free
    assert len(c.host) == host_before, "partial page-in popped handles"
    assert c.seqs[sid].table[0][0] == "host"
    assert [e[0] for e in c.seqs[other].table] == ["hbm", "hbm"]
    assert np.array_equal(np.array(c.k_pool)[others], k_before)
    assert np.array_equal(np.array(c.v_pool)[others], v_before)


def test_host_fresh_page_in_is_one_write_of_every_layer():
    """A bypassed (host-fresh, raw f32) page comes back to the pool in one
    writer call for every layer: row li*P + page of each layer holds its
    values cast to the pool dtype, and no other row moves."""
    m = Metrics()
    c = PagedKVCache(_cfg(n_pages=3, read_tier_pages=0, dtype=jnp.bfloat16),
                     metrics=m)
    o = c.new_sequence()
    _fill(c, o, 4, np.random.default_rng(0))       # 1 page
    a = c.new_sequence()
    _fill(c, a, 12, np.random.default_rng(1))      # 2 pages, then bypass
    kind, buf = c.seqs[a].table[2]
    assert kind == "host-fresh" and m.count["kv.write_traces"] == 1
    c.release(o)                                   # frees one page
    page = c._free[-1]
    before = {kv: np.array(getattr(c, kv + "_pool")) for kv in "kv"}
    writes = m.count["kv_slot_writes"]
    c.activate(a)
    assert c.seqs[a].table[2] == ("hbm", page)
    assert m.count["pages_in"] == 1 and m.count["kv.write_traces"] == 2
    assert m.count["kv_slot_writes"] == writes
    P = c.cfg.n_pages
    for kv in "kv":
        want = before[kv].copy()
        for li in range(c.cfg.n_layers):
            want[li * P + page] = buf[kv][li].astype(want.dtype)
        got = np.array(getattr(c, kv + "_pool"))
        assert got.tobytes() == want.tobytes()


def test_release_accounts_mixed_hbm_host_fresh_pages():
    m = Metrics()
    c = PagedKVCache(_cfg(n_pages=4, host_pages=64), metrics=m)
    rng = np.random.default_rng(1)
    a = c.new_sequence()
    _fill(c, a, 8, rng)                            # 2 hbm pages
    b = c.new_sequence()
    _fill(c, b, 8, rng)                            # pool now full
    _fill(c, b, 4, rng)                            # bypass -> host-fresh
    c.deactivate(a)                                # a's pages -> host
    assert [e[0] for e in c.seqs[a].table] == ["host", "host"]
    assert c.free_pages() == 2                     # a's pool pages freed
    kinds_b = [e[0] for e in c.seqs[b].table]
    assert kinds_b == ["hbm", "hbm", "host-fresh"]
    c.release(b)                                   # hbm + host-fresh mix
    assert c.free_pages() == 4
    c.release(a)                                   # packed host pages
    assert c.free_pages() == 4
    assert len(c.host) == 0
    assert c.seqs == {}


# ------------------------------------------- page-out: one transfer a batch
def _fill_layers(cache, sid, n_tokens, rng):
    """Like ``_fill``, with different K/V in every layer."""
    L = cache.cfg.n_layers
    H, hd = cache.cfg.n_kv_heads, cache.cfg.head_dim
    for _ in range(n_tokens):
        k = rng.normal(size=(L, H, hd)).astype(np.float32)
        v = rng.normal(size=(L, H, hd)).astype(np.float32)
        cache.append_token(sid, list(k), list(v))


@pytest.mark.parametrize("n_pages", [1, 3])
def test_page_out_batch_is_bit_identical_to_the_reference(n_pages):
    """A batched page-out files in the host tier exactly what a codec
    call on one page of one layer gives: the same int8 rows, f32 scales
    and Adler-32 word, so ``_pack_page`` writes the same spill record and
    page-in restores the same rows; all of them equal the jnp reference,
    compiled as the kernel is (run eagerly, its divide by 127 can round
    the scale's last bit the other way, and with it a row's byte)."""
    import jax

    from repro.kernels import ref as kref
    from repro.kernels.ops import gather_quantize_crc

    gather_quantize_ref = jax.jit(kref.gather_quantize_ref)

    m = Metrics()
    c = PagedKVCache(_cfg(n_layers=3, read_tier_pages=0), metrics=m)
    P, pg = c.cfg.n_pages, c.cfg.page_size
    sid = c.new_sequence()
    _fill_layers(c, sid, n_pages * pg, np.random.default_rng(7))
    pools = {"k": c.k_pool, "v": c.v_pool}
    pages = [e[1] for e in c.seqs[sid].table]
    c.deactivate(sid)
    assert m.count["kv_page_out_transfers"] == 1
    want = {}
    for logical, (kind, handles) in enumerate(c.seqs[sid].table):
        assert kind == "host"
        record = []
        for li, hkv in enumerate(handles):
            ids = jnp.asarray([li * P + pages[logical]], jnp.int32)
            for kv, h in zip("kv", hkv):
                q, s, crc = c.host.get(li, h)
                qr, sr = (np.asarray(x) for x in
                          gather_quantize_ref(pools[kv], ids))
                q1, s1, c1 = (np.asarray(x)[0] for x in
                              gather_quantize_crc(pools[kv], ids))
                assert q.tobytes() == q1.tobytes() == qr[0].tobytes()
                assert s.dtype == np.float32
                assert s.tobytes() == s1.tobytes() == sr[0].tobytes()
                assert crc == int(c1) == int(kref.transit_crc_ref(qr)[0])
                assert crc == int(kref.transit_crc_ref(q[None])[0])
                want[kv, li, logical] = q1, s1, int(c1)
            (qk, sk, ck), (qv, sv, cv) = (want[kv, li, logical]
                                          for kv in "kv")
            record += [np.uint32(ck).tobytes(), np.uint32(cv).tobytes(),
                       qk.tobytes(), sk.astype("<f4").tobytes(),
                       qv.tobytes(), sv.astype("<f4").tobytes()]
        assert c._pack_page(handles) == b"".join(record)
    c.activate(sid)
    assert m.count["transit_crc_errors"] == 0
    for logical, (kind, page) in enumerate(c.seqs[sid].table):
        assert kind == "hbm"
        for kv in "kv":
            got = np.asarray(getattr(c, kv + "_pool"))
            for li in range(c.cfg.n_layers):
                q, s, _ = want[kv, li, logical]
                deq = (q.astype(np.float32) * s[:, None]).astype(got.dtype)
                assert got[li * P + page].tobytes() == deq.tobytes()


class _QueueOnly:
    """An eviction pool that only records what it is handed."""

    def __init__(self):
        self.items = []

    def register(self, cache):
        pass

    def submit(self, cache, item):
        self.items.append(item)


@pytest.mark.parametrize("n_pages", [1, 3])
def test_page_out_is_two_codec_calls_a_page_and_one_transfer(
        n_pages, monkeypatch):
    """A deactivate of k HBM pages makes 2k codec calls, each on the (L,)
    ids of one page in every layer, and one host transfer; an eviction
    pool's batch is one transfer too, and a page it holds twice goes out
    once."""
    from repro.serve import kvcache

    shapes = []
    transfers = []
    codec, device_get = kvcache.gather_quantize_crc, kvcache.jax.device_get

    def counting_codec(pool, ids):
        shapes.append((ids.shape, ids.dtype))
        return codec(pool, ids)

    def counting_get(x):
        transfers.append(len(x))
        return device_get(x)

    monkeypatch.setattr(kvcache, "gather_quantize_crc", counting_codec)
    monkeypatch.setattr(kvcache.jax, "device_get", counting_get)
    L, pg = 3, 4
    rng = np.random.default_rng(8)

    m = Metrics()
    c = PagedKVCache(_cfg(n_layers=L, read_tier_pages=0), metrics=m)
    sid = c.new_sequence()
    _fill_layers(c, sid, n_pages * pg, rng)
    c.deactivate(sid)
    assert len(shapes) == 2 * n_pages
    assert set(shapes) == {((L,), np.dtype(np.int32))}
    assert transfers == [n_pages]
    assert m.count["kv_page_out_transfers"] == 1
    assert m.count["pages_out"] == n_pages == m.count["kv.page_out"] * n_pages

    pool = _QueueOnly()
    m2 = Metrics()
    c2 = PagedKVCache(_cfg(n_layers=L, read_tier_pages=0), metrics=m2,
                      evict_pool=pool)
    sids = [c2.new_sequence() for _ in range(2)]
    for s in sids:
        _fill_layers(c2, s, n_pages * pg, rng)
        c2.deactivate(s)
    c2.deactivate(sids[0])                         # queued twice
    assert len(pool.items) == 3 * n_pages
    shapes.clear()
    transfers.clear()
    c2._evict_slots(pool.items)
    for _ in pool.items:
        c2._complete_eviction()
    assert len(shapes) == 2 * 2 * n_pages
    assert transfers == [2 * n_pages]
    assert m2.count["kv_page_out_transfers"] == 1
    assert m2.count["pages_out"] == 2 * n_pages
    assert m2.count["evict_skipped"] == n_pages
    assert len(c2._free) == len(set(c2._free)) == c2.cfg.n_pages
    assert all(e[0] == "host" for s in sids for e in c2.seqs[s].table)


# ------------------------------------------------------- engine + metrics
def test_kv_paging_path_metrics_shape():
    m = Metrics()
    path = m.kv_paging_path()
    for key in KV_PAGING_COUNTERS:
        assert path[key] == 0
    assert path["dedup_rate"] == 0.0
    assert path["prefetch_hit_rate"] == 0.0
    m.bump("kv_spills", 3)
    m.bump("kv_dedup_hits", 1)
    m.bump("kv_restores", 2)
    m.bump("kv_prefetch_hits", 1)
    path = m.kv_paging_path()
    assert path["dedup_rate"] == pytest.approx(0.25)
    assert path["prefetch_hit_rate"] == pytest.approx(0.5)


def test_engine_suspend_resume_through_the_pager():
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = get_config("qwen2.5-3b", smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    vol = _vol(n_lbas=4096)
    pager = KVPager(vol, capacity_blocks=2048)
    cache_cfg = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        page_size=4, n_pages=16, host_pages=0, max_pages_per_seq=16)
    eng = ServeEngine(cfg, params, cache_cfg=cache_cfg, max_batch=2,
                      pager=pager, prefetch_depth=2)
    r1 = eng.submit(list(range(2, 14)), max_new_tokens=6)
    r2 = eng.submit(list(range(3, 15)), max_new_tokens=6)
    eng.step()                                     # both admitted
    eng.suspend(eng.running[0])                    # preempt: spill to vol
    assert eng.metrics.count["kv_spills"] > 0
    assert eng.suspended
    eng.run(max_ticks=200)                         # resumes + finishes
    assert r1.done and r2.done
    assert len(r1.out_tokens) == 6 and len(r2.out_tokens) == 6
    assert eng.metrics.count["resumes"] >= 1
    assert eng.metrics.count["kv_restores"] > 0
    assert eng.metrics.count["kv_restore_crc_errors"] == 0
    assert eng.metrics.count["transit_crc_errors"] == 0


# ------------------------------------------------------------------- sim
def test_kv_paging_sim_sweep_invariants():
    from repro.core.sim import run_kv_paging_sim_workload as run

    common = dict(hbm_pages=16, host_pages=16, pages_per_session=4,
                  page_blocks=8, shared_pages=1, rounds=3, decode_us=20.0)
    base = run(n_sessions=4, **common)
    assert base["spills"] == 0 and base["restores_vol"] == 0
    x4 = run(n_sessions=32, **common)              # 4x HBM+host capacity
    x4_sync = run(n_sessions=32, prefetch_depth=0, **common)
    assert x4["tokens_s"] / base["tokens_s"] >= 0.5       # CI floor
    assert x4["tokens_s"] >= x4_sync["tokens_s"]          # prefetch wins
    assert x4["dedup_hits"] > 0                           # shared prefix
    assert x4["prefetch_hits"] > 0 and x4_sync["prefetch_hits"] == 0
    assert x4["restores_vol"] <= x4["spills"] + x4["dedup_hits"]
    assert x4 == run(n_sessions=32, **common)             # deterministic


# ---------------------------------------------------------- record sizing
@pytest.mark.parametrize("n_layers,page_size,head_dim", [
    (2, 4, 8),
    (8, 16, 128),        # a 17-block record: longer than a 16-deep window
])
def test_page_record_bytes_sizes_the_spill_tier(n_layers, page_size,
                                                head_dim):
    """``page_record_bytes`` is exactly what ``_pack_page`` writes, the
    launcher's pager holds that many records of it at once, and its
    in-flight window takes every record's prefetch chain."""
    from repro.launch.serve import make_spill_pager
    cfg = _cfg(n_layers=n_layers, page_size=page_size, head_dim=head_dim)
    n_records = 3
    pager = make_spill_pager(cfg, n_records)
    c = PagedKVCache(cfg, pager=pager)
    sid = c.new_sequence()
    _fill(c, sid, page_size * n_records, np.random.default_rng(0))
    c.deactivate(sid)                      # every page packed to the host
    payloads = [c._pack_page(e[1]) for e in c.seqs[sid].table]
    assert {len(p) for p in payloads} == {cfg.page_record_bytes}
    handles = [pager.spill(p) for p in payloads]
    assert pager.free_slots() == 0         # sized for exactly n_records
    assert pager.prefetch(handles) == len(handles)
    assert [pager.fetch(h) for h in handles] == payloads
    assert pager.metrics.count["kv_prefetch_hits"] == len(handles)
    pager.vol.close()


def test_engine_spans_count_where_the_work_happens():
    """Every serving span is entered on the engine's shared Metrics (the
    volume ops on the volume's own): one ``kv.append`` and one
    ``kv.attention`` per layer per decode step, one ``kv.page_out`` per
    host transfer of a page-out batch, one ``kv.page_in`` per page moved
    in, one ``pager.fetch`` per volume restore."""
    import jax

    from repro.configs import get_config
    from repro.core.metrics import SERVE_SPANS
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = get_config("qwen2.5-3b", smoke=True)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    vol = _vol(n_lbas=4096)
    cache_cfg = PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        page_size=4, n_pages=16, host_pages=0, max_pages_per_seq=16)
    eng = ServeEngine(cfg, params, cache_cfg=cache_cfg, max_batch=2,
                      pager=KVPager(vol, capacity_blocks=2048))
    eng.submit(list(range(2, 14)), max_new_tokens=6)
    eng.submit(list(range(3, 15)), max_new_tokens=6)
    eng.step()
    eng.suspend(eng.running[0])
    eng.run(max_ticks=200)
    c = eng.metrics.count
    assert c["lm.decode"] >= 5 and c["lm.prefill"] == 2
    assert c["kv.append"] == c["kv.attention"] == cfg.n_layers * c["lm.decode"]
    assert c["serve.suspend"] == 1 and c["serve.step"] >= c["lm.decode"]
    assert c["kv.page_out"] == c["kv_page_out_transfers"] > 0
    assert c["pages_out"] >= c["kv.page_out"]
    assert c["kv.page_in"] == c["pages_in"] > 0
    assert c["pager.fetch"] == c["kv_restores"] > 0
    assert c["kv.activate"] == c["resumes"] and c["kv.spill"] > 0
    vc = vol.metrics.count
    assert vc["vol.read"] > 0 and vc["vol.write"] + vc["vol.write_multi"] > 0
    on_engine = {n for n in SERVE_SPANS if not n.startswith("vol.")}
    assert all(eng.metrics.ns[n] > 0 for n in on_engine)
    assert not {"fused_kernel_passes", "cache_eviction_and_write"} & set(c)
