"""Paged KV cache — BTT + Caiti re-expressed for the HBM/host tier pair.

Mapping of the paper's structures:

  BTT map (lba -> pba)        -> per-sequence block table (logical page ->
                                 physical page in the HBM pool)
  BTT lanes / free blocks     -> the pool's free list (CAS-style pops)
  DRAM transit cache          -> the HBM pool itself is the *fast* tier;
                                 the host tier (int8-packed) is the slow one
  eager eviction              -> cold sequences' pages are packed
                                 (gather_quantize) to the host tier as soon
                                 as the sequence stops decoding
  conditional bypass          -> a page allocation against a full pool goes
                                 straight to the host tier (no stall evicting
                                 someone else's hot page on the decode path)
  fsync / PREFLUSH            -> ``barrier()``: complete all pending
                                 migrations (used before pool reshape)
  volume read tier            -> a small CLOCK cache of *dequantized* host
                                 pages (``repro.volume.ReadTier`` in object
                                 mode): hybrid attention re-reads the same
                                 cold pages every decode step, so the
                                 int8->f32 unpack is paid once per residency
                                 instead of once per step.  Clean data only
                                 (host pages are immutable while live), so
                                 invalidation is just page-in/release.
  durable tier                -> an optional :class:`~repro.serve.kvpager
                                 .KVPager` spills the host tier's overflow
                                 onto the async striped volume (chained
                                 write_multi records, content-hash dedup,
                                 decode-ahead linked-read prefetch) so
                                 session KV is bounded by the volume, not
                                 DRAM — the tier walk is HBM -> host
                                 (int8) -> volume, exactly the paper's
                                 transit-cache -> PMem descent.

The pool arrays live per layer: (P, page_size, Hkv, hd).  On a TPU the
decode attention resolves the table inside the Pallas kernel (Mosaic); on
the CPU the jnp ref, or the same kernel in interpret mode, does the same
resolution.

The write path is one jitted scatter that donates the pools: a decode
step reserves each sequence's slot once (``reserve_slots``), then writes
one layer's K/V for all of them per call (``write_slots``); a prefill
token writes every layer's in one call (``append_token``).

Concurrency contract: ``seq.table``, ``self._free``, the host tier and the
active flags are guarded by ``_tlock`` — public entry points take it,
``_locked`` helpers assume it (the eviction-pool workers' ``_evict_slot*``
hooks take the same lock, so a decode-thread ``append_token`` can never
interleave with a worker's page-out on the same free list).  A pool
array is read and replaced only under ``_tlock`` or on the decode thread
that owns the writes: a write donates the array it replaces, so nothing
may hold a pool array across a write.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import Metrics
from repro.kernels import ref as kref
from repro.kernels.ops import (gather_quantize_crc, on_tpu,
                               paged_attention, scatter_dequantize_crc)
from repro.volume.read_tier import ReadTier


@dataclass
class PagedCacheConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    page_size: int = 16
    n_pages: int = 256            # HBM pool pages (per layer)
    host_pages: int = 1024        # host-tier page budget (spill target
                                  # when a KVPager is attached)
    max_pages_per_seq: int = 64
    dtype: object = jnp.bfloat16
    eager_eviction: bool = True
    conditional_bypass: bool = True
    read_tier_pages: int = 128    # dequantized-page cache (0 disables)

    @property
    def page_record_bytes(self) -> int:
        """Bytes of one packed page as ``PagedKVCache._pack_page`` writes
        it: per layer two crcs, then K and V as int8 rows + f32 scales."""
        pg, D = self.page_size, self.n_kv_heads * self.head_dim
        return self.n_layers * (8 + 2 * (pg * D + pg * 4))


class HostTier:
    """The slow tier: int8-packed pages + scales + the wire checksum the
    fused transit kernel computed at spill time, keyed (layer, handle)."""

    def __init__(self) -> None:
        self.pages: dict[tuple[int, int],
                         tuple[np.ndarray, np.ndarray, int]] = {}
        self._next = 0

    def put(self, layer: int, q: np.ndarray, scale: np.ndarray,
            crc: int = 0) -> int:
        h = self._next
        self._next += 1
        self.pages[(layer, h)] = (q, scale, crc)
        return h

    def get(self, layer: int, handle: int):
        return self.pages[(layer, handle)]

    def pop(self, layer: int, handle: int):
        return self.pages.pop((layer, handle))

    def __len__(self) -> int:
        return len(self.pages)


@dataclass
class Sequence:
    seq_id: int
    length: int = 0
    # logical page -> ("hbm", phys_page) | ("host", [(k_handle, v_handle)
    # per layer]) | ("host-fresh", {"k","v" raw f32}) | ("vol", pager handle)
    table: list = field(default_factory=list)
    active: bool = True


@dataclass
class Slots:
    """One token's reserved slot in each of N sequences.  ``pages`` and
    ``offs`` are (N,) int32 indices into the pools; a host-resident slot
    carries the out-of-range page ``n_pages``, which the scatter drops,
    and is listed in ``host`` as (row, host-fresh buffer, offset)."""
    pages: object
    offs: object
    host: list


def _scatter_slots(bump, k_pools, v_pools, pages, offs, k, v):
    """Write N slots into each pool of ``k_pools``/``v_pools``: k/v hold
    (n_pools, N, Hkv, hd) rows in any shape of that size, cast to the
    pool dtype (the convert an eager ``.astype`` makes).  The body runs
    only while JAX traces, and bumps ``kv.write_traces`` then."""
    bump("kv.write_traces")
    shape = (len(k_pools), pages.shape[0]) + k_pools[0].shape[2:]
    k, v = k.reshape(shape), v.reshape(shape)

    def put(pools, rows):
        return tuple(p.at[pages, offs].set(r.astype(p.dtype), mode="drop")
                     for p, r in zip(pools, rows))
    return put(k_pools, k), put(v_pools, v)


# the pools are donated: XLA updates them in place, and the arrays passed
# in are dead once the call returns
_write_slots = jax.jit(_scatter_slots, static_argnums=0,
                       donate_argnums=(1, 2))


class PagedKVCache:
    """Host-side manager + on-device pools for one model's KV state."""

    def __init__(self, cfg: PagedCacheConfig,
                 metrics: Metrics | None = None,
                 evict_pool=None, pager=None) -> None:
        self.cfg = cfg
        self.metrics = metrics or Metrics()
        # optional SharedEvictionPool: eager page-out DMA runs on the
        # volume's eviction cores instead of the decode thread (the
        # paper's per-device eviction threads, shared).  Workers gather
        # from the pools under _tlock, which every pool write holds (a
        # write donates the array it replaces); table / free-list /
        # host-tier mutations serialize on it too.
        self._tlock = threading.Lock()
        self._evict_cv = threading.Condition(self._tlock)
        self._evict_pool = evict_pool
        self._inflight_evictions = 0
        if evict_pool is not None:
            evict_pool.register(self)
        # optional volume-backed spill tier: host pages past
        # ``cfg.host_pages`` descend to KVPager records (see kvpager.py)
        self.pager = pager
        if pager is not None and getattr(pager, "own_metrics", False):
            pager.metrics = self.metrics     # unify the kv_* counters
            pager.own_metrics = False
        L, P, pg, H, hd = (cfg.n_layers, cfg.n_pages, cfg.page_size,
                          cfg.n_kv_heads, cfg.head_dim)
        self.k_pool = [jnp.zeros((P, pg, H, hd), cfg.dtype) for _ in range(L)]
        self.v_pool = [jnp.zeros((P, pg, H, hd), cfg.dtype) for _ in range(L)]
        self._free: list[int] = list(range(P))          # global free set
        self.host = HostTier()
        # clean read tier over the host tier: caches dequantized pages for
        # the hybrid-attention slow path (object mode — slots hold arrays)
        self.read_tier = (ReadTier(block_size=None,
                                   n_slots=cfg.read_tier_pages,
                                   metrics=self.metrics)
                          if cfg.read_tier_pages > 0 else None)
        self.seqs: dict[int, Sequence] = {}
        self._next_seq = 0

    # ------------------------------------------------------------ allocation
    def free_pages(self) -> int:
        return len(self._free)

    def new_sequence(self) -> int:
        with self._tlock:
            sid = self._next_seq
            self._next_seq += 1
            self.seqs[sid] = Sequence(sid)
            return sid

    def _alloc_page(self) -> int | None:
        if self._free:
            return self._free.pop()                      # CAS-style pop
        return None

    def _evict_coldest_locked(self) -> bool:
        """Sync eviction (the staging fallback): pack the coldest inactive
        sequence's first HBM page to the host tier."""
        for seq in self.seqs.values():
            if seq.active:
                continue
            for li, entry in enumerate(seq.table):
                if entry[0] == "hbm":
                    self._page_out_locked(seq, li)
                    return True
        return False

    # -------------------------------------------------------------- write path
    def _reserve_locked(self, sids: list[int]) -> Slots:
        """Reserve the next token slot of each sequence in ``sids``,
        allocating a fresh page at a page boundary (or bypassing it to
        the host tier), and count the token in ``seq.length``."""
        pg = self.cfg.page_size
        pages = np.full((len(sids),), self.cfg.n_pages, np.int32)
        offs = np.zeros((len(sids),), np.int32)
        host = []
        for row, sid in enumerate(sids):
            seq = self.seqs[sid]
            off = seq.length % pg
            if off == 0:                                 # need a fresh page
                # max_pages_per_seq bounds the DENSE block table the fast
                # attention path builds — a longer sequence never gets an
                # HBM page (it would index past table_for's array)
                over = len(seq.table) >= self.cfg.max_pages_per_seq
                page = None if over else self._alloc_page()
                if page is None:
                    if over and not self.cfg.conditional_bypass:
                        raise MemoryError(
                            f"seq {sid} would grow to {len(seq.table) + 1} "
                            f"pages, past max_pages_per_seq="
                            f"{self.cfg.max_pages_per_seq}; raise the bound "
                            f"or enable conditional_bypass to let long "
                            f"sequences overflow to the host tier")
                    if self.cfg.conditional_bypass:
                        # pool full (or table full) -> host tier
                        self.metrics.bump("bypass_pages")
                        if over:
                            self.metrics.bump("long_seq_bypass")
                        seq.table.append(("host-fresh",
                                          self._host_fresh_page()))
                        self._maybe_spill_locked()
                    else:
                        if not self._evict_coldest_locked():
                            raise MemoryError("KV pool exhausted")
                        self._maybe_spill_locked()
                        page = self._alloc_page()
                        seq.table.append(("hbm", page))
                else:
                    seq.table.append(("hbm", page))
            entry = seq.table[seq.length // pg]
            offs[row] = off
            if entry[0] == "hbm":
                pages[row] = entry[1]
            else:                                        # host-resident page
                host.append((row, entry[1], off))
            seq.length += 1
        return Slots(pages, offs, host)

    def _write_locked(self, slots: Slots, layers: range, k, v) -> None:
        """Write one token's K/V into ``slots`` for the pools of
        ``layers``: the HBM rows in one in-place scatter, the host rows
        through numpy."""
        n = len(slots.offs)
        self.metrics.bump("kv_slot_writes", n)
        lo, hi = layers.start, layers.stop
        self.k_pool[lo:hi], self.v_pool[lo:hi] = _write_slots(
            self.metrics.bump, tuple(self.k_pool[lo:hi]),
            tuple(self.v_pool[lo:hi]), slots.pages, slots.offs, k, v)
        if slots.host:
            shape = (hi - lo, n, self.cfg.n_kv_heads, self.cfg.head_dim)
            k_rows = np.asarray(k, np.float32).reshape(shape)
            v_rows = np.asarray(v, np.float32).reshape(shape)
            for row, buf, off in slots.host:
                buf["k"][lo:hi, off] = k_rows[:, row]
                buf["v"][lo:hi, off] = v_rows[:, row]
                self.metrics.bump("kv_host_slot_writes")

    def reserve_slots(self, sids: list[int]) -> Slots:
        """Reserve one token slot per sequence for every layer; the
        index arrays go to the device once, for all the layers' writes."""
        with self._tlock:
            slots = self._reserve_locked(sids)
        return Slots(jnp.asarray(slots.pages), jnp.asarray(slots.offs),
                     slots.host)

    def write_slots(self, slots: Slots, layers: range, k, v) -> None:
        """k/v: the token's rows for ``layers`` x the slots' sequences,
        ``(len(layers), N, Hkv, hd)`` or any shape of that size."""
        with self._tlock:
            self._write_locked(slots, layers, k, v)

    def append_token(self, sid: int, k_token, v_token) -> None:
        """k/v_token: per-layer list of (Hkv, hd) arrays for ONE new token."""
        k = np.stack([np.asarray(x) for x in k_token])
        v = np.stack([np.asarray(x) for x in v_token])
        with self._tlock:
            self._write_locked(self._reserve_locked([sid]),
                               range(self.cfg.n_layers), k, v)

    def _host_fresh_page(self) -> dict:
        L, pg, H, hd = (self.cfg.n_layers, self.cfg.page_size,
                        self.cfg.n_kv_heads, self.cfg.head_dim)
        return {"k": np.zeros((L, pg, H, hd), np.float32),
                "v": np.zeros((L, pg, H, hd), np.float32)}

    # ----------------------------------------------------------- transit ops
    def _page_out_locked(self, seq: Sequence, logical: int) -> None:
        """Transit one HBM page to the host tier via the FUSED kernel:
        gather + int8 pack + wire checksum in one VMEM pass (the old
        path quantized, then walked the packed bytes again on the host
        for the checksum)."""
        with self.metrics.span("kv.page_out"):
            kind, page = seq.table[logical]
            assert kind == "hbm"
            handles = []
            ids = jnp.array([page], jnp.int32)
            for li in range(self.cfg.n_layers):
                pool_k = self.k_pool[li].reshape(self.cfg.n_pages,
                                                 self.cfg.page_size, -1)
                pool_v = self.v_pool[li].reshape(self.cfg.n_pages,
                                                 self.cfg.page_size, -1)
                qk, sk, ck = gather_quantize_crc(pool_k, ids)
                qv, sv, cv = gather_quantize_crc(pool_v, ids)
                hk = self.host.put(li, np.asarray(qk[0]), np.asarray(sk[0]),
                                   int(ck[0]))
                hv = self.host.put(li, np.asarray(qv[0]), np.asarray(sv[0]),
                                   int(cv[0]))
                handles.append((hk, hv))
            seq.table[logical] = ("host", handles)
            self._free.append(page)
            self.metrics.bump("pages_out")

    # ------------------------------------------------------ volume spill tier
    def host_page_count(self) -> int:
        """Logical pages currently in the host tier (packed or fresh)."""
        return sum(1 for seq in self.seqs.values()
                   for e in seq.table if e[0] in ("host", "host-fresh"))

    def _pack_page(self, handles) -> bytes:
        """Serialize one packed host page (all layers) for the pager:
        per layer, the fused-kernel crcs then the int8 payloads + f32
        scales.  The pager wraps this in its own wire crc32; page-in
        re-verifies the int8 bytes against the embedded kernel crcs via
        ``scatter_dequantize_crc`` — integrity end to end."""
        parts = []
        for li, (hk, hv) in enumerate(handles):
            qk, sk, ck = self.host.get(li, hk)
            qv, sv, cv = self.host.get(li, hv)
            parts.append(np.uint32(ck).tobytes())
            parts.append(np.uint32(cv).tobytes())
            parts.append(np.ascontiguousarray(qk, np.int8).tobytes())
            parts.append(np.ascontiguousarray(sk, "<f4").tobytes())
            parts.append(np.ascontiguousarray(qv, np.int8).tobytes())
            parts.append(np.ascontiguousarray(sv, "<f4").tobytes())
        return b"".join(parts)

    def _unpack_page(self, raw: bytes) -> list:
        """Inverse of :meth:`_pack_page` — per-layer
        ``(qk, sk, ck, qv, sv, cv)`` tuples (arrays not yet in the host
        tier; the caller decides whether to install them)."""
        pg = self.cfg.page_size
        D = self.cfg.n_kv_heads * self.cfg.head_dim
        qn, sn = pg * D, pg * 4
        out = []
        off = 0
        for _li in range(self.cfg.n_layers):
            ck = int(np.frombuffer(raw[off:off + 4], np.uint32)[0])
            cv = int(np.frombuffer(raw[off + 4:off + 8], np.uint32)[0])
            off += 8
            qk = np.frombuffer(raw[off:off + qn], np.int8).reshape(pg, D)
            off += qn
            sk = np.frombuffer(raw[off:off + sn], "<f4").astype(np.float32)
            off += sn
            qv = np.frombuffer(raw[off:off + qn], np.int8).reshape(pg, D)
            off += qn
            sv = np.frombuffer(raw[off:off + sn], "<f4").astype(np.float32)
            off += sn
            out.append((qk, sk, ck, qv, sv, cv))
        return out

    def _maybe_spill_locked(self) -> None:
        """Descend host-tier overflow onto the volume: while the host
        holds more than ``cfg.host_pages`` logical pages, spill the
        oldest INACTIVE sequence's packed pages as pager records
        (content-hash dedup makes prefix-shared pages one record).
        Host-fresh pages (raw f32, still being written) never spill."""
        if self.pager is None:
            return
        with self.metrics.span("kv.spill"):
            while self.host_page_count() > self.cfg.host_pages:
                victim = None
                for seq in self.seqs.values():               # oldest sid first
                    if seq.active:
                        continue
                    for li, entry in enumerate(seq.table):
                        if entry[0] == "host":
                            victim = (seq, li, entry[1])
                            break
                    if victim is not None:
                        break
                if victim is None:                           # all hot: tolerate
                    return
                seq, li, handles = victim
                payload = self._pack_page(handles)
                handle = self.pager.spill(payload)
                for lj, (hk, hv) in enumerate(handles):
                    if self.read_tier is not None:
                        self.read_tier.invalidate(("page", lj, hk, hv))
                    self.host.pop(lj, hk)
                    self.host.pop(lj, hv)
                seq.table[li] = ("vol", handle)

    def prefetch(self, sid: int) -> int:
        """Decode-ahead restore for a suspended sequence: issue linked
        async reads for its volume records so ``activate()`` finds the
        payloads already in flight.  Returns chains issued."""
        if self.pager is None:
            return 0
        with self._tlock:
            seq = self.seqs.get(sid)
            if seq is None:
                return 0
            handles = [e[1] for e in seq.table if e[0] == "vol"]
        if not handles:
            return 0
        return self.pager.prefetch(handles)

    def _page_in_locked(self, seq: Sequence, logical: int) -> bool:
        """Bring a cold page back into the pool (dequantize+scatter).

        A volume record is promoted to the host tier first (wire-crc
        verified in the pager), then the fused restore kernel re-verifies
        the int8 payload against the spill-time checksums.  On a checksum
        mismatch the allocated pool page goes back to the free list and
        the host entries stay put (nothing is popped until the whole
        page verified) — an IOError never leaks pool capacity."""
        with self.metrics.span("kv.page_in"):
            kind, payload = seq.table[logical]
            if kind == "vol":
                raw = self.pager.fetch(payload)              # may raise IOError
                handles = []
                for li, (qk, sk, ck, qv, sv, cv) in \
                        enumerate(self._unpack_page(raw)):
                    handles.append((self.host.put(li, qk, sk, ck),
                                    self.host.put(li, qv, sv, cv)))
                self.pager.release(payload)
                seq.table[logical] = ("host", handles)
                kind, payload = "host", handles
            page = self._alloc_page()
            if page is None:
                return False
            pg, H, hd = self.cfg.page_size, self.cfg.n_kv_heads, self.cfg.head_dim
            if kind == "host":
                ids = jnp.array([page], jnp.int32)
                new_k, new_v = [], []
                try:
                    for li, (hk, hv) in enumerate(payload):
                        qk, sk, ck = self.host.get(li, hk)
                        qv, sv, cv = self.host.get(li, hv)
                        pool_k = self.k_pool[li].reshape(self.cfg.n_pages, pg, -1)
                        pool_v = self.v_pool[li].reshape(self.cfg.n_pages, pg, -1)
                        # fused restore: dequantize+scatter AND checksum the int8
                        # payload as received, in the same pass — verified against
                        # the spill-time value before the page goes live
                        pool_k, rck = scatter_dequantize_crc(
                            pool_k, ids, jnp.asarray(qk)[None],
                            jnp.asarray(sk)[None])
                        pool_v, rcv = scatter_dequantize_crc(
                            pool_v, ids, jnp.asarray(qv)[None],
                            jnp.asarray(sv)[None])
                        if int(rck[0]) != ck or int(rcv[0]) != cv:
                            self.metrics.bump("transit_crc_errors")
                            raise IOError(
                                f"KV transit checksum mismatch: layer {li} page "
                                f"{logical} of seq {seq.seq_id} tore in transit")
                        new_k.append(pool_k.reshape(self.cfg.n_pages, pg, H, hd))
                        new_v.append(pool_v.reshape(self.cfg.n_pages, pg, H, hd))
                except IOError:
                    self._free.append(page)                  # no capacity leak
                    raise
                for li, (hk, hv) in enumerate(payload):      # verified: commit
                    if self.read_tier is not None:
                        self.read_tier.invalidate(("page", li, hk, hv))
                    self.host.pop(li, hk)
                    self.host.pop(li, hv)
                    self.k_pool[li] = new_k[li]
                    self.v_pool[li] = new_v[li]
            else:                                            # host-fresh (raw f32)
                for li in range(self.cfg.n_layers):
                    self.k_pool[li] = self.k_pool[li].at[page].set(
                        jnp.asarray(payload["k"][li], self.cfg.dtype))
                    self.v_pool[li] = self.v_pool[li].at[page].set(
                        jnp.asarray(payload["v"][li], self.cfg.dtype))
            seq.table[logical] = ("hbm", page)
            self.metrics.bump("pages_in")
            return True

    def deactivate(self, sid: int) -> None:
        """Sequence paused/finished: eagerly transit its pages out.

        With an eviction pool attached, the page-out DMA (fused
        gather+quantize+checksum) is submitted to the volume's shared
        eviction cores instead of running on the decode thread.  The
        sync fallback runs the whole page-out loop under ``_tlock`` —
        a concurrent deactivate of the same sequence sees "host"
        entries and skips, instead of double-freeing pool pages."""
        items = []
        with self._tlock:
            seq = self.seqs[sid]
            seq.active = False
            if not self.cfg.eager_eviction:
                return
            if self._evict_pool is not None:
                for li, entry in enumerate(seq.table):
                    if entry[0] == "hbm":
                        self._inflight_evictions += 1
                        items.append((seq, li))
            else:
                for li, entry in enumerate(seq.table):
                    if entry[0] == "hbm":
                        self._page_out_locked(seq, li)
                self._maybe_spill_locked()
        for it in items:
            self._evict_pool.submit(self, it)

    # eviction-pool participant hooks (same contract as CaitiCache)
    def _evict_slot(self, item) -> None:
        seq, li = item
        with self._tlock:
            # a re-activated sequence cancels its pending page-outs
            if seq.active or seq.table[li][0] != "hbm":
                self.metrics.bump("evict_skipped")
                return
            self._page_out_locked(seq, li)
            self._maybe_spill_locked()

    def _evict_slots(self, items) -> None:
        """Batch drain hook: the pool hands several queued page-outs at
        once; one lock acquisition covers the whole batch."""
        self.metrics.bump("evict_batches")
        with self._tlock:
            for seq, li in items:
                if seq.active or seq.table[li][0] != "hbm":
                    self.metrics.bump("evict_skipped")
                    continue
                self._page_out_locked(seq, li)
            self._maybe_spill_locked()

    def _complete_eviction(self) -> None:
        with self._evict_cv:
            self._inflight_evictions -= 1
            self._evict_cv.notify_all()

    def drain_evictions(self, timeout: float = 10.0,
                        raise_on_timeout: bool = True) -> bool:
        """Barrier: wait until every submitted page-out has run (the
        pool-side analogue of ``barrier()``/PREFLUSH).  Returns True
        when the drain completed; on expiry raises TimeoutError (or
        returns False with ``raise_on_timeout=False``) — a silent
        timeout would let ``activate()`` read tables that page-out
        workers are still mutating."""
        with self._evict_cv:
            done = self._evict_cv.wait_for(
                lambda: self._inflight_evictions == 0, timeout=timeout)
            pending = self._inflight_evictions
        if not done and raise_on_timeout:
            raise TimeoutError(
                f"drain_evictions: {pending} page-outs still in flight "
                f"after {timeout}s")
        return done

    def activate(self, sid: int) -> None:
        """Resume a sequence: page everything back in (may bypass).

        Raises TimeoutError if the eviction barrier expires (page-outs
        still in flight — proceeding would race their table writes)."""
        with self.metrics.span("kv.activate"):
            if self._evict_pool is not None:
                self.drain_evictions()
            with self._tlock:
                seq = self.seqs[sid]
                seq.active = True
                for li, entry in enumerate(seq.table):
                    if entry[0] in ("host", "host-fresh", "vol"):
                        if not self._page_in_locked(seq, li):
                            self.metrics.bump("activate_stalls")
                            return                            # partial: retry later

    def release(self, sid: int) -> None:
        with self._tlock:
            seq = self.seqs.pop(sid)
            for entry in seq.table:
                if entry[0] == "hbm":
                    self._free.append(entry[1])
                elif entry[0] == "host":
                    for li, (hk, hv) in enumerate(entry[1]):
                        if self.read_tier is not None:
                            self.read_tier.invalidate(("page", li, hk, hv))
                        self.host.pop(li, hk)
                        self.host.pop(li, hv)
                elif entry[0] == "vol":
                    if self.read_tier is not None:
                        for li in range(self.cfg.n_layers):
                            self.read_tier.invalidate(
                                ("vol-page", li, entry[1]))
                    self.pager.release(entry[1])

    # -------------------------------------------------------------- attention
    def table_for(self, sids: list[int]) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Dense (B, max_pages) physical table + (B,) lengths for attention.
        Sequences must be fully HBM-resident (activate() first)."""
        mp = self.cfg.max_pages_per_seq
        table = np.zeros((len(sids), mp), np.int32)
        lens = np.zeros((len(sids),), np.int32)
        with self._tlock:
            for bi, sid in enumerate(sids):
                seq = self.seqs[sid]
                if len(seq.table) > mp:
                    raise ValueError(
                        f"seq {sid} holds {len(seq.table)} pages > "
                        f"max_pages_per_seq={mp}: too long for the dense "
                        f"block table (serve it through the hybrid "
                        f"attention path)")
                lens[bi] = seq.length
                for li, entry in enumerate(seq.table):
                    assert entry[0] == "hbm", \
                        f"page {li} of seq {sid} not resident"
                    table[bi, li] = entry[1]
        return jnp.asarray(table), jnp.asarray(lens)

    def _page_kv(self, layer: int, entry) -> tuple[np.ndarray, np.ndarray]:
        """One logical page's (page_size, Hkv, hd) k/v from whichever tier
        holds it (the transit read path: cache hit OR backend read)."""
        pg, H, hd = self.cfg.page_size, self.cfg.n_kv_heads, self.cfg.head_dim
        if entry[0] == "hbm":
            return (np.asarray(self.k_pool[layer][entry[1]], np.float32),
                    np.asarray(self.v_pool[layer][entry[1]], np.float32))
        if entry[0] == "host":
            hk, hv = entry[1][layer]
            if self.read_tier is not None:
                cached = self.read_tier.lookup(("page", layer, hk, hv))
                if cached is not None:
                    return cached
            qk, sk, _ck = self.host.get(layer, hk)
            qv, sv, _cv = self.host.get(layer, hv)
            k = (qk.astype(np.float32) * sk[:, None]).reshape(pg, H, hd)
            v = (qv.astype(np.float32) * sv[:, None]).reshape(pg, H, hd)
            if self.read_tier is not None:
                self.read_tier.insert(("page", layer, hk, hv), (k, v))
            return k, v
        if entry[0] == "vol":
            # hybrid attention over a spilled page: restore the record
            # WITHOUT promoting it (the sequence stays cold); the read
            # tier amortizes the volume round trip across layers/steps
            handle = entry[1]
            if self.read_tier is not None:
                cached = self.read_tier.lookup(("vol-page", layer, handle))
                if cached is not None:
                    return cached
            raw = self.pager.fetch(handle)
            layers = self._unpack_page(raw)
            out = None
            for li, (qk, sk, _ck, qv, sv, _cv) in enumerate(layers):
                k = (qk.astype(np.float32) * sk[:, None]).reshape(pg, H, hd)
                v = (qv.astype(np.float32) * sv[:, None]).reshape(pg, H, hd)
                if self.read_tier is not None:
                    self.read_tier.insert(("vol-page", li, handle), (k, v))
                if li == layer:
                    out = (k, v)
            return out
        return (entry[1]["k"][layer].astype(np.float32),
                entry[1]["v"][layer].astype(np.float32))   # host-fresh

    def attention(self, layer: int, q, sids: list[int], *,
                  use_kernel: bool | None = None):
        """q: (B, H, hd) one decode step for the given sequences.

        Fast path: every page HBM-resident AND every table within the
        dense bound -> block-table kernel (lba->pba walk fused in).  The
        kernel is the default on a TPU; ``use_kernel`` overrides the
        platform's choice (tests compare the kernel with the jnp ref).
        Slow path (pages bypassed to the host tier under pool pressure,
        or a sequence past max_pages_per_seq): materialize each
        sequence's KV from every tier — decode keeps running instead of
        stalling on page-in, the serving analogue of Caiti's conditional
        bypass."""
        with self.metrics.span("kv.attention"):
            mp = self.cfg.max_pages_per_seq
            resident = all(len(self.seqs[sid].table) <= mp
                           and all(e[0] == "hbm" for e in self.seqs[sid].table)
                           for sid in sids)
            if resident:
                table, lens = self.table_for(sids)
                if on_tpu() if use_kernel is None else use_kernel:
                    return paged_attention(q, self.k_pool[layer],
                                           self.v_pool[layer], table, lens)
                return kref.paged_attention_ref(q, self.k_pool[layer],
                                                self.v_pool[layer], table, lens)
            self.metrics.bump("hybrid_attention")
            pg, H, hd = self.cfg.page_size, self.cfg.n_kv_heads, self.cfg.head_dim
            B = len(sids)
            with self._tlock:
                S = max(len(self.seqs[s].table) for s in sids) * pg
                k = np.zeros((B, S, H, hd), np.float32)
                v = np.zeros((B, S, H, hd), np.float32)
                lens = np.zeros((B,), np.int32)
                for bi, sid in enumerate(sids):
                    seq = self.seqs[sid]
                    lens[bi] = seq.length
                    for li, entry in enumerate(seq.table):
                        pk, pv = self._page_kv(layer, entry)
                        k[bi, li * pg:(li + 1) * pg] = pk
                        v[bi, li * pg:(li + 1) * pg] = pv
            # single-"page" ref attention over the materialized view
            kpool = jnp.asarray(k).reshape(B * 1, S, H, hd)
            vpool = jnp.asarray(v).reshape(B * 1, S, H, hd)
            table = jnp.arange(B, dtype=jnp.int32)[:, None]
            return kref.paged_attention_ref(q, kpool, vpool, table,
                                            jnp.asarray(lens))

    # ---------------------------------------------------------------- stats
    def occupancy(self) -> float:
        return 1.0 - len(self._free) / self.cfg.n_pages
