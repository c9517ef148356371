"""Serving engine: continuous batching over the paged (BTT-style) KV cache.

The engine runs dense/GQA decoder LMs (the transformer family) with the
paged decode path: per layer, the new token's K/V are appended to the
sequence's pages (block-table write = the lba->pba map update) and decode
attention gathers pages through the table.  On a TPU that is always the
paged-attention Pallas kernel, lowered to Mosaic; elsewhere the default is
the jnp reference, and tests pass ``use_kernel=True`` to run the same
kernel in interpret mode.

Scheduling follows the paper's transit discipline:
  * finished / preempted sequences are *eagerly* packed to the host tier
    (``deactivate``) so the HBM pool stays near-empty, exactly like Caiti's
    WBQ drain;
  * when admission would overflow the pool anyway, the new sequence's pages
    *bypass* to the host tier rather than stall a running decode;
  * a step "fsync" (``barrier``) completes all migrations before the batch
    shape changes.

The decode step's layer loop runs in Python over the cache's two pools,
one array for K and one for V, each layer a block of their rows: each
layer's dense math is two jitted calls around the cache's slot write
(one jitted in-place scatter for the batch) and attention, both of which
take the layer as a traced row offset.  Prefill is eager ops; it writes
each token's K/V for every layer in one scatter.
The same code serves the CPU tests (smoke widths) and the chip (full
widths, ``launch/serve.py --full``).
"""
from __future__ import annotations

import json
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metrics import Metrics
from repro.models.common import ModelConfig
from repro.models.layers import apply_norm, mlp_apply, rope
from .kvcache import PagedCacheConfig, PagedKVCache


@dataclass
class Request:
    req_id: int
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: list[int] = field(default_factory=list)
    seq_id: int = -1
    done: bool = False
    t_submit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0


def _layer_params(params, i: int):
    return jax.tree.map(lambda a: a[i], params["blocks"])


def _dense_step_fns(cfg: ModelConfig, bump):
    """The dense math of one decode step as four jitted functions: the
    embedding, each layer's attention input and output halves, and the
    head.  ``cfg`` is closed over (static); every weight is an argument,
    and the layer index is a traced int32, so one executable per batch
    shape serves every layer.  Without excess precision every op rounds
    to its dtype as an eager op does, so the logits are those of the
    eager ops, bit for bit.  Each body bumps ``lm.dense_traces``: it runs
    only while JAX traces."""
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def embed(table, tokens):
        bump("lm.dense_traces")
        return jnp.take(table, tokens[:, None], axis=0)      # (B, 1, D)

    def attn_in(blocks, li, x, pos):
        """ln1, QKV (+bias), RoPE -> q (B, H, hd), and the keys and
        values (B, Hkv, hd) the cache writes."""
        bump("lm.dense_traces")
        blk = jax.tree.map(lambda a: a[li], blocks)
        B = x.shape[0]
        xn = apply_norm(x, blk["ln1"], cfg.norm)
        q = (xn @ blk["attn"]["wq"]).reshape(B, 1, H, hd)
        k = (xn @ blk["attn"]["wk"]).reshape(B, 1, Hkv, hd)
        v = (xn @ blk["attn"]["wv"]).reshape(B, 1, Hkv, hd)
        if "bq" in blk["attn"]:
            q = q + blk["attn"]["bq"].reshape(1, 1, H, hd)
            k = k + blk["attn"]["bk"].reshape(1, 1, Hkv, hd)
            v = v + blk["attn"]["bv"].reshape(1, 1, Hkv, hd)
        if cfg.pos == "rope":
            q = rope(q, pos[:, None], cfg.rope_theta)
            k = rope(k, pos[:, None], cfg.rope_theta)
        return q[:, 0], k[:, 0], v[:, 0]

    def attn_out(blocks, li, x, a):
        """wo and the residual, ln2, the MLP and its residual."""
        bump("lm.dense_traces")
        blk = jax.tree.map(lambda w: w[li], blocks)
        x = x + a.reshape(x.shape[0], 1, -1) @ blk["attn"]["wo"]
        h = apply_norm(x, blk["ln2"], cfg.norm)
        return x + mlp_apply(h, blk["mlp"], cfg.act)

    def head(final_norm, w, x):
        bump("lm.dense_traces")
        x = apply_norm(x, final_norm, cfg.norm)
        w = w.T if cfg.tie_embeddings else w
        return (x @ w).astype(jnp.float32)[:, 0]              # (B, V)

    opts = {"xla_allow_excess_precision": False}
    return tuple(jax.jit(f, compiler_options=opts)
                 for f in (embed, attn_in, attn_out, head))


class PagedLM:
    """Paged decode path for the dense transformer family."""

    def __init__(self, cfg: ModelConfig, params, cache: PagedKVCache,
                 use_kernel: bool | None = None) -> None:
        assert cfg.family == "dense", "paged engine serves dense LMs"
        self.cfg = cfg
        self.params = params
        self.cache = cache
        self.use_kernel = use_kernel
        (self._embed, self._attn_in, self._attn_out,
         self._head) = _dense_step_fns(cfg, cache.metrics.bump)
        self._layer_ids = [jnp.asarray(i, jnp.int32)
                           for i in range(cfg.n_layers)]

    def prefill(self, tokens: np.ndarray, sid: int) -> jnp.ndarray:
        """Run the prompt through the model, append K/V pages, return the
        last-token logits. tokens: (T,) one sequence."""
        with self.cache.metrics.span("lm.prefill"):
            cfg, p = self.cfg, self.params
            T = len(tokens)
            tok = jnp.asarray(tokens, jnp.int32)[None]
            x = jnp.take(p["embed"], tok, axis=0)
            positions = jnp.arange(T, dtype=jnp.int32)[None]
            kv_per_layer = []
            for li in range(cfg.n_layers):
                blk = _layer_params(p, li)
                xn = apply_norm(x, blk["ln1"], cfg.norm)
                q = (xn @ blk["attn"]["wq"]).reshape(1, T, cfg.n_heads, cfg.hd)
                k = (xn @ blk["attn"]["wk"]).reshape(1, T, cfg.n_kv_heads, cfg.hd)
                v = (xn @ blk["attn"]["wv"]).reshape(1, T, cfg.n_kv_heads, cfg.hd)
                if "bq" in blk["attn"]:
                    q = q + blk["attn"]["bq"].reshape(1, 1, cfg.n_heads, cfg.hd)
                    k = k + blk["attn"]["bk"].reshape(1, 1, cfg.n_kv_heads, cfg.hd)
                    v = v + blk["attn"]["bv"].reshape(1, 1, cfg.n_kv_heads, cfg.hd)
                if cfg.pos == "rope":
                    q = rope(q, positions, cfg.rope_theta)
                    k = rope(k, positions, cfg.rope_theta)
                # dense causal attention for the prompt (prefill is compute-bound;
                # pages are written below for the decode phase)
                from repro.kernels.ref import flash_attention_ref
                a = flash_attention_ref(q, k, v, causal=True,
                                        window=cfg.attn_window)
                x = x + a.reshape(1, T, -1) @ blk["attn"]["wo"]
                h = apply_norm(x, blk["ln2"], cfg.norm)
                x = x + mlp_apply(h, blk["mlp"], cfg.act)
                kv_per_layer.append((k[0], v[0]))            # (T, Hkv, hd)
            # append pages token by token, each token's rows of every
            # layer from one copy of the prompt's K/V on the host
            ks, vs = (np.asarray(jnp.stack(a)) for a in zip(*kv_per_layer))
            for t in range(T):
                self.cache.append_token(sid, list(ks[:, t]), list(vs[:, t]))
            x = apply_norm(x[:, -1:], p["final_norm"], cfg.norm)
            w = p["embed"].T if cfg.tie_embeddings else p["head"]
            return (x @ w).astype(jnp.float32)[0, 0]

    def decode_step(self, tokens: np.ndarray, sids: list[int],
                    positions: np.ndarray) -> jnp.ndarray:
        """One token for each running sequence. tokens: (B,), returns
        (B, V) logits."""
        with self.cache.metrics.span("lm.decode"):
            cfg, p = self.cfg, self.params
            blocks = p["blocks"]
            x = self._embed(p["embed"], jnp.asarray(tokens, jnp.int32))
            pos = jnp.asarray(positions, jnp.int32)
            slots = None
            for li, lid in enumerate(self._layer_ids):
                q, k, v = self._attn_in(blocks, lid, x, pos)
                # write THIS layer's kv before attending (token attends to
                # self); layer 0 reserves the slot every layer writes
                with self.cache.metrics.span("kv.append"):
                    if slots is None:
                        slots = self.cache.reserve_slots(sids)
                    self.cache.write_slots(slots, range(li, li + 1), k, v)
                a = self.cache.attention(li, q, sids,
                                         use_kernel=self.use_kernel)
                x = self._attn_out(blocks, lid, x, a)
            w = p["embed"] if cfg.tie_embeddings else p["head"]
            return self._head(p["final_norm"], w, x)


class AsyncRequestLog:
    """Durable request log riding a striped volume's async frontend.

    Each retired request is one JSON record, appended as a chained
    ``write_multi`` through ``volume.submit`` — the write overlaps the
    next decode step instead of stalling the scheduler tick on the PMem
    round trip (the transit discipline, applied to the serving plane's
    own durability).  ``drain()`` settles every in-flight ticket and
    issues one async fsync barrier (which coalesces with any concurrent
    committer via the volume's GroupCommitter); a device error surfaces
    there as that record's per-ticket failure, not a serving-loop
    exception.

    ``volume`` is anything speaking the async surface — a
    ``StripedVolume`` or a ``repro.cluster.ClusterVolume`` (a
    replicated request log that survives node loss).  Records are
    capped at the device's ``max_atomic_write_blocks()`` so a
    multi-block append stays whole-record atomic everywhere (on a
    cluster that bound is one placement chunk — a record spanning
    chunks would commit chain by chain).

    ``registered_buffers > 0`` acquires a :class:`BufferRegistry` pool
    on the volume's engine and appends through it: each record's blocks
    are filled into pinned pool buffers and the HANDLES ride the ticket
    — the engine never snapshots the payload under its lock, and the
    buffers release back to the pool at completion (success, failure or
    cancel).  This is the same zero-copy discipline the checkpoint
    blockstore's commit path uses, extended to the serving plane's
    ``write_multi`` block lists."""

    def __init__(self, volume, *, base_lba: int = 0,
                 capacity_blocks: int | None = None,
                 tenant: str | None = None,
                 registered_buffers: int = 0) -> None:
        self.vol = volume
        self.tenant = tenant
        self.block_size = volume.block_size
        self._reg = (volume.register_buffers(registered_buffers)
                     if registered_buffers > 0
                     and hasattr(volume, "register_buffers") else None)
        self._max_rec = (volume.max_atomic_write_blocks()
                         if hasattr(volume, "max_atomic_write_blocks")
                         else None)
        self._base = base_lba
        # the log is a RING over [base_lba, base_lba + capacity): a
        # long-running serve loop wraps and overwrites its oldest
        # records instead of writing past the volume (ship records to
        # cold storage before a wrap if they must be kept forever)
        self._cap = (volume.n_lbas - base_lba if capacity_blocks is None
                     else capacity_blocks)
        assert self._cap >= 1
        self._off = 0
        self._tickets: list = []
        self.logged = 0
        self.wraps = 0
        self.errors: list[tuple[int, BaseException]] = []

    def _alloc(self, n_blocks: int) -> int:
        assert n_blocks <= self._cap, "record larger than the log ring"
        assert self._max_rec is None or n_blocks <= self._max_rec, \
            (f"record of {n_blocks} blocks exceeds the device's "
             f"whole-object-atomic bound ({self._max_rec})")
        if self._off + n_blocks > self._cap:
            self._off = 0                    # wrap: oldest records go
            self.wraps += 1
        lba = self._base + self._off
        self._off += n_blocks
        return lba

    def append(self, record: dict) -> None:
        raw = json.dumps(record).encode()
        bs = self.block_size
        payload = len(raw).to_bytes(4, "little") + raw
        blocks = [payload[i:i + bs].ljust(bs, b"\x00")
                  for i in range(0, len(payload), bs)]
        if self._reg is not None:
            # zero-copy: fill pool buffers OUTSIDE the engine lock and
            # submit the pinned handles; completion releases them
            regs = []
            for chunk in blocks:
                buf = self._reg.acquire()
                buf.data[:len(chunk)] = np.frombuffer(chunk, np.uint8)
                regs.append(buf)
            blocks = regs
        # block=True: a retirement burst deeper than the engine's
        # in-flight window waits its turn (the one stall this log
        # accepts) — a record is never silently dropped
        lba = self._alloc(len(blocks))
        if len(blocks) > 1:
            t = self.vol.submit("write_multi", lba, blocks=blocks,
                                tenant=self.tenant, block=True)
        else:
            t = self.vol.submit("write", lba, data=blocks[0],
                                tenant=self.tenant, block=True)
        self._tickets.append((lba, t))
        self.logged += 1

    def drain(self) -> int:
        """One async fsync barrier + post-barrier error collection;
        returns how many records have failed since the previous drain
        (all failures stay collected in ``errors``).

        The barrier is submitted FIRST: IO_DRAIN gates it on every
        in-flight append in-engine, so the drain pays ONE wait round
        trip instead of one per record — by the time the barrier
        completes, every append ticket is already settled and error
        collection is a ring sweep, not a sequence of waits."""
        reported = len(self.errors)
        sync = self.vol.submit("fsync", block=True)
        self.vol.wait(sync)
        tickets, self._tickets = self._tickets, []
        for lba, t in tickets:           # already DONE: consume + collect
            self.vol.wait(t)
            if t.error is not None:
                self.errors.append((lba, t.error))
        if sync.error is not None:
            raise sync.error
        return len(self.errors) - reported


class ServeEngine:
    """Continuous-batching front end."""

    def __init__(self, cfg: ModelConfig, params, *,
                 cache_cfg: PagedCacheConfig | None = None,
                 max_batch: int = 8, eos_token: int = -1,
                 use_kernel: bool | None = None, rng_seed: int = 0,
                 request_log: AsyncRequestLog | None = None,
                 autotune_every: int = 0,
                 pager=None, prefetch_depth: int = 2) -> None:
        self.cfg = cfg
        self.metrics = Metrics()
        # optional durable request log: retired requests are appended
        # through the volume's async frontend, overlapped with decode
        self.request_log = request_log
        # control-plane cadence: every N scheduler ticks, run one
        # autotune_step() on the request log's backing volume (no-op
        # unless the volume has a controller attached) — the serve loop
        # is the natural place for the storage control ticks to ride
        self.autotune_every = autotune_every
        self._ticks_since_tune = 0
        # optional volume-backed KV spill tier (serve.kvpager.KVPager):
        # suspended sessions' cold pages descend past the host tier onto
        # the striped volume; prefetch_depth suspended requests get
        # decode-ahead linked reads issued each tick so their resume
        # overlaps the current batch's decode
        self.prefetch_depth = prefetch_depth
        self.cache = PagedKVCache(cache_cfg or PagedCacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.hd), metrics=self.metrics, pager=pager)
        self.lm = PagedLM(cfg, params, self.cache, use_kernel=use_kernel)
        self.max_batch = max_batch
        self.eos = eos_token
        self.queue: list[Request] = []
        self.running: list[Request] = []
        self.suspended: list[Request] = []
        self.finished: list[Request] = []
        self._rng = np.random.default_rng(rng_seed)
        self._next_id = 0

    def submit(self, prompt: list[int], max_new_tokens: int = 16,
               temperature: float = 0.0) -> Request:
        req = Request(self._next_id, list(prompt), max_new_tokens,
                      temperature, t_submit=time.perf_counter())
        self._next_id += 1
        self.queue.append(req)
        return req

    # ----------------------------------------------------------- scheduling
    def suspend(self, req: Request) -> None:
        """Preempt a running request: its pages eagerly transit out
        (host tier, then the volume once the host budget overflows);
        ``_admit`` resumes it ahead of fresh prompts."""
        with self.metrics.span("serve.suspend"):
            self.running.remove(req)
            self.cache.deactivate(req.seq_id)
            self.suspended.append(req)
            self.metrics.bump("suspends")

    def _prefetch_ahead(self) -> None:
        """Decode-ahead restore: linked async reads for the next
        ``prefetch_depth`` suspended requests' spilled pages, issued
        BEFORE admission so the volume round trip overlaps this tick's
        decode instead of stalling activate()."""
        for req in self.suspended[:self.prefetch_depth]:
            self.cache.prefetch(req.seq_id)

    def _admit(self) -> None:
        # resumes first: a suspended request already holds KV (and its
        # prefetched pages are in flight) — cheaper than a fresh prefill
        while self.suspended and len(self.running) < self.max_batch:
            req = self.suspended.pop(0)
            self.cache.activate(req.seq_id)
            self.running.append(req)
            self.metrics.bump("resumes")
        while self.queue and len(self.running) < self.max_batch:
            req = self.queue.pop(0)
            req.seq_id = self.cache.new_sequence()
            logits = self.lm.prefill(np.asarray(req.prompt, np.int32),
                                     req.seq_id)
            tok = self._sample(logits[None], [req])[0]
            req.out_tokens.append(int(tok))
            req.t_first = time.perf_counter()
            self.running.append(req)

    def _sample(self, logits, reqs) -> np.ndarray:
        out = np.zeros((len(reqs),), np.int64)
        logits = np.asarray(logits)
        for i, req in enumerate(reqs):
            if req.temperature <= 0:
                out[i] = int(np.argmax(logits[i]))
            else:
                z = logits[i] / req.temperature
                z = z - z.max()
                prob = np.exp(z) / np.exp(z).sum()
                out[i] = int(self._rng.choice(len(prob), p=prob))
        return out

    def _retire(self, req: Request) -> None:
        req.done = True
        req.t_done = time.perf_counter()
        self.cache.deactivate(req.seq_id)     # eager transit to host tier
        self.cache.release(req.seq_id)
        if self.request_log is not None:      # overlapped, never a stall
            self.request_log.append({"req_id": req.req_id,
                                     "prompt": req.prompt,
                                     "tokens": req.out_tokens})
        self.finished.append(req)

    def step(self) -> int:
        """One scheduler tick: admit, decode one token for every runner."""
        with self.metrics.span("serve.step"):
            self._prefetch_ahead()
            self._admit()
            if not self.running:
                return 0
            reqs = self.running
            tokens = np.asarray([r.out_tokens[-1] for r in reqs], np.int64)
            positions = np.asarray([len(r.prompt) + len(r.out_tokens) - 1
                                    for r in reqs], np.int64)
            logits = self.lm.decode_step(tokens, [r.seq_id for r in reqs],
                                         positions)
            nxt = self._sample(logits, reqs)
            still = []
            for req, tok in zip(reqs, nxt):
                req.out_tokens.append(int(tok))
                if (len(req.out_tokens) >= req.max_new_tokens
                        or tok == self.eos):
                    self._retire(req)
                else:
                    still.append(req)
            self.running = still
            return len(reqs)

    def _autotune_tick(self) -> None:
        if self.autotune_every <= 0 or self.request_log is None:
            return
        self._ticks_since_tune += 1
        if self._ticks_since_tune < self.autotune_every:
            return
        self._ticks_since_tune = 0
        vol = getattr(self.request_log, "vol", None)
        step = getattr(vol, "autotune_step", None)
        if step is not None:
            moves = step()
            if moves:
                self.metrics.bump("autotune_moves", len(moves))

    def run(self, max_ticks: int = 10_000) -> list[Request]:
        ticks = 0
        while (self.queue or self.running or self.suspended) \
                and ticks < max_ticks:
            self.step()
            self._autotune_tick()
            ticks += 1
        if self.request_log is not None:
            n_bad = self.request_log.drain()  # settle overlapped appends
            if n_bad:                         # surfaced, not swallowed
                self.metrics.bump("request_log_failures", n_bad)
        return self.finished
