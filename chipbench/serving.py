"""Drive the serving engine as a cell's traffic does, and stamp tokens.

The entry the window drives is ``ServeEngine.step`` with ``submit`` and
``suspend``: ``PagedLM.prefill``/``decode_step`` -> ``PagedKVCache``
(append, paged attention, page-out and page-in through the fused codec)
-> ``KVPager`` -> ``StripedVolume``.  This file builds and drives the
engine; ``instrument.py`` wraps its methods in a traced run.

Suspended sessions rotate through ``eng.suspended`` itself: the engine
resumes from its head, the harness appends the session it suspends.
When a finished session's next request waits in the queue, the harness
holds back the suspended sessions that would take its slot for that one
step, since the engine resumes before it admits.
"""
from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.serve import make_spill_pager  # noqa: E402
from repro.models.common import ModelConfig  # noqa: E402
from repro.serve import PagedCacheConfig, ServeEngine  # noqa: E402

from weights import dims  # noqa: E402

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts executables built (compiled, or loaded from the persistent
    cache) in this process."""

    def __init__(self) -> None:
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


def model_config(cfg: dict) -> ModelConfig:
    dm = dims(cfg)
    return ModelConfig(
        name=cfg["source"], family="dense", n_layers=dm["L"],
        d_model=dm["D"], n_heads=dm["H"], n_kv_heads=dm["Hkv"],
        d_ff=dm["F"], vocab=dm["V"], head_dim=dm["hd"],
        qkv_bias=dm["qkv_bias"], rope_theta=dm["theta"],
        tie_embeddings=dm["tied"], dtype=dm["dtype"])


def cache_geometry(cfg: dict, engine: dict, max_tokens: int) -> dict:
    """Pages per session at its longest, and the pool that holds
    ``pool_sessions`` such sessions."""
    dm = dims(cfg)
    page = engine["page_size"]
    per_seq = -(-max_tokens // page)
    return {"L": dm["L"], "Hkv": dm["Hkv"], "hd": dm["hd"], "page": page,
            "pages_per_seq": per_seq,
            "pool_pages": engine["pool_sessions"] * per_seq,
            "bytes_per_elem": dm["dtype"].itemsize}


def build_engine(cfg: dict, engine: dict, sessions: int, max_tokens: int,
                 params) -> ServeEngine:
    g = cache_geometry(cfg, engine, max_tokens)
    cache_cfg = PagedCacheConfig(
        n_layers=g["L"], n_kv_heads=g["Hkv"], head_dim=g["hd"],
        page_size=g["page"], n_pages=g["pool_pages"],
        host_pages=engine["host_pages"],
        max_pages_per_seq=g["pages_per_seq"], dtype=dims(cfg)["dtype"])
    pager = make_spill_pager(cache_cfg, sessions * g["pages_per_seq"])
    return ServeEngine(model_config(cfg), params, cache_cfg=cache_cfg,
                       max_batch=engine["max_batch"], pager=pager)


def close_engine(eng: ServeEngine) -> None:
    eng.cache.pager.vol.close()


@dataclass
class Served:
    """What one request was served, for the latency metrics and for the
    comparison with the reference."""
    session: int
    prompt: list[int]
    req: object
    stamps: list[float] = field(default_factory=list)
    suspended_after: set[int] = field(default_factory=set)
    suspend_lengths: list[int] = field(default_factory=list)
    run_since: int = 0

    @property
    def tokens(self) -> list[int]:
        return self.req.out_tokens


@dataclass
class Step:
    t0: float
    t1: float
    resumed: list[int]            # req ids resumed in this step
    compiles: int


class ClosedLoop:
    def __init__(self, eng: ServeEngine, traffic, compiles: CompileCounter,
                 preempt_every: int = 0) -> None:
        self.eng = eng
        self.traffic = traffic
        self.compiles = compiles
        self.preempt_every = preempt_every
        self.served: dict[int, Served] = {}
        self.turns = [0] * traffic.sessions
        self.steps: list[Step] = []
        self._n_finished = 0

    # ------------------------------------------------------------ requests
    def _submit(self, session: int) -> None:
        prompt, answer = self.traffic.request(session,
                                              self.turns[session])
        self.turns[session] += 1
        req = self.eng.submit(prompt, max_new_tokens=answer)   # greedy
        self.served[req.req_id] = Served(session, list(prompt), req)

    def _suspend(self, s: Served) -> None:
        s.suspended_after.add(len(s.tokens) - 1)
        s.suspend_lengths.append(len(s.prompt) + len(s.tokens) - 1)
        self.eng.suspend(s.req)

    # ---------------------------------------------------------------- step
    def step(self) -> Step:
        eng = self.eng
        held = []
        if eng.queue:                     # a finished session's next turn
            free = eng.max_batch - len(eng.running)
            keep = max(0, free - len(eng.queue))
            held, eng.suspended = eng.suspended[keep:], eng.suspended[:keep]
        waiting = {r.req_id for r in eng.suspended}
        c0 = self.compiles.n
        t0 = time.perf_counter()
        eng.step()
        t1 = time.perf_counter()
        eng.suspended.extend(held)
        n = len(self.steps)
        resumed = []
        for s in self.served.values():
            new = len(s.tokens) - len(s.stamps)
            if new <= 0:
                continue
            if not s.stamps:              # the prefill's token, synced
                s.stamps.append(s.req.t_first)
                s.run_since = n
                new -= 1
            s.stamps.extend([t1] * new)
            if s.req.req_id in waiting:
                resumed.append(s.req.req_id)
                s.run_since = n
        rec = Step(t0, t1, resumed, self.compiles.n - c0)
        self.steps.append(rec)
        for req in eng.finished[self._n_finished:]:
            self._submit(self.served[req.req_id].session)
        self._n_finished = len(eng.finished)
        if (self.preempt_every and (n + 1) % self.preempt_every == 0
                and eng.running):
            victim = min((self.served[r.req_id] for r in eng.running),
                         key=lambda s: s.run_since)
            self._suspend(victim)
        return rec

    # --------------------------------------------------------------- phases
    def fill(self) -> None:
        """Prefill every session, ``max_batch`` at a time; the sessions
        beyond the last batch wait suspended, oldest first."""
        eng = self.eng
        held = []
        order = list(range(self.traffic.sessions))
        groups = [order[i:i + eng.max_batch]
                  for i in range(0, len(order), eng.max_batch)]
        for gi, group in enumerate(groups):
            for s in group:
                self._submit(s)
            self.step()
            if gi + 1 < len(groups):
                for r in list(eng.running):
                    self._suspend(self.served[r.req_id])
                held += eng.suspended
                eng.suspended = []
        eng.suspended = held + eng.suspended

    def warm_up(self, min_steps: int, max_steps: int = 12) -> int:
        """Steps of the cell's own traffic until one builds no new
        executable; returns the steps taken."""
        for i in range(max_steps):
            rec = self.step()
            if i + 1 >= min_steps and rec.compiles == 0:
                return i + 1
        return max_steps

    def window(self, seconds: float) -> tuple[float, float]:
        """Steps until the first that ends at or after ``seconds``;
        returns (start, end) on the host clock."""
        start = self.steps[-1].t1
        while True:
            rec = self.step()
            if rec.t1 - start >= seconds:
                return start, rec.t1


def window_numbers(loop: ClosedLoop, start: float, end: float) -> dict:
    """Tokens, gaps between tokens and resume latencies of the window."""
    tokens = 0
    gaps = []
    for s in loop.served.values():
        st = s.stamps
        tokens += sum(1 for t in st if t > start)
        for i in range(len(st) - 1):
            if st[i] >= start and i not in s.suspended_after:
                gaps.append(st[i + 1] - st[i])
    resumes = [rec.t1 - rec.t0 for rec in loop.steps
               if rec.t0 >= start and rec.resumed
               for _ in rec.resumed]
    compiles = sum(rec.compiles for rec in loop.steps if rec.t0 >= start)
    return {"span_s": end - start, "tokens": tokens, "gaps_s": gaps,
            "resumes_s": resumes, "compiles": compiles,
            "steps": sum(1 for rec in loop.steps if rec.t0 >= start),
            "ends": [rec.t1 for rec in loop.steps if rec.t0 >= start]}


def p95(xs: list[float]) -> float | None:
    """Nearest-rank 95th percentile: the smallest sample with at least
    95% of the samples at or below it."""
    if not xs:
        return None
    ys = sorted(xs)
    return ys[max(0, math.ceil(0.95 * len(ys)) - 1)]


def session_records(loop: ClosedLoop) -> list[dict]:
    """Prompt, served tokens and suspension lengths of every request that
    was served at least one token."""
    return [{"req_id": rid, "prompt": s.prompt, "tokens": list(s.tokens),
             "suspends": list(s.suspend_lengths)}
            for rid, s in sorted(loop.served.items()) if s.tokens]


def free(*arrays_or_trees) -> None:
    """Drop device buffers now rather than at the next collection."""
    for t in arrays_or_trees:
        for leaf in jax.tree.leaves(t):
            if isinstance(leaf, jnp.ndarray) and not leaf.is_deleted():
                leaf.delete()
