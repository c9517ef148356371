"""``PagedLM.decode_step``: the jitted dense layer math against the dense
ring-cache reference, how often it traces, and the KV slot writer against
eager per-slot writes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import PagedCacheConfig, PagedKVCache, PagedLM
from repro.serve import kvcache

CONFIGS = {
    # QKV bias; the tied variant reads the head off the embedding
    "qwen25-smoke-tied": lambda: get_config(
        "qwen2.5-3b", smoke=True).with_(tie_embeddings=True),
    "qwen25-smoke": lambda: get_config("qwen2.5-3b", smoke=True),
    "internlm2-smoke": lambda: get_config("internlm2-1.8b", smoke=True),
}


def _paged_lm(cfg, params, n_pages=32):
    cache = PagedKVCache(PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        page_size=8, n_pages=n_pages, max_pages_per_seq=8, dtype=cfg.dtype))
    return PagedLM(cfg, params, cache, use_kernel=False)


def _prefill(lm, prompts):
    sids = []
    for prompt in prompts:
        sid = lm.cache.new_sequence()
        lm.prefill(np.asarray(prompt, np.int32), sid)
        sids.append(sid)
    return sids


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_decode_step_logits_match_dense_reference(name):
    cfg = CONFIGS[name]()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    attn = params["blocks"]["attn"]
    for i, b in enumerate(("bq", "bk", "bv")):     # zeros at init
        if b in attn:
            attn[b] = (0.5 * jax.random.normal(jax.random.PRNGKey(10 + i),
                                               attn[b].shape)
                       ).astype(attn[b].dtype)
    prompts = np.random.default_rng(2).integers(2, cfg.vocab, size=(2, 11))
    lm = _paged_lm(cfg, params)
    sids = _prefill(lm, prompts)

    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompts)},
                                  s_max=prompts.shape[1] + 8)
    tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
    pos = np.full((2,), prompts.shape[1], np.int32)
    for _ in range(4):
        ref, cache = model.decode_step(params, cache, jnp.asarray(tok),
                                       jnp.asarray(pos))
        got = lm.decode_step(tok, sids, pos)
        assert got.shape == (2, cfg.vocab) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref, np.float32),
                                   atol=0.1, rtol=0)
        tok = np.asarray(jnp.argmax(ref, axis=-1), np.int32)
        pos = pos + 1


@pytest.mark.parametrize("name", ["qwen25-smoke-tied", "internlm2-smoke"])
def test_jitted_step_equals_the_same_ops_run_eagerly(name):
    """Only the dispatch changed: the jitted step rounds as eager ops do,
    so its logits equal those of its own body run op by op."""
    cfg = CONFIGS[name]().with_(n_layers=3)
    params = build_model(cfg).init(jax.random.PRNGKey(3))
    prompts = [[3, 4, 5, 6, 7], [8, 9, 10]]
    jitted, eager = _paged_lm(cfg, params), _paged_lm(cfg, params)
    sids_j, sids_e = _prefill(jitted, prompts), _prefill(eager, prompts)
    tok, pos = np.array([11, 12], np.int32), np.array([5, 3], np.int32)
    for step in range(3):
        got = np.asarray(jitted.decode_step(tok, sids_j, pos + step))
        with jax.disable_jit():
            want = np.asarray(eager.decode_step(tok, sids_e, pos + step))
        np.testing.assert_array_equal(got, want)
        tok = want.argmax(-1).astype(np.int32)
    assert jitted.cache.metrics.count["lm.dense_traces"] == 4
    # op by op, every body runs on every call: 3 steps x (1 + 2 x 3 + 1)
    assert eager.cache.metrics.count["lm.dense_traces"] == 3 * 8


@pytest.mark.parametrize("n_layers", [1, 3])
def test_dense_math_traces_once_per_batch_shape(n_layers):
    cfg = get_config("qwen2.5-3b", smoke=True).with_(n_layers=n_layers)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    lm = _paged_lm(cfg, params)
    count = lm.cache.metrics.count
    sids = _prefill(lm, [[3, 4, 5], [6, 7]])
    assert count["lm.dense_traces"] == 0          # prefill is not traced
    for step in range(3):
        lm.decode_step(np.array([8, 9], np.int32), sids,
                       np.array([3 + step, 2 + step], np.int32))
    # embedding, attention input, attention output, head: one trace each
    assert count["lm.dense_traces"] == 4
    lm.decode_step(np.array([10], np.int32), sids[:1],
                   np.array([6], np.int32))
    assert count["lm.dense_traces"] == 8
    assert count["lm.decode"] == 4


# ------------------------------------------------------------ slot writes
def _rows(bases, pages, rows):
    """The writer's K or V rows as (n_layers, N, Hkv*hd) on the host."""
    return np.asarray(rows).reshape(len(bases), len(pages), -1)


def _eager_writes(bump, k_pool, v_pool, bases, pages, offs, k, v):
    """The write path the scatter replaced: one eager, un-donated
    ``.at[row, off].set`` per slot and layer, at row ``base + page``;
    a row past the pool (a host slot) is skipped."""
    out = []
    for pool, rows in ((k_pool, k), (v_pool, v)):
        rows = _rows(bases, pages, rows)
        for li, base in enumerate(np.asarray(bases)):
            for n, (page, off) in enumerate(zip(np.asarray(pages),
                                                np.asarray(offs))):
                if base + page < pool.shape[0]:
                    pool = pool.at[base + page, off].set(
                        jnp.asarray(rows[li, n]).astype(pool.dtype))
        out.append(pool)
    return tuple(out)


@pytest.fixture
def writes(monkeypatch):
    """Every call of the slot writer, as (k rows, v rows) of shape
    (n_layers, N, Hkv*hd), in call order."""
    calls = []
    orig = kvcache._write_slots

    def spy(bump, k_pool, v_pool, bases, pages, offs, k, v):
        calls.append((_rows(bases, pages, k), _rows(bases, pages, v)))
        return orig(bump, k_pool, v_pool, bases, pages, offs, k, v)
    monkeypatch.setattr(kvcache, "_write_slots", spy)
    return calls


def _same_bits(a, b) -> bool:
    return np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_pools_equal_eager_per_slot_writes(name, writes):
    """Prefill, then decode steps across a page boundary: each pool holds,
    bit for bit, what eager ``.at[row, off].set`` writes of the same rows
    at the slots the tokens' positions map to, row li*P + page for
    layer li."""
    cfg = CONFIGS[name]()
    params = build_model(cfg).init(jax.random.PRNGKey(4))
    prompts = [[3, 4, 5, 6, 7], [8, 9, 10, 11, 12, 13, 14]]
    lm = _paged_lm(cfg, params)
    sids = _prefill(lm, prompts)
    tok, pos = np.array([15, 16], np.int32), np.array([5, 7], np.int32)
    for step in range(6):                          # lengths 11 and 13
        tok = np.asarray(lm.decode_step(tok, sids, pos + step)
                         ).argmax(-1).astype(np.int32)
    cache, pg, L, P = lm.cache, 8, cfg.n_layers, lm.cache.cfg.n_pages
    assert [len(cache.seqs[s].table) for s in sids] == [2, 2]
    assert cache.k_pool.shape == (L * P, pg, cfg.n_kv_heads * cfg.hd)

    def slot(sid, t):
        kind, page = cache.seqs[sid].table[t // pg]
        assert kind == "hbm"
        return page, t % pg

    # the writes in call order: one per prompt token (every layer), then
    # one per layer per decode step (every sequence)
    order = [(range(L), [(sid, t)]) for sid, prompt in zip(sids, prompts)
             for t in range(len(prompt))]
    order += [(range(li, li + 1), [(sid, len(prompt) + step)
                                   for sid, prompt in zip(sids, prompts)])
              for step in range(6) for li in range(L)]
    assert len(writes) == len(order)
    ref = {kv: jnp.zeros(cache.k_pool.shape, cfg.dtype) for kv in "kv"}
    for (layers, toks), rows in zip(order, writes):
        for kv, r in zip("kv", rows):
            for i, li in enumerate(layers):
                for n, (sid, t) in enumerate(toks):
                    page, off = slot(sid, t)
                    ref[kv] = ref[kv].at[li * P + page, off].set(
                        jnp.asarray(r[i, n]).astype(cfg.dtype))
    assert _same_bits(cache.k_pool, ref["k"])
    assert _same_bits(cache.v_pool, ref["v"])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_bypassed_slot_is_written_on_the_host(name, writes):
    """A full pool sends one sequence's new page to the host tier: its row
    is written through numpy, the other sequence's in HBM, and the
    counters say which.  Of the whole K and V arrays only the HBM slot's
    row in each layer changes: the host slot's sentinel page lands in no
    layer's rows (not page 0 of the next layer)."""
    cfg = CONFIGS[name]()
    params = build_model(cfg).init(jax.random.PRNGKey(5))
    lm = _paged_lm(cfg, params, n_pages=2)
    sids = _prefill(lm, [list(range(3, 11)), [11, 12, 13]])
    count, L = lm.cache.metrics.count, cfg.n_layers
    assert lm.cache.free_pages() == 0 and count["kv_slot_writes"] == 11
    n_calls = len(writes)
    before = {kv: np.array(getattr(lm.cache, kv + "_pool"))
              for kv in "kv"}
    lm.decode_step(np.array([14, 15], np.int32), sids,
                   np.array([8, 3], np.int32))
    assert count["bypass_pages"] == 1 and count["hybrid_attention"] == L
    assert count["kv_slot_writes"] == 11 + 2 * L
    assert count["kv_host_slot_writes"] == L
    kind, buf = lm.cache.seqs[sids[0]].table[1]
    assert kind == "host-fresh"
    page = lm.cache.seqs[sids[1]].table[0][1]
    want = {kv: a.copy() for kv, a in before.items()}
    for li, (k, v) in enumerate(writes[n_calls:]):
        np.testing.assert_array_equal(buf["k"][li, 0],
                                      k[0, 0].astype(np.float32))
        np.testing.assert_array_equal(buf["v"][li, 0],
                                      v[0, 0].astype(np.float32))
        assert not buf["k"][li, 1:].any()
        want["k"][li * 2 + page, 3] = k[0, 1]
        want["v"][li * 2 + page, 3] = v[0, 1]
    assert len(writes) == n_calls + L
    assert _same_bits(lm.cache.k_pool, want["k"])
    assert _same_bits(lm.cache.v_pool, want["v"])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_slot_writer_traces_once_per_shape(name):
    """Prefill's (L layers, 1 slot) and decode's (1 layer, B slots): one
    trace each, whatever the prompt lengths and the step count."""
    cfg = CONFIGS[name]()
    params = build_model(cfg).init(jax.random.PRNGKey(6))
    lm = _paged_lm(cfg, params)
    prompts = [[3] * 3, [4] * 9, [5] * 17, [6] * 9]
    sids = _prefill(lm, prompts)
    count = lm.cache.metrics.count
    assert count["kv.write_traces"] == 1
    tok = np.array([7, 8, 9, 10], np.int32)
    pos = np.array([len(p) for p in prompts], np.int32)
    for step in range(20):
        lm.decode_step(tok, sids, pos + step)
    assert count["kv.write_traces"] == 2
    assert count["kv_slot_writes"] == 38 + 20 * cfg.n_layers * 4
    assert count["kv_host_slot_writes"] == 0


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_decode_logits_equal_eager_per_slot_writes(name, monkeypatch):
    """The scatter changes only the dispatch: with the eager per-slot
    writes in its place, the step's logits and the pools are the same
    bits."""
    cfg = CONFIGS[name]()
    params = build_model(cfg).init(jax.random.PRNGKey(7))
    prompts = [[3, 4, 5, 6, 7, 8], [9, 10, 11]]
    tok, pos = np.array([12, 13], np.int32), np.array([6, 3], np.int32)

    def serve(lm):
        sids = _prefill(lm, prompts)
        t, out = tok, []
        for step in range(4):                      # across a page boundary
            lg = np.asarray(lm.decode_step(t, sids, pos + step))
            out.append(lg)
            t = lg.argmax(-1).astype(np.int32)
        return out

    scattered = _paged_lm(cfg, params)
    got = serve(scattered)
    eager = _paged_lm(cfg, params)
    with monkeypatch.context() as m:
        m.setattr(kvcache, "_write_slots", _eager_writes)
        want = serve(eager)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert _same_bits(scattered.cache.k_pool, eager.cache.k_pool)
    assert _same_bits(scattered.cache.v_pool, eager.cache.v_pool)
