"""``PagedLM.decode_step``: the jitted dense layer math against the dense
ring-cache reference, and how often it traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import PagedCacheConfig, PagedKVCache, PagedLM

CONFIGS = {
    # QKV bias; the tied variant reads the head off the embedding
    "qwen25-smoke-tied": lambda: get_config(
        "qwen2.5-3b", smoke=True).with_(tie_embeddings=True),
    "qwen25-smoke": lambda: get_config("qwen2.5-3b", smoke=True),
    "internlm2-smoke": lambda: get_config("internlm2-1.8b", smoke=True),
}


def _paged_lm(cfg, params):
    cache = PagedKVCache(PagedCacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
        page_size=8, n_pages=32, max_pages_per_seq=8, dtype=cfg.dtype))
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
