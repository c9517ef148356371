"""Pallas kernel sweeps: every kernel, across shapes and dtypes, against
the pure-jnp oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import (flash_attention, gather_quantize_crc,
                           paged_attention, scatter_dequantize_crc)
from repro.kernels import ref
from repro.kernels.block_transit import (gather_quantize_crc_pallas,
                                         scatter_dequantize_crc_pallas)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,S,H,Hkv,hd", [
    (1, 128, 128, 2, 2, 64),       # MHA square
    (2, 256, 256, 4, 2, 64),       # GQA
    (1, 128, 384, 8, 1, 128),      # MQA, rectangular, wide head
    (2, 384, 128, 4, 4, 64),       # more Q than KV
])
def test_flash_attention_sweep(B, T, S, H, Hkv, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128)
    exp = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [32, 128, 500])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, T, H, hd = 1, 256, 2, 64
    q = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window)
    exp = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, T, S, H, hd = 2, 128, 256, 2, 64
    q = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=False)
    exp = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,hd,P,page,maxp", [
    (2, 4, 2, 64, 16, 16, 4),
    (3, 8, 1, 128, 12, 32, 3),     # MQA
    (1, 2, 2, 64, 4, 8, 2),
])
def test_paged_attention_sweep(B, H, Hkv, hd, P, page, maxp, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kp = jax.random.normal(ks[1], (P, page, Hkv * hd), dtype)
    vp = jax.random.normal(ks[2], (P, page, Hkv * hd), dtype)
    rng = np.random.default_rng(0)
    bt = jnp.asarray(rng.permutation(P)[:B * maxp].reshape(B, maxp),
                     jnp.int32)
    sl = jnp.asarray(rng.integers(1, page * maxp + 1, (B,)), jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl)
    exp = ref.paged_attention_ref(q, kp, vp, bt, sl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("layer", [0, 2])
def test_paged_attention_row_base_selects_a_layer(layer):
    """Three layers stacked as row blocks of P pages: with ``row_base``
    layer*P the table's pages read that layer's rows, in the kernel and
    in the ref, exactly as the layer's own pool read alone."""
    B, H, Hkv, hd, P, page, maxp = 2, 4, 2, 64, 6, 8, 3
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (3 * P, page, Hkv * hd), jnp.float32)
    vp = jax.random.normal(ks[2], (3 * P, page, Hkv * hd), jnp.float32)
    bt = jnp.asarray(np.random.default_rng(2).permutation(P)[:B * maxp]
                     .reshape(B, maxp), jnp.int32)
    sl = jnp.asarray([page * maxp, page + 3], jnp.int32)
    base = jnp.asarray([layer * P], jnp.int32)
    own = slice(layer * P, (layer + 1) * P)
    alone = ref.paged_attention_ref(q, kp[own], vp[own], bt, sl)
    np.testing.assert_array_equal(
        np.asarray(ref.paged_attention_ref(q, kp, vp, bt, sl, base)),
        np.asarray(alone))
    np.testing.assert_allclose(
        np.asarray(paged_attention(q, kp, vp, bt, sl, base)),
        np.asarray(alone), atol=2e-5, rtol=2e-5)


@settings(max_examples=15, deadline=None)
@given(seq_lens=st.lists(st.integers(1, 64), min_size=1, max_size=4))
def test_paged_attention_respects_lengths(seq_lens):
    """Property: tokens beyond seq_len never influence the output."""
    B = len(seq_lens)
    H, Hkv, hd, page = 2, 2, 64, 16
    maxp = 4
    P = B * maxp
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (P, page, Hkv * hd), jnp.float32)
    vp = jax.random.normal(ks[2], (P, page, Hkv * hd), jnp.float32)
    bt = jnp.arange(P, dtype=jnp.int32).reshape(B, maxp)
    sl = jnp.asarray(seq_lens, jnp.int32)
    out1 = paged_attention(q, kp, vp, bt, sl)
    # poison everything beyond each sequence's length; output must not move
    kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
    for b, L in enumerate(seq_lens):
        for pi in range(maxp):
            lo = pi * page
            for off in range(page):
                if lo + off >= L:
                    kp2[bt[b, pi], off] = 99.0
                    vp2[bt[b, pi], off] = -99.0
    out2 = paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2), bt, sl)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("P,page,F", [(8, 16, 128), (4, 32, 256),
                                      (16, 8, 384)])
def test_transit_codec_roundtrip(P, page, F):
    pool = jax.random.normal(jax.random.PRNGKey(5), (P, page, F),
                             jnp.float32)
    ids = jnp.asarray(np.random.default_rng(1).permutation(P)[:3], jnp.int32)
    q, sc, _ = gather_quantize_crc(pool, ids)
    qr, sr = ref.gather_quantize_ref(pool, ids)
    assert np.array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sr), rtol=1e-6)
    # roundtrip error bounded by one quantization step
    restored, _ = scatter_dequantize_crc(jnp.zeros_like(pool), ids, q, sc)
    orig = np.asarray(pool)[np.asarray(ids)]
    got = np.asarray(restored)[np.asarray(ids)]
    step = np.abs(orig).max(axis=-1, keepdims=True) / 127.0
    assert (np.abs(got - orig) <= step * 0.75 + 1e-7).all()


@settings(max_examples=12, deadline=None)
@given(shape=st.sampled_from([(6, 8, 64), (8, 16, 128), (4, 32, 96),
                              (12, 8, 256)]),
       seed=st.integers(0, 2**31 - 1),
       n_ids=st.integers(1, 4))
def test_fused_transit_crc_matches_three_pass_property(shape, seed, n_ids):
    """Property (satellite): the FUSED crc+quantize+gather kernel is
    bit-identical (q, crc) and allclose (scales, dequant) to the
    three-pass composition gather_quantize_ref -> transit_crc_ref ->
    scatter_dequantize_ref — in direct interpret=True mode AND through
    the jit-compiled public wrappers.  The crc oracle itself is pinned
    to ``zlib.adler32`` of the packed page bytes."""
    import zlib
    P, page, F = shape
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.standard_normal((P, page, F)), jnp.float32)
    ids = jnp.asarray(rng.permutation(P)[:min(n_ids, P)], jnp.int32)

    qr, sr = ref.gather_quantize_ref(pool, ids)          # pass 1+2
    crc_r = ref.transit_crc_ref(qr)                      # pass 3 (walk)
    for pi, crc in zip(np.asarray(qr), crc_r):           # oracle's oracle
        assert int(crc) == zlib.adler32(pi.tobytes())

    for q, sc, crc in (
            gather_quantize_crc_pallas(pool, ids, interpret=True),
            gather_quantize_crc(pool, ids)):             # jit-compiled
        assert np.array_equal(np.asarray(q), np.asarray(qr))
        np.testing.assert_allclose(np.asarray(sc), np.asarray(sr),
                                   rtol=1e-6)
        assert np.array_equal(np.asarray(crc), crc_r)    # bit-identical

    exp_pool = ref.scatter_dequantize_ref(jnp.zeros_like(pool), ids, qr, sr)
    for new_pool, crc in (
            scatter_dequantize_crc_pallas(jnp.zeros_like(pool), ids,
                                          qr, sr, interpret=True),
            scatter_dequantize_crc(jnp.zeros_like(pool), ids, qr, sr)):
        assert np.array_equal(np.asarray(crc), crc_r)    # verify-on-land
        np.testing.assert_allclose(np.asarray(new_pool),
                                   np.asarray(exp_pool),
                                   atol=1e-6, rtol=1e-6)
    # end-to-end roundtrip error bounded by one quantization step
    got = np.asarray(new_pool)[np.asarray(ids)]
    orig = np.asarray(pool)[np.asarray(ids)]
    step = np.abs(orig).max(axis=-1, keepdims=True) / 127.0
    assert (np.abs(got - orig) <= step * 0.75 + 1e-7).all()


def test_fused_crc_detects_payload_corruption():
    """Flipping ONE byte of a quantized page moves its crc — the
    property the kvcache restore path relies on to detect torn transit."""
    pool = jax.random.normal(jax.random.PRNGKey(9), (4, 16, 64),
                             jnp.float32)
    ids = jnp.asarray([1, 3], jnp.int32)
    q, sc, crc = gather_quantize_crc(pool, ids)
    qc = np.asarray(q).copy()
    qc[0, 3, 7] = qc[0, 3, 7] ^ 1
    _, crc2 = scatter_dequantize_crc(jnp.zeros_like(pool), ids,
                                     jnp.asarray(qc), sc)
    assert int(crc2[0]) != int(crc[0])        # corrupted page flagged
    assert int(crc2[1]) == int(crc[1])        # untouched page unchanged


def test_scatter_preserves_other_pages():
    pool = jax.random.normal(jax.random.PRNGKey(6), (8, 16, 128),
                             jnp.float32)
    ids = jnp.asarray([2, 5], jnp.int32)
    q, sc, _ = gather_quantize_crc(pool, ids)
    before = np.asarray(pool)
    out, _ = scatter_dequantize_crc(pool, ids, q, sc)     # donates pool
    for p in range(8):
        if p in (2, 5):
            continue
        np.testing.assert_array_equal(np.asarray(out[p]), before[p])


def test_flash_attention_grad_flows():
    """The kernel must be differentiable (used in training paths)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    B, T, H, hd = 1, 128, 2, 64
    q = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.float32)

    def loss(q):
        return flash_attention(q, k, v, causal=True).sum()

    g = jax.grad(loss)(q)
    assert bool(jnp.isfinite(g).all())
