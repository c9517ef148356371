"""Multi-device tests: run in a subprocess with 8 forced host devices
(XLA fixes the device count at first init, so the main test process — which
must see 1 device — cannot host these)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow      # each test spawns an 8-device subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    code = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n" +
            textwrap.dedent(body))
    # the child runs on virtual CPU devices only: it must never contend
    # for an accelerator that the parent process holds
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def test_train_step_on_mesh_matches_single_device():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.parallel import make_ctx, param_spec_tree, named, \\
        batch_spec_tree
    from repro.train.step import make_train_step

    cfg = get_config('internlm2-1.8b', smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=1e-3)
    opt_state = opt.init(params)
    r = np.random.default_rng(0)
    batch = {'tokens': jnp.asarray(r.integers(0, cfg.vocab, (8, 32)),
                                   jnp.int32),
             'targets': jnp.asarray(r.integers(0, cfg.vocab, (8, 32)),
                                    jnp.int32)}
    # single device reference
    step1 = jax.jit(make_train_step(model, opt))
    p1, o1, m1 = step1(params, opt_state, batch)

    mesh = jax.make_mesh((2, 4), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = make_ctx(mesh, 8)
    pspec = param_spec_tree(jax.eval_shape(lambda: params), mesh)
    pshard = named(pspec, mesh)
    bshard = named(batch_spec_tree(jax.eval_shape(lambda: batch), ctx), mesh)
    params_s = jax.device_put(params, pshard)
    opt_s = opt.init(params_s)
    step8 = jax.jit(make_train_step(model, opt, ctx),
                    in_shardings=(pshard, None, bshard))
    p8, o8, m8 = step8(params_s, opt_s, batch)
    d = abs(float(m1['loss']) - float(m8['loss']))
    assert d < 1e-2, (float(m1['loss']), float(m8['loss']))
    # params close after one step
    l1 = jax.tree.leaves(p1)[0]
    l8 = jax.tree.leaves(p8)[0]
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l8, np.float32), atol=3e-2)
    print('mesh-vs-single OK', float(m1['loss']), float(m8['loss']))
    """)


def test_int8_ring_allreduce_close_to_mean():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.collectives import compressed_allreduce_tree
    from repro.models.common import MeshCtx
    mesh = jax.make_mesh((8,), ('data',))
    ctx = MeshCtx(mesh=mesh, batch_axes=('data',), model_axis=None)
    r = np.random.default_rng(0)
    g = {'a': jnp.asarray(r.standard_normal((64, 64)), jnp.float32),
         'b': jnp.asarray(r.standard_normal((1000,)), jnp.float32)}
    out = jax.jit(lambda t: compressed_allreduce_tree(t, ctx))(g)
    # grads identical on all shards -> mean == input; int8 error bounded
    for k in g:
        err = np.abs(np.asarray(out[k]) - np.asarray(g[k])).max()
        amax = np.abs(np.asarray(g[k])).max()
        assert err <= amax / 127.0 * 8 + 1e-6, (k, err)
    print('ring int8 OK')
    """)


def test_decode_attention_seq_sharded_matches_ref():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.common import MeshCtx
    from repro.models.layers import decode_attention, chunked_attention
    mesh = jax.make_mesh((1, 8), ('data', 'model'))
    ctx = MeshCtx(mesh=mesh, batch_axes=('data',), model_axis='model')
    B, S, H, hd = 2, 64, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
    k_pos = jnp.arange(S)[None].repeat(B, 0)
    pos = jnp.full((B,), S - 1)
    msk = jnp.ones((B, S), bool)
    out = decode_attention(q, k, v, k_pos=k_pos, pos=pos, window=0,
                           kv_mask=msk, ctx=ctx, chunk=32,
                           dtype=jnp.float32)
    ref = chunked_attention(q, k, v, q_pos=pos[:, None], k_pos=k_pos,
                            causal=True, kv_mask=msk, chunk=32,
                            dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)
    print('seq-sharded decode OK')
    """)


def test_zero1_specs_divide_shapes():
    _run("""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel import param_spec_tree, zero_spec_tree
    mesh = jax.make_mesh((2, 4), ('data', 'model'))
    for arch in ('internlm2-1.8b', 'qwen3-moe-235b-a22b', 'xlstm-1.3b'):
        cfg = get_config(arch, smoke=True)
        model = build_model(cfg)
        shapes = model.param_shape()
        specs = param_spec_tree(shapes, mesh)
        zspecs = zero_spec_tree(specs, shapes, mesh)
        def check(path, leaf, spec):
            for ax, name in enumerate(spec):
                if name is None:
                    continue
                assert leaf.shape[ax] % mesh.shape[name] == 0, \\
                    (arch, path, leaf.shape, spec)
        jax.tree_util.tree_map_with_path(
            check, shapes, zspecs,
            is_leaf=lambda x: isinstance(x, P))
    print('zero1 specs OK')
    """)


def test_moe_zero3_expert_gather_matches_single_device():
    """ZeRO-3 expert weights (stored sharded over 'data', gathered per
    layer) must produce the same loss as the unsharded single-device path."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel import make_ctx, named, param_spec_tree, \\
        batch_spec_tree

    cfg = get_config('qwen3-moe-235b-a22b', smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    batch = {'tokens': jnp.asarray(r.integers(0, cfg.vocab, (8, 32)),
                                   jnp.int32),
             'targets': jnp.asarray(r.integers(0, cfg.vocab, (8, 32)),
                                    jnp.int32)}
    ref = float(model.loss(params, batch))

    # the model shards through GSPMD (with_sharding_constraint), so the
    # mesh's axes are Auto; jax.make_mesh defaults to Explicit axes
    mesh = jax.make_mesh((2, 4), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = make_ctx(mesh, 8)
    pspec = param_spec_tree(jax.eval_shape(lambda: params), mesh)
    # confirm the ZeRO-3 rule fired: expert F axis sharded over data
    wg_spec = pspec['blocks']['moe']['wg']
    assert 'data' in tuple(wg_spec), wg_spec
    pshard = named(pspec, mesh)
    p_s = jax.device_put(params, pshard)
    bshard = named(batch_spec_tree(jax.eval_shape(lambda: batch), ctx), mesh)
    b_s = jax.device_put(batch, bshard)
    got = float(jax.jit(lambda p, b: model.loss(p, b, ctx))(p_s, b_s))
    assert abs(got - ref) < 2e-2, (got, ref)
    print('moe zero3 OK', ref, got)
    """)


@pytest.mark.xfail(reason="psum accumulation-order noise marginally exceeds "
                   "the 3e-2 tol on CPU jax 0.4.37 (1/512 elements)",
                   strict=False)
def test_sharded_cache_decode_matches_single_device():
    """decode_update_and_attend with an S-sharded KV cache must emit the
    same logits as the unsharded decode."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel import cache_spec_tree, make_ctx, named, \\
        param_spec_tree

    cfg = get_config('internlm2-1.8b', smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    B, T = 2, 32
    prompt = jnp.asarray(r.integers(0, cfg.vocab, (B, T)), jnp.int32)
    logits, cache = model.prefill(params, {'tokens': prompt}, s_max=T + 8)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((B,), T, jnp.int32)
    ref, _ = model.decode_step(params, cache, tok, pos)

    mesh = jax.make_mesh((1, 8), ('data', 'model'))
    ctx = make_ctx(mesh, B)
    pshard = named(param_spec_tree(jax.eval_shape(lambda: params), mesh),
                   mesh)
    cshard = named(cache_spec_tree(jax.eval_shape(lambda: cache), ctx, mesh),
                   mesh)
    p_s = jax.device_put(params, pshard)
    c_s = jax.device_put(cache, cshard)
    got, new_c = jax.jit(
        lambda p, c, t, q: model.decode_step(p, c, t, q, ctx))(
        p_s, c_s, tok, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)
    # the new token landed in exactly one shard's slot
    kpos = np.asarray(new_c['pos'])
    assert (kpos[:, :, T] == T).all()
    print('sharded-cache decode OK')
    """)
