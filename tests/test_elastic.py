"""Elastic scaling: a checkpoint saved on mesh A restores onto mesh B
(different shape) with identical values — the restart-with-resize path of
a production fleet.  Runs in a subprocess with 8 forced host devices."""
import os
import subprocess
import sys
import textwrap

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


def test_checkpoint_resharded_across_meshes(tmp_path):
    pool = str(tmp_path / "pool.bin")
    _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.ckpt import CheckpointEngine, make_blockstore
    from repro.configs import get_config
    from repro.models import build_model
    from repro.parallel import make_ctx, named, param_spec_tree

    cfg = get_config('internlm2-1.8b', smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # save on a (2, 4) mesh
    mesh_a = jax.make_mesh((2, 4), ('data', 'model'))
    shard_a = named(param_spec_tree(jax.eval_shape(lambda: params), mesh_a),
                    mesh_a)
    p_a = jax.device_put(params, shard_a)
    store = make_blockstore({pool!r}, capacity_bytes=512 << 20)
    eng = CheckpointEngine(store)
    eng.save(0, p_a)
    eng.close()

    # restore onto a (4, 2) mesh — different TP degree
    mesh_b = jax.make_mesh((4, 2), ('data', 'model'))
    shard_b = named(param_spec_tree(jax.eval_shape(lambda: params), mesh_b),
                    mesh_b)
    store2 = make_blockstore({pool!r}, capacity_bytes=512 << 20)
    eng2 = CheckpointEngine(store2)
    p_b, step = eng2.restore(like=params, shardings=shard_b)
    eng2.close()
    assert step == 0

    # values identical, shardings follow mesh B
    for la, lb in zip(jax.tree.leaves(params), jax.tree.leaves(p_b)):
        np.testing.assert_array_equal(
            np.asarray(la, np.float32), np.asarray(lb, np.float32))
    leaf_b = jax.tree.leaves(p_b)[0]
    assert leaf_b.sharding.mesh.shape['model'] == 2
    print('elastic reshard OK')
    """)


def test_trainer_resumes_on_resized_mesh(tmp_path):
    """Train 3 steps on mesh (2,4), checkpoint, resume 2 steps on (4,2):
    losses must continue the single-mesh trajectory (data schedule is
    mesh-independent)."""
    pool = str(tmp_path / "pool2.bin")
    _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.ckpt import CheckpointEngine, make_blockstore
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.models import build_model
    from repro.optim import AdamW
    from repro.train.loop import TrainConfig, Trainer

    cfg = get_config('internlm2-1.8b', smoke=True)
    model = build_model(cfg)
    src = SyntheticLM(cfg.vocab, seq=32, global_batch=8)

    def mk_trainer(eng, steps):
        return Trainer(model, AdamW(lr=1e-3), src, ckpt=eng,
                       cfg=TrainConfig(total_steps=steps, ckpt_every=100,
                                       async_ckpt=False))

    # reference: 5 steps uninterrupted (single device)
    ref = mk_trainer(None, 5).run(jax.random.PRNGKey(0))

    store = make_blockstore({pool!r}, capacity_bytes=512 << 20)
    eng = CheckpointEngine(store)
    out1 = mk_trainer(eng, 3).run(jax.random.PRNGKey(0))
    assert out1['last_step'] == 2
    out2 = mk_trainer(eng, 5).run(jax.random.PRNGKey(0))
    assert out2['last_step'] == 4
    np.testing.assert_allclose(out2['losses'], ref['losses'][3:5],
                               rtol=1e-4, atol=1e-5)
    eng.close()
    print('resume-after-resize OK')
    """)
