"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --smoke --steps 50 --ckpt /tmp/ckpt.pool

On the CPU container this trains the reduced (smoke) config end-to-end with
the full production substrate: deterministic pipeline, Caiti-backed async
checkpointing, watchdog, restart-resume (run it twice with the same --ckpt
to see the resume).  On a TPU fleet the same entry point takes the full
config plus the production mesh (see launch/mesh.py and launch/dryrun.py
for the lowering contract).
"""
from __future__ import annotations

import argparse

import jax

from repro.ckpt import CheckpointEngine, make_blockstore
from repro.configs import ARCHS, get_config
from repro.data import SyntheticLM
from repro.launch.jax_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import AdamW
from repro.train.loop import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default=None, help="block-pool file path")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--ckpt-policy", default="caiti")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    opt = AdamW(lr=args.lr, total_steps=args.steps)
    source = SyntheticLM(cfg.vocab, args.seq, args.batch)

    ckpt = None
    if args.ckpt:
        store = make_blockstore(args.ckpt, policy=args.ckpt_policy,
                                capacity_bytes=2 << 30)
        ckpt = CheckpointEngine(store)

    trainer = Trainer(model, opt, source, ckpt=ckpt,
                      cfg=TrainConfig(total_steps=args.steps,
                                      ckpt_every=args.ckpt_every,
                                      accum=args.accum))
    out = trainer.run(jax.random.PRNGKey(0))
    print(f"[train] arch={args.arch} steps->{out['last_step']} "
          f"loss {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f} "
          f"stragglers={out['stragglers']}")
    if ckpt is not None:
        ckpt.close()


if __name__ == "__main__":
    main()
