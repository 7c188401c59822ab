"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs a REDUCED config end-to-end on local devices (on a real TPU slice
pass --full to use the assigned config with the production mesh).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_arch
from repro.data.synthetic import LMStream
from repro.train import optimizer as opt
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (TPU slice only)")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    spec = get_arch(args.arch)
    assert spec.family == "lm", "train.py drives LM archs; see examples/ for others"
    cfg = spec.model_cfg if args.full else spec.smoke_cfg

    from repro.models import transformer as T
    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=20, total_steps=args.steps)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    state = opt.adamw_init(params, ocfg)
    stream = LMStream(cfg.vocab, args.batch, args.seq, seed=0)

    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=10),
        T.make_train_step(cfg, ocfg), params, state, stream)
    if args.resume and trainer.maybe_resume():
        print(f"resumed from step {trainer.step}")
    out = trainer.run()
    print(f"done: final loss {out['final_loss']:.4f} "
          f"(start {out['history'][0]:.4f})")


if __name__ == "__main__":
    main()
