"""End-to-end training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --steps 50 --batch 8 --seq 256 --smoke

``--smoke`` runs the arch's reduced config on CPU; without it the full
config is used (intended for real TPU slices via the production mesh).
The loop is the fault-tolerant Trainer: step-indexed data, async atomic
checkpoints, straggler monitor, automatic restart.
"""

from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import make_train_step, _init_fn
from repro.optim import adamw, cosine_schedule
from repro.runtime import Trainer, TrainerConfig, FailureInjector


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--inject-failure-at", type=int, default=-1)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    init_fn = _init_fn(cfg)

    opt = adamw()
    train_step = jax.jit(make_train_step(cfg, opt, lr=args.lr))

    def init_state():
        params = init_fn(cfg, jax.random.PRNGKey(0))
        return dict(params=params, opt_state=opt.init(params))

    dataset = SyntheticLMDataset(
        cfg.vocab_size, args.seq, args.batch, family=cfg.family,
        d_model=cfg.d_model, n_frames=cfg.n_audio_frames,
        n_patches=cfg.n_patches)

    injector = (FailureInjector([args.inject_failure_at])
                if args.inject_failure_at >= 0 else None)
    trainer = Trainer(
        TrainerConfig(total_steps=args.steps, checkpoint_every=args.ckpt_every,
                      checkpoint_dir=args.ckpt_dir),
        train_step, init_state, dataset, failure_injector=injector)
    out = trainer.run()
    losses = [m["loss"] for m in out["metrics"]]
    print(f"first loss {losses[0]:.4f} -> last loss {losses[-1]:.4f} "
          f"({len(losses)} steps, {out['restarts']} restarts)")
    return out


if __name__ == "__main__":
    main()
