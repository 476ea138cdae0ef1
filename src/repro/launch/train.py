"""Training launcher.

On the CPU container this drives smoke-scale configs end-to-end (the
same code path the fault-tolerance tests use); on a real TPU slice the
same CLI runs the full assigned configs — the mesh, sharding rules, and
step function are identical, only the device count changes.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="internlm2-1.8b")
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced same-family config (CPU)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    p.add_argument("--ckpt-interval", type=int, default=50)
    p.add_argument("--log-interval", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--optimizer", default="adamw",
                   choices=("adamw", "adafactor", "sgd"))
    p.add_argument("--cim", default="float",
                   choices=("float", "ternary", "exact"))
    args = p.parse_args(argv)

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from repro import configs, optim
    from repro.core.cim_linear import CIMConfig
    from repro.data import DataConfig, entropy_floor
    from repro.models import registry
    from repro.train import Trainer, TrainerConfig

    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    model = registry.build(cfg)
    n = cfg.param_count()
    print(f"arch={cfg.name} family={cfg.family} params={n/1e6:.1f}M "
          f"devices={jax.device_count()}")

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed)
    print(f"data entropy floor ~= {entropy_floor(data_cfg):.3f} nats/token")

    lr = optim.warmup_cosine(args.lr, max(args.steps // 20, 5), args.steps)
    opt = {"adamw": optim.adamw, "adafactor": optim.adafactor,
           "sgd": optim.sgd}[args.optimizer](lr)
    cim = None if args.cim == "float" else CIMConfig(mode=args.cim)

    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_interval=args.ckpt_interval,
                         log_interval=args.log_interval,
                         microbatches=args.microbatches, seed=args.seed)
    trainer = Trainer(model, opt, data_cfg, tcfg, cim=cim)

    t0 = time.monotonic()
    state = trainer.run()
    dt = time.monotonic() - t0
    losses = [h["loss"] for h in trainer.history]
    tok_per_step = args.batch * args.seq
    print(json.dumps({
        "steps": int(state.step),
        "first_loss": round(losses[0], 4) if losses else None,
        "last_loss": round(sum(losses[-10:]) / max(len(losses[-10:]), 1), 4),
        "wall_s": round(dt, 1),
        "tokens_per_s": round(tok_per_step * len(losses) / max(dt, 1e-9)),
        "restarts": trainer.restarts,
    }))


if __name__ == "__main__":
    main()
