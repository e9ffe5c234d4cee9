"""Training launcher.

Runs the :class:`~repro.train.trainer.Trainer` on one device — the CPU
or one chip — on a reduced or paper config.  It builds no mesh: the
sharded lowering is proven by ``python -m repro.launch.dryrun`` and run on
four chips by ``python chip_smoke.py --four-chips``.  With
``REPRO_DISTRIBUTED`` set it calls ``jax.distributed.initialize()`` first
(env-driven multi-host start).

Examples:
  python -m repro.launch.train --arch llama-tiny --steps 200
  python -m repro.launch.train --arch llama-60m --optimizer lowrank_adam \
      --sampler stiefel --rank 128 --lazy-k 200 --steps 1000
  python -m repro.launch.train --arch qwen2-7b --reduced --steps 20
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama-tiny")
    p.add_argument("--reduced", action="store_true",
                   help="use the smoke-test reduction of the arch")
    p.add_argument("--optimizer", default="lowrank_adam",
                   help="any method registered in repro.methods "
                        "(adamw | lowrank_adam | lowrank_lr | galore | "
                        "...); unknown names error listing the registry")
    p.add_argument("--sampler", default="stiefel",
                   choices=["stiefel", "coordinate", "gaussian",
                            "dependent_diag"])
    p.add_argument("--rank", type=int, default=128)
    p.add_argument("--c", type=float, default=1.0)
    p.add_argument("--lazy-k", type=int, default=200)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--workdir", default="")
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--min-dim-lowrank", type=int, default=128)
    args = p.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if os.environ.get("REPRO_DISTRIBUTED"):  # multi-host entry (TPU pods)
        import jax
        jax.distributed.initialize()

    from repro.configs import TrainConfig, get_config
    from repro.data.synthetic import StatelessLoader
    from repro.train.trainer import Trainer

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(
        optimizer=args.optimizer, sampler=args.sampler, rank=args.rank,
        c=args.c, lazy_k=args.lazy_k, lr=args.lr,
        warmup_steps=min(100, args.steps // 10),
        total_steps=max(args.steps, 1), seed=args.seed,
        min_dim_for_lowrank=args.min_dim_lowrank)

    if cfg.is_encoder_decoder:
        loader = StatelessLoader(
            "encdec", seed=args.seed, batch=args.batch,
            enc_len=cfg.encoder_seq, dec_len=min(args.seq,
                                                 cfg.max_decode_len),
            d_model=cfg.d_model, vocab=cfg.vocab_size)
    else:
        loader = StatelessLoader("lm", seed=args.seed, batch=args.batch,
                                 seq_len=args.seq, vocab=cfg.vocab_size)

    tr = Trainer(cfg, tcfg, loader, workdir=args.workdir or None,
                 checkpoint_every=args.checkpoint_every)
    rep = tr.run(args.steps, log_every=args.log_every)
    print(json.dumps({
        "arch": cfg.name, "optimizer": args.optimizer,
        "sampler": args.sampler,
        "first_loss": rep.losses[0] if rep.losses else None,
        "last_loss": rep.losses[-1] if rep.losses else None,
        "steps": rep.steps_run, "resumed_from": rep.resumed_from,
        "stragglers": rep.straggler_events,
        "mean_step_ms": 1e3 * sum(rep.step_times) /
        max(len(rep.step_times), 1),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
