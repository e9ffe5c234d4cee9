"""Forward-only fine-tuning with LowRank-LR (the paper's Section 6.2.1
scenario): no backprop, no activation storage — two forward passes per step
with a rank-r Stiefel-projected perturbation.

Run:  PYTHONPATH=src python examples/finetune_lr.py
"""
import jax
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import classification_batch
from repro.models import encoder_cls
from repro.optim import subspace, zo
from repro.train.loss import cls_accuracy, cls_ce

N_CLASSES = 4
STEPS = 250

cfg = get_config("encoder-small").replace(num_layers=2, d_model=128,
                                          d_ff=256, vocab_size=512)
tcfg = TrainConfig(optimizer="lowrank_lr", sampler="stiefel", rank=4,
                   lazy_k=50, lr=2e-4, zo_sigma=1e-2, schedule="constant",
                   warmup_steps=0, total_steps=STEPS,
                   min_dim_for_lowrank=64, weight_decay=0.0)


def loss_fn(packed, batch):
    return cls_ce(encoder_cls.forward(packed, batch["tokens"], cfg),
                  batch["labels"])


# grouped master weights + grouped subspace state: the one training form
params, state = subspace.init(
    encoder_cls.init_params(cfg, N_CLASSES, jax.random.key(0)), tcfg,
    jax.random.key(1))


@jax.jit
def step(params, state, batch):
    key = jax.random.fold_in(state.key, state.step)
    loss, p, s, _ = zo.zo_inner_step(loss_fn, params, state, batch, key,
                                     lr=tcfg.lr, tcfg=tcfg)
    return p, s, loss


outer = jax.jit(lambda p, s: subspace.outer_merge_resample(p, s, tcfg))


def accuracy(params):
    params = subspace.params_of(params)     # the model-shaped tree
    accs = []
    for i in range(6):
        b = classification_batch(99, i, batch=32, seq_len=32,
                                 vocab=cfg.vocab_size, n_classes=N_CLASSES)
        accs.append(float(cls_accuracy(
            encoder_cls.forward(params, b["tokens"], cfg), b["labels"])))
    return float(np.mean(accs))


print(f"zero-shot accuracy: {accuracy(params):.3f}")
for i in range(STEPS):
    if i and i % tcfg.lazy_k == 0:
        params, state = outer(params, state)
    b = classification_batch(0, i, batch=16, seq_len=32,
                             vocab=cfg.vocab_size, n_classes=N_CLASSES)
    params, state, loss = step(params, state, b)
    if i % 50 == 0:
        print(f"step {i:4d} loss {float(loss):.4f}")
params, state = outer(params, state)
print(f"fine-tuned accuracy: {accuracy(params):.3f} "
      f"(forward-only training — no backprop was used)")
