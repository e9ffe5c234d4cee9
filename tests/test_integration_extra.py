"""Extra integration coverage: grouped MoE dispatch, dependent_diag
training, lazy-K sweep, c<1 weak-unbiased training."""

import jax
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import StatelessLoader
from repro.models.moe import moe_ffn
from repro.optim import subspace
from repro.train.trainer import Trainer


def test_moe_grouped_dispatch_matches_ungrouped():
    """groups>1 must be a pure re-partitioning when capacity is ample."""
    key = jax.random.key(0)
    ks = jax.random.split(key, 5)
    B, S, d, E, f, k = 4, 16, 8, 4, 16, 2
    x = jax.random.normal(ks[0], (B, S, d))
    router = jax.random.normal(ks[1], (d, E)) * 0.1
    wg = jax.random.normal(ks[2], (E, d, f)) * 0.1
    wu = jax.random.normal(ks[3], (E, d, f)) * 0.1
    wd = jax.random.normal(ks[4], (E, f, d)) * 0.1
    y1, _ = moe_ffn(x, router, wg, wu, wd, top_k=k, capacity_factor=32.0,
                    groups=1)
    y4, _ = moe_ffn(x, router, wg, wu, wd, top_k=k, capacity_factor=32.0,
                    groups=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4),
                               rtol=2e-4, atol=2e-5)


def test_moe_reduced_arch_trains():
    cfg = get_config("qwen3-moe-30b-a3b").reduced()
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                       lazy_k=5, lr=2e-3, warmup_steps=0, total_steps=50,
                       min_dim_for_lowrank=32, weight_decay=0.0,
                       schedule="constant")
    loader = StatelessLoader("lm", seed=0, batch=4, seq_len=32,
                             vocab=cfg.vocab_size)
    rep = Trainer(cfg, tcfg, loader).run(12)
    assert np.isfinite(rep.losses).all()
    assert rep.losses[-1] < rep.losses[0]


def test_ssm_reduced_arch_trains():
    cfg = get_config("mamba2-780m").reduced()
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=16,
                       lazy_k=10, lr=5e-3, warmup_steps=0, total_steps=100,
                       min_dim_for_lowrank=32, weight_decay=0.0,
                       schedule="constant")
    loader = StatelessLoader("lm", seed=0, batch=8, seq_len=64,
                             vocab=cfg.vocab_size)
    rep = Trainer(cfg, tcfg, loader).run(50)
    assert np.isfinite(rep.losses).all()
    assert np.mean(rep.losses[-5:]) < np.mean(rep.losses[:5]) - 0.2


def test_dependent_diag_training_updates_energy():
    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="dependent_diag",
                       rank=8, lazy_k=4, lr=1e-3, warmup_steps=0,
                       total_steps=40, min_dim_for_lowrank=64,
                       weight_decay=0.0, schedule="constant")
    tr = Trainer(cfg, tcfg, StatelessLoader("lm", seed=0, batch=4,
                                            seq_len=32,
                                            vocab=cfg.vocab_size))
    rep = tr.run(10)
    assert np.isfinite(rep.losses).all()
    energies = [np.asarray(g.energy) for g in tr.opt_state.groups]
    assert any(e.size and e.sum() > 0 for e in energies), \
        "dependent_diag energy EMA never updated"


@pytest.mark.parametrize("lazy_k", [1, 3, 10])
def test_lazy_k_variants_train(lazy_k):
    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="coordinate",
                       rank=8, lazy_k=lazy_k, lr=2e-3, warmup_steps=0,
                       total_steps=40, min_dim_for_lowrank=64,
                       weight_decay=0.0, schedule="constant")
    rep = Trainer(cfg, tcfg, StatelessLoader(
        "lm", seed=0, batch=4, seq_len=32, vocab=cfg.vocab_size)).run(8)
    assert np.isfinite(rep.losses).all()


def test_weak_unbiased_c_half_trains():
    """c < 1 (weak unbiasedness): still a descent method (Remark 1)."""
    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=16,
                       c=0.5, lazy_k=10, lr=3e-3, warmup_steps=0,
                       total_steps=60, min_dim_for_lowrank=64,
                       weight_decay=0.0, schedule="constant")
    rep = Trainer(cfg, tcfg, StatelessLoader(
        "lm", seed=0, batch=8, seq_len=64, vocab=cfg.vocab_size)).run(30)
    assert rep.losses[-1] < rep.losses[0]


def test_encdec_trains():
    cfg = get_config("whisper-small").reduced()
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                       lazy_k=5, lr=2e-3, warmup_steps=0, total_steps=40,
                       min_dim_for_lowrank=32, weight_decay=0.0,
                       schedule="constant")
    loader = StatelessLoader("encdec", seed=0, batch=4,
                             enc_len=cfg.encoder_seq, dec_len=16,
                             d_model=cfg.d_model, vocab=cfg.vocab_size)
    rep = Trainer(cfg, tcfg, loader).run(10)
    assert np.isfinite(rep.losses).all()
    assert rep.losses[-1] < rep.losses[0]


def test_grad_accum_matches_single_step():
    """grad_accum=2 over the same global batch == single-step gradients."""
    import jax
    from repro.train import steps as steps_mod
    cfg = get_config("llama-tiny")
    base = dict(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                lazy_k=10, lr=1e-3, warmup_steps=0, total_steps=10,
                min_dim_for_lowrank=64, weight_decay=0.0,
                schedule="constant", grad_clip=0.0)
    t1 = TrainConfig(**base)
    t2 = TrainConfig(**{**base, "grad_accum": 2})
    from repro.models import lm
    params, state = subspace.init(lm.init_params(cfg, jax.random.key(0)),
                                  t1, jax.random.key(1))
    batch = StatelessLoader("lm", seed=0, batch=8, seq_len=32,
                            vocab=cfg.vocab_size)(0)
    s1 = jax.jit(steps_mod.make_train_step(cfg, t1))
    s2 = jax.jit(steps_mod.make_train_step(cfg, t2))
    p1, st1, m1 = s1(params, state, batch)
    p2, st2, m2 = s2(params, state, batch)
    assert np.isclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        if hasattr(a, "dtype") and a.dtype.kind == "f":
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-4, atol=1e-5)


def test_galore_baseline_trains():
    """The GaLore projected-gradient baseline (paper's related work)."""
    import jax
    from repro.optim import galore
    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=16,
                       lazy_k=25, lr=3e-3, warmup_steps=0, total_steps=100,
                       min_dim_for_lowrank=64, weight_decay=0.0,
                       schedule="constant")
    from repro.models import lm
    params, state = galore.init(lm.init_params(cfg, jax.random.key(0)),
                                tcfg, jax.random.key(1))
    loader = StatelessLoader("lm", seed=0, batch=8, seq_len=64,
                             vocab=cfg.vocab_size)
    step_refresh = jax.jit(lambda p, s, b: galore.make_train_step(
        cfg, tcfg)(p, s, b, True))
    step_plain = jax.jit(lambda p, s, b: galore.make_train_step(
        cfg, tcfg)(p, s, b, False))
    losses = []
    for i in range(30):
        fn = step_refresh if i % tcfg.lazy_k == 0 else step_plain
        params, state, m = fn(params, state, loader(i))
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2
