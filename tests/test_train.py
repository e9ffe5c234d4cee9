"""Training substrate tests: optimizer identities, fault tolerance, data."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import StatelessLoader, lm_batch
from repro.models import lm
from repro.optim import subspace
from repro.train import checkpoint as ckpt
from repro.train import steps as steps_mod
from repro.train.trainer import Trainer

CFG = get_config("llama-tiny")
TCFG = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                   lazy_k=5, lr=1e-3, warmup_steps=0, total_steps=100,
                   min_dim_for_lowrank=64, weight_decay=0.0,
                   schedule="constant")


def _loader(batch=4, seq=32):
    return StatelessLoader("lm", seed=0, batch=batch, seq_len=seq,
                           vocab=CFG.vocab_size)


def test_subspace_grad_equals_projected_dense_grad():
    """dL/dB == (dL/dW)^T V per low-rank leaf — the Thm.-1 lift identity,
    verified through the full transformer + chunked-CE stack.  The grouped
    trainable's stacked gradient rows must each equal the member's lift."""
    params = lm.init_params(CFG, jax.random.key(0))
    gp, state = subspace.init(params, TCFG, jax.random.key(1))
    batch = _loader()(0)
    loss_fn = steps_mod.build_loss_fn(CFG)

    trainable = subspace.trainable_of(gp, state)

    def f_sub(t):
        return loss_fn(subspace.packed_params(gp, state, t), batch)

    grads_b = jax.grad(f_sub)(trainable)
    dense_grads = jax.grad(lambda p: loss_fn(p, batch))(params)
    flat_gd = jax.tree.leaves(dense_grads)

    checked = 0
    for g, spec in enumerate(state.layout.groups):
        proj = state.groups[g].proj
        for j, i in enumerate(spec.leaf_idx):
            want = jnp.einsum("...kn,...kr->...nr", flat_gd[i], proj[j])
            np.testing.assert_allclose(np.asarray(grads_b.groups[g][j]),
                                       np.asarray(want),
                                       rtol=2e-3, atol=2e-5)
            checked += 1
    assert checked >= 4  # attn + mlp + unembed leaves


def test_outer_merge_preserves_function():
    """Merging W += V B^T and zeroing B must not change the model output."""
    params, state = subspace.init(lm.init_params(CFG, jax.random.key(0)),
                                  TCFG, jax.random.key(1))
    # take a few inner steps so B != 0
    step = steps_mod.make_train_step(CFG, TCFG)
    batch = _loader()(0)
    for i in range(3):
        params, state, _ = step(params, state, _loader()(i))
    loss_fn = steps_mod.build_loss_fn(CFG)
    trainable = subspace.trainable_of(params, state)
    before = float(loss_fn(subspace.packed_params(params, state, trainable),
                           batch))
    outer = steps_mod.make_outer_step(CFG, TCFG)
    params2, state2 = outer(params, state)
    trainable2 = subspace.trainable_of(params2, state2)
    after = float(loss_fn(subspace.packed_params(params2, state2,
                                                 trainable2), batch))
    assert np.isclose(before, after, rtol=1e-4), (before, after)
    # and B is zeroed
    for slot in state2.groups:
        assert float(jnp.abs(slot.b).max()) == 0.0


def test_outer_resample_changes_projection():
    params, state = subspace.init(lm.init_params(CFG, jax.random.key(0)),
                                  TCFG, jax.random.key(1))
    outer = steps_mod.make_outer_step(CFG, TCFG)
    _, state2 = outer(params, state)
    diffs = [float(jnp.abs(a.proj - b.proj).max())
             for a, b in zip(state.groups, state2.groups)]
    assert all(d > 1e-3 for d in diffs)


def test_lowrank_memory_accounting():
    """Optimizer state shrinks by ~n/r for the low-rank leaves (Table 2)."""
    counts = subspace.lowrank_param_count(
        lm.init_params(CFG, jax.random.key(0)), TCFG)
    assert counts["adam_state_lowrank"] < 0.5 * counts["adam_state_full"]


def test_training_reduces_loss():
    import dataclasses
    tcfg = dataclasses.replace(TCFG, lr=3e-3, rank=16, lazy_k=10)
    tr = Trainer(CFG, tcfg, _loader())
    rep = tr.run(35)
    assert rep.losses[-1] < rep.losses[0] - 0.2


def test_zo_training_runs_and_is_finite():
    tcfg = TCFG._replace() if hasattr(TCFG, "_replace") else TCFG
    import dataclasses
    tcfg = dataclasses.replace(TCFG, optimizer="lowrank_lr", lr=1e-4,
                               zo_sigma=1e-2)
    tr = Trainer(CFG, tcfg, _loader())
    rep = tr.run(6)
    assert all(np.isfinite(rep.losses))


def test_checkpoint_resume_bitexact(tmp_path):
    wd = str(tmp_path / "ckpt")
    # run 8 steps with checkpoint every 4
    tr1 = Trainer(CFG, TCFG, _loader(), workdir=wd, checkpoint_every=4)
    tr1.run(8)
    # fresh trainer resumes from step 8 checkpoint and continues
    tr2 = Trainer(CFG, TCFG, _loader(), workdir=wd, checkpoint_every=0)
    rep2 = tr2.run(4)
    assert rep2.resumed_from == 8
    # reference: uninterrupted 12 steps
    tr3 = Trainer(CFG, TCFG, _loader())
    rep3 = tr3.run(12)
    np.testing.assert_allclose(rep2.losses, rep3.losses[8:], rtol=1e-5)


def test_checkpoint_integrity_detects_corruption(tmp_path):
    wd = str(tmp_path / "c2")
    tree = {"a": jnp.arange(8, dtype=jnp.float32)}
    ckpt.save(wd, 1, tree)
    # corrupt the array file
    import numpy as np_
    path = os.path.join(wd, "step_00000001", "arrays.npz")
    data = dict(np_.load(path))
    data["a"] = data["a"] + 1
    np_.savez(path, **data)
    with pytest.raises(IOError):
        ckpt.restore(wd, 1, tree)


def test_checkpoint_keep_k(tmp_path):
    wd = str(tmp_path / "c3")
    tree = {"a": jnp.zeros(4)}
    for s in range(6):
        ckpt.save(wd, s, tree, keep=2)
    assert ckpt.all_steps(wd) == [4, 5]


def test_preemption_checkpoints_and_stops(tmp_path):
    wd = str(tmp_path / "c4")
    tr = Trainer(CFG, TCFG, _loader(), workdir=wd)
    tr.request_preemption()
    rep = tr.run(10)
    assert rep.preempted and rep.steps_run == 1
    assert ckpt.latest_step(wd) == 1


def test_straggler_watchdog_fires():
    events = []
    tr = Trainer(CFG, TCFG, _loader(), straggler_factor=0.0,
                 on_straggler=lambda *a: events.append(a))
    tr.run(10)
    assert len(events) > 0  # factor 0 -> every step after warmup flags


def test_data_is_step_indexed_and_shardable():
    b1 = lm_batch(0, 7, batch=8, seq_len=16, vocab=100)
    b2 = lm_batch(0, 7, batch=8, seq_len=16, vocab=100)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    full = StatelessLoader("lm", seed=0, batch=8, seq_len=16, vocab=100)
    s0 = StatelessLoader("lm", seed=0, shard=0, num_shards=2, batch=8,
                         seq_len=16, vocab=100)
    s1 = StatelessLoader("lm", seed=0, shard=1, num_shards=2, batch=8,
                         seq_len=16, vocab=100)
    f, a, b = full(3), s0(3), s1(3)
    np.testing.assert_array_equal(
        np.asarray(f["tokens"]),
        np.concatenate([np.asarray(a["tokens"]), np.asarray(b["tokens"])]))


def test_elastic_restore_roundtrip(tmp_path):
    """Checkpoint saved unsharded restores onto a different 'mesh' width
    (simulated on CPU with single-device shardings)."""
    wd = str(tmp_path / "c5")
    params = lm.init_params(CFG, jax.random.key(0))
    ckpt.save(wd, 0, params)
    restored, _ = ckpt.restore(wd, 0, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
