"""Method registry (ISSUE-4 acceptance):

  * every registered paradigm smoke-trains through the Trainer on the
    tiny config (finite, decreasing-or-flat loss) and round-trips a
    checkpoint save -> resume bit-exactly;
  * cross-method resume is refused with a clear error (and old manifests
    without a method tag keep restoring);
  * unknown method / sampler names error listing the available set —
    no silent fallthrough anywhere (Trainer, cells, samplers);
  * ``galore`` via the Trainer (registry dispatch, traced SVD-refresh
    cond, one jitted step) is bit-exact with the standalone
    ``optim.galore.make_train_step`` two-variant path on the same grouped
    layout;
  * the dry-run lowers a train cell for every registered method through
    the method-provided pspecs.
"""
import jax
import numpy as np
import pytest

from repro import methods
from repro.configs import TrainConfig, get_config
from repro.data.synthetic import StatelessLoader
from repro.models import lm
from repro.optim import galore, subspace
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer

CFG = get_config("llama-tiny")

# per-method knobs that make 3 smoke steps meaningful on llama-tiny
_LR = {"adamw": 1e-3, "lowrank_adam": 3e-3, "galore": 1e-3,
       "lowrank_lr": 1e-4, "lowrank_lion": 3e-4}


def _tcfg(name, **kw):
    base = dict(optimizer=name, sampler="stiefel", rank=8, lazy_k=3,
                lr=_LR.get(name, 1e-3), warmup_steps=0, total_steps=100,
                min_dim_for_lowrank=64, weight_decay=0.0,
                schedule="constant", zo_sigma=1e-2, seed=0)
    base.update(kw)
    return TrainConfig(**base)


def _loader(batch=4, seq=32):
    return StatelessLoader("lm", seed=0, batch=batch, seq_len=seq,
                           vocab=CFG.vocab_size)


# ---------------------------------------------------------------------------
# Registry surface
# ---------------------------------------------------------------------------

def test_registry_lists_all_paradigms():
    assert {"adamw", "lowrank_adam", "lowrank_lr", "galore"} <= set(
        methods.available())
    for name in methods.available():
        m = methods.get(name)
        assert m.name == name and m.checkpoint_tag
        d = m.describe()
        assert d["family"] in ("bp", "zo") and d["gradient"]


def test_unknown_method_lists_available():
    with pytest.raises(ValueError, match=r"lowrank_adam.*lowrank_lr"):
        methods.get("sgd")
    # the Trainer surfaces the same listing (no ValueError(tcfg.optimizer))
    with pytest.raises(ValueError, match="galore"):
        Trainer(CFG, _tcfg("lowrank_adam", optimizer="nonsense"), _loader())


def test_unknown_sampler_lists_available():
    from repro.core import samplers
    with pytest.raises(ValueError, match=r"coordinate.*stiefel"):
        samplers.sample_v("bogus", jax.random.key(0), 8, 2)
    with pytest.raises(ValueError, match=r"coordinate.*stiefel"):
        samplers.sample_v_batched("bogus", jax.random.key(0), 2, 8, 2)


# ---------------------------------------------------------------------------
# Every registered method trains + checkpoints through the Trainer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(methods.available()))
def test_method_smoke_trains(name):
    tr = Trainer(CFG, _tcfg(name), _loader())
    rep = tr.run(3)
    assert np.isfinite(rep.losses).all()
    # decreasing-or-flat: 3 steps must not blow the loss up (ZO moves in
    # a random subspace, so allow estimator-level jitter around flat)
    assert rep.losses[-1] <= rep.losses[0] + 0.15, rep.losses
    # model_params always hands back the model-shaped tree
    assert set(tr.model_params) == set(lm.init_params(CFG,
                                                      jax.random.key(0)))


@pytest.mark.parametrize("name", sorted(methods.available()))
def test_method_checkpoint_resume_bitexact(name, tmp_path):
    wd = str(tmp_path / name)
    tcfg = _tcfg(name)
    Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=2).run(4)
    tr2 = Trainer(CFG, tcfg, _loader(), workdir=wd)
    rep2 = tr2.run(2)
    assert rep2.resumed_from == 4
    rep3 = Trainer(CFG, tcfg, _loader()).run(6)
    np.testing.assert_allclose(rep2.losses, rep3.losses[4:], rtol=1e-5)
    # manifest carries the method tag
    _, manifest = ckpt.restore_latest(
        wd, {"params": tr2.params, "opt": tr2.opt_state})
    assert manifest["extra"]["method"] == methods.get(name).checkpoint_tag


def test_cross_method_resume_rejected(tmp_path):
    wd = str(tmp_path / "xmethod")
    Trainer(CFG, _tcfg("lowrank_adam"), _loader(), workdir=wd,
            checkpoint_every=2).run(2)
    tr = Trainer(CFG, _tcfg("galore"), _loader(), workdir=wd)
    with pytest.raises(ValueError, match="cross-method resume"):
        tr.run(1)


def test_untagged_manifest_still_resumes(tmp_path):
    """Manifests predating the method tag (no extra.method) restore."""
    wd = str(tmp_path / "legacy")
    tcfg = _tcfg("lowrank_adam")
    tr = Trainer(CFG, tcfg, _loader())
    tr.run(2)
    # simulate a pre-Method checkpoint: same tree, no method in extra
    ckpt.save(wd, 2, {"params": tr.params, "opt": tr.opt_state},
              extra={"arch": CFG.name})
    tr2 = Trainer(CFG, tcfg, _loader(), workdir=wd)
    assert tr2.maybe_resume() == 2


# ---------------------------------------------------------------------------
# GaLore via the Trainer == the standalone step builder, bit for bit
# ---------------------------------------------------------------------------

def test_galore_trainer_matches_standalone_bitexact():
    """The registry path (one jitted step, SVD refresh as a traced
    ``step % lazy_k == 0`` cond) must be bit-identical to the standalone
    ``make_train_step`` path (two jitted variants, python-bool refresh)
    on the same grouped layout and key schedule."""
    tcfg = _tcfg("galore", weight_decay=0.01, lazy_k=3)
    loader = _loader()
    tr = Trainer(CFG, tcfg, loader)
    rep = tr.run(7)

    # standalone: identical key schedule to Trainer.__init__
    pkey, okey = jax.random.split(jax.random.key(tcfg.seed))
    gp, state = galore.init(lm.init_params(CFG, pkey), tcfg, okey)
    mk = galore.make_train_step(CFG, tcfg)
    step_refresh = jax.jit(lambda p, s, b: mk(p, s, b, True))
    step_plain = jax.jit(lambda p, s, b: mk(p, s, b, False))
    losses = []
    for i in range(7):
        fn = step_refresh if i % tcfg.lazy_k == 0 else step_plain
        gp, state, m = fn(gp, state, loader(i))
        losses.append(float(m["loss"]))

    assert rep.losses == losses
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(gp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
            jax.tree.leaves((tr.opt_state.dense, tr.opt_state.groups,
                             tr.opt_state.step)),
            jax.tree.leaves((state.dense, state.groups, state.step))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_galore_trains_loss_goes_down():
    tcfg = _tcfg("galore", rank=16, lazy_k=25, lr=3e-3)
    rep = Trainer(CFG, tcfg, _loader(batch=8, seq=64)).run(30)
    assert np.isfinite(rep.losses).all()
    assert np.mean(rep.losses[-5:]) < np.mean(rep.losses[:5]) - 0.2


# ---------------------------------------------------------------------------
# Dry-run cells lower for every registered method (method-provided pspecs)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(methods.available()))
def test_every_method_cell_lowers_on_host_mesh(name):
    from repro.configs import SHAPE_BY_NAME
    from repro.launch import cells
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import ctx

    mesh = make_host_mesh()
    try:
        step, args, sh, meta = cells.build_cell(
            get_config("llama-20m"), SHAPE_BY_NAME["train_4k"], mesh,
            optimizer=name)
        assert meta["method"] == name
        lowered = jax.jit(step, in_shardings=sh).lower(*args)
        assert lowered.as_text()  # lowering succeeded
    finally:
        ctx.set_mesh(None)


def test_unknown_method_cell_raises_not_falls_through():
    """build_cell must error listing the registry, not silently lower the
    lowrank_adam step for a name it does not know."""
    from repro.configs import SHAPE_BY_NAME
    from repro.launch import cells
    from repro.launch.mesh import make_host_mesh
    from repro.sharding import ctx

    mesh = make_host_mesh()
    try:
        with pytest.raises(ValueError, match="available"):
            cells.build_cell(get_config("llama-20m"),
                             SHAPE_BY_NAME["train_4k"], mesh,
                             optimizer="sgdm")
    finally:
        ctx.set_mesh(None)


# ---------------------------------------------------------------------------
# Method-init representations stay consistent with the subspace machinery
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["lowrank_adam", "lowrank_lr", "galore"])
def test_lowrank_family_init_is_grouped(name):
    tcfg = _tcfg(name)
    params = lm.init_params(CFG, jax.random.key(0))
    p, opt = methods.get(name).init(params, tcfg, jax.random.key(1))
    assert isinstance(p, subspace.GroupedParams)
    assert isinstance(opt, subspace.SubspaceState)
    assert opt.layout is p.layout
    if name == "galore":  # V starts zeroed: first refresh fills from SVD
        assert all(float(jax.numpy.abs(g.proj).max()) == 0.0
                   for g in opt.groups)


def test_adamw_init_keeps_model_tree():
    params = lm.init_params(CFG, jax.random.key(0))
    p, opt = methods.get("adamw").init(params, _tcfg("adamw"),
                                       jax.random.key(1))
    assert p is params
    assert jax.tree.structure(opt.m) == jax.tree.structure(params)
