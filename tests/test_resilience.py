"""Resilience chaos suite: non-finite guards, rollback, checkpoint
hardening — driven by the deterministic fault injection in
``repro.train.chaos``.

Covers the contract of the resilient training loop:
  * an injected NaN/inf gradient at an arbitrary step is SKIPPED — params,
    grouped masters and opt state bit-identical to pre-step — for every
    registered method;
  * N consecutive anomalies escalate: restore last good checkpoint, LR
    backoff, sampler-key reseed; the run then converges to within the
    documented tolerance of an uninjected run (10% relative for
    lowrank_adam, 15% for the noisier ZO path, over 3 outer cycles);
  * the guard is traced: no host callbacks / device->host transfer inside
    the jitted inner step (jaxpr-audited);
  * kill-during-save can never lose a restorable checkpoint: every
    injected crash/truncation point in ``save`` leaves ``restore_latest``
    an intact CRC-verified step, and damaged checkpoints are quarantined
    as ``step_*.corrupt``, never deleted;
  * SIGTERM drains the in-flight step, saves a manifest tagged
    ``extra.preempted``, and the previous signal handlers are restored.

Every test runs under a SIGALRM wall-clock guard so a hung rollback loop
fails fast instead of stalling the CI job.
"""
import dataclasses
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import methods
from repro.configs import TrainConfig, get_config
from repro.data.synthetic import StatelessLoader
from repro.models import lm
from repro.optim import subspace
from repro.train import chaos, health
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer

CFG = get_config("llama-tiny")
METHODS = list(methods.available())

TEST_TIMEOUT_S = 300  # per-test wall clock: hung rollback loops fail fast


def _tcfg(**kw):
    base = dict(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                lazy_k=5, lr=1e-3, warmup_steps=0, total_steps=100,
                min_dim_for_lowrank=64, weight_decay=0.0,
                schedule="constant", spike_warmup=1000)
    base.update(kw)
    return TrainConfig(**base)


def _loader(batch=4, seq=32):
    return StatelessLoader("lm", seed=0, batch=batch, seq_len=seq,
                           vocab=CFG.vocab_size)


def _snap(tree):
    """Host snapshot of every leaf (typed PRNG keys via key_data)."""
    out = []
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and jax.dtypes.issubdtype(
                leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        out.append(np.asarray(leaf))
    return out


@pytest.fixture(autouse=True)
def _timeout_and_chaos_hygiene():
    """SIGALRM per-test timeout + guaranteed chaos uninstall, so one
    test's fault schedule can never leak into the next."""
    def boom(signum, frame):
        raise TimeoutError(
            f"resilience test exceeded {TEST_TIMEOUT_S}s (hung rollback "
            f"loop?)")
    prev = signal.signal(signal.SIGALRM, boom)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)
        chaos.uninstall()


# ---------------------------------------------------------------------------
# Traced guard: skip-step semantics, per method
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", METHODS)
def test_injected_nan_is_skipped_bit_identically(name):
    """A NaN injected into the gradient estimate at step 1 must leave
    params AND opt state bit-identical to pre-step, then recover."""
    tcfg = _tcfg(optimizer=name)
    m = methods.get(name)
    params, opt = m.init(lm.init_params(CFG, jax.random.key(0)), tcfg,
                         jax.random.key(1))
    loader = _loader()
    with chaos.injected(chaos.ChaosHook(grad_nan_steps=(1,))):
        step = jax.jit(health.guard_inner_step(
            m.make_inner_step(CFG, tcfg), tcfg))
        h = health.init_health()
        params, opt, h, met = step(params, opt, h, loader(0))
        assert health.read_health(met).ok
        before = _snap((params, opt))
        p2, o2, h2, met2 = step(params, opt, h, loader(1))
        hr = health.read_health(met2)
        assert not hr.ok and hr.consec_skips == 1
        for a, b in zip(before, _snap((p2, o2))):
            np.testing.assert_array_equal(a, b)
        assert int(h2.total_skips) == 1 and int(h2.last_anomaly) == 1
        assert bool(health.tree_all_finite((p2, o2)))
        # the guard re-opens: the next step is accepted and updates state
        p3, o3, h3, met3 = step(p2, o2, h2, loader(2))
        assert health.read_health(met3).ok
        assert int(h3.consec_skips) == 0 and int(h3.total_skips) == 1


@pytest.mark.parametrize("name", METHODS)
def test_donated_state_holds_no_buffer_twice(name):
    """On an accelerator the Trainer donates (params, opt_state, health)
    to its jitted steps, and the runtime refuses a donating call that
    gets one buffer as two arguments.  CPU runs never donate, so only a
    chip would show it: check that every leaf owns its buffer, also after
    the rollback re-arm."""
    tr = Trainer(CFG, _tcfg(optimizer=name), _loader())
    for h in (tr.health, health.after_rollback(tr.health)):
        leaves = [x for x in jax.tree.leaves((tr.params, tr.opt_state, h))
                  if x.size and not jax.dtypes.issubdtype(
                      x.dtype, jax.dtypes.prng_key)]
        ptrs = [x.unsafe_buffer_pointer() for x in leaves]
        assert len(set(ptrs)) == len(ptrs)


def test_injected_inf_is_skipped_too():
    tcfg = _tcfg()
    m = methods.get("lowrank_adam")
    params, opt = m.init(lm.init_params(CFG, jax.random.key(0)), tcfg,
                         jax.random.key(1))
    with chaos.injected(chaos.ChaosHook(grad_nan_steps=(0,),
                                        grad_mode="inf")):
        step = jax.jit(health.guard_inner_step(
            m.make_inner_step(CFG, tcfg), tcfg))
        before = _snap((params, opt))
        p2, o2, h2, _ = step(params, opt, health.init_health(),
                             _loader()(0))
        for a, b in zip(before, _snap((p2, o2))):
            np.testing.assert_array_equal(a, b)
        assert int(h2.total_skips) == 1


def test_guard_is_transparent_when_healthy():
    """With no anomaly, the guarded step's outputs are bit-identical to
    the unguarded step's — the guard only ever selects, never perturbs."""
    tcfg = _tcfg()
    m = methods.get("lowrank_adam")
    params, opt = m.init(lm.init_params(CFG, jax.random.key(0)), tcfg,
                         jax.random.key(1))
    batch = _loader()(0)
    raw = jax.jit(m.make_inner_step(CFG, tcfg))
    guarded = jax.jit(health.guard_inner_step(
        m.make_inner_step(CFG, tcfg), tcfg))
    p_r, o_r, _ = raw(params, opt, batch)
    p_g, o_g, _, met = guarded(params, opt, health.init_health(), batch)
    assert health.read_health(met).ok
    # allclose, not bit-equal: raw and guarded are separately compiled XLA
    # programs, so fusion choices may differ at the ULP level
    for a, b in zip(_snap((p_r, o_r)), _snap((p_g, o_g))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_guard_jaxpr_free_of_host_callbacks():
    """The acceptance gate: the guard introduces no host callback / no
    device->host transfer primitive into the traced inner step."""
    tcfg = _tcfg()
    m = methods.get("lowrank_adam")
    params, opt = m.init(lm.init_params(CFG, jax.random.key(0)), tcfg,
                         jax.random.key(1))
    guarded = health.guard_inner_step(m.make_inner_step(CFG, tcfg), tcfg)
    health.assert_no_host_transfer(guarded, params, opt,
                                   health.init_health(), _loader()(0))


def test_spike_detector_skips_finite_outlier():
    """A finite 50x loss spike (no NaN anywhere) is still skipped by the
    EMA z-score detector once armed."""
    tcfg = _tcfg(spike_warmup=5, spike_zscore=4.0)
    with chaos.injected(chaos.ChaosHook(spike_scale_steps=(8,),
                                        spike_scale=50.0)):
        tr = Trainer(CFG, tcfg, _loader())
        rep = tr.run(12)
    assert rep.skipped_steps == 1
    assert rep.last_anomaly_step == 8
    assert rep.rollbacks == 0


# ---------------------------------------------------------------------------
# Escalation: rollback + LR backoff + reseed, per method
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", METHODS)
def test_consecutive_anomalies_rollback_backoff_reseed(tmp_path, name):
    tcfg = _tcfg(optimizer=name, max_consecutive_skips=2, max_rollbacks=3)
    wd = str(tmp_path / f"rb_{name}")
    # anomalies at guard steps 4,5,6 only: one rollback, then recovery
    with chaos.injected(chaos.ChaosHook(grad_nan_steps=(4, 5, 6))):
        tr = Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=2)
        has_key = hasattr(tr.opt_state, "key")
        key_before = (np.asarray(jax.random.key_data(tr.opt_state.key))
                      if has_key else None)
        rep = tr.run(12)
    assert rep.rollbacks == 1
    assert rep.skipped_steps >= 2
    assert not rep.health_exhausted
    assert tr.tcfg.lr == pytest.approx(tcfg.lr * tcfg.rollback_backoff)
    assert rep.lr_backoffs == [pytest.approx(tcfg.lr *
                                             tcfg.rollback_backoff)]
    if has_key:  # reseed: the offending draw's key stream is abandoned
        key_after = np.asarray(jax.random.key_data(tr.opt_state.key))
        assert not np.array_equal(key_before, key_after)
    assert rep.steps_run > 0 and np.isfinite(rep.losses[-1])
    # the manifest carries the anomaly history
    man_path = os.path.join(
        wd, f"step_{ckpt.latest_step(wd):08d}", "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    assert man["extra"]["health"]["rollbacks"] == 1
    assert man["extra"]["health"]["skips"] >= 2


def test_rollback_budget_exhausts_cleanly(tmp_path):
    """A persistent anomaly (every step poisoned) must stop the run after
    max_rollbacks with the last GOOD state — never spin forever (the
    SIGALRM fixture is the backstop) and never publish poisoned state."""
    tcfg = _tcfg(max_consecutive_skips=2, max_rollbacks=2)
    wd = str(tmp_path / "exhaust")
    with chaos.injected(chaos.ChaosHook(grad_nan_steps=tuple(range(2, 60)))):
        tr = Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=2)
        rep = tr.run(20)
    assert rep.health_exhausted
    assert rep.rollbacks == 2
    assert rep.steps_run < 20
    assert bool(health.tree_all_finite((tr.params, tr.opt_state)))
    # the final save is restorable and finite
    restored, man = ckpt.restore_latest(
        wd, {"params": tr.params, "opt": tr.opt_state})
    assert restored is not None
    assert bool(health.tree_all_finite(restored))


@pytest.mark.parametrize("name,tol", [("lowrank_adam", 0.10),
                                      ("lowrank_lr", 0.15)])
def test_injected_run_converges_close_to_clean(name, tol):
    """One injected NaN over 3 outer cycles: final loss within the
    documented tolerance of the uninjected run (10% lowrank_adam, 15%
    for the noisier forward-only ZO path)."""
    kw = dict(optimizer=name, lr=3e-3, rank=16, lazy_k=5)
    if name == "lowrank_lr":
        kw.update(lr=1e-4, zo_sigma=1e-2)
    tcfg = _tcfg(**kw)
    tr_clean = Trainer(CFG, tcfg, _loader())
    rep_clean = tr_clean.run(18)   # 3+ outer cycles at lazy_k=5
    with chaos.injected(chaos.ChaosHook(grad_nan_steps=(7,))):
        tr = Trainer(CFG, tcfg, _loader())
        rep = tr.run(18)
    assert rep.skipped_steps == 1
    clean = float(np.mean(rep_clean.losses[-3:]))
    injected = float(np.mean(rep.losses[-3:]))
    assert abs(injected - clean) <= tol * abs(clean), (injected, clean)


def test_guard_disabled_runs_legacy_path():
    tcfg = _tcfg(health_guard=False)
    tr = Trainer(CFG, tcfg, _loader())
    rep = tr.run(3)
    assert len(rep.losses) == 3 and np.all(np.isfinite(rep.losses))
    assert rep.skipped_steps == 0


# ---------------------------------------------------------------------------
# Checkpoint durability: kill-during-save, torn writes, quarantine
# ---------------------------------------------------------------------------

def _tree(seed=0):
    k = jax.random.key(seed)
    a, b = jax.random.split(k)
    return {"a": jax.random.normal(a, (64,), jnp.float32),
            "b": jax.random.normal(b, (16, 16), jnp.float32)}


@pytest.mark.parametrize("site", chaos.SAVE_SITES)
def test_kill_during_save_never_loses_restorable_checkpoint(tmp_path, site):
    """For EVERY labeled crash point in save: restore_latest succeeds on
    an intact CRC-verified step afterwards, and a subsequent clean save
    works (crashed tmp dirs are reaped, not accumulated)."""
    wd = str(tmp_path / "kill")
    t1, t2, t3 = _tree(1), _tree(2), _tree(3)
    ckpt.save(wd, 1, t1)
    with chaos.injected(chaos.ChaosHook(raise_in_save=site)):
        with pytest.raises(chaos.ChaosError):
            ckpt.save(wd, 2, t2)
    restored, man = ckpt.restore_latest(wd, t1)
    assert restored is not None
    # crash after the publish rename keeps step 2; before it, step 1
    want = {2: t2, 1: t1}[2 if site == "save:post_rename" else 1]
    assert man["step"] == (2 if site == "save:post_rename" else 1)
    for k in want:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(want[k]))
    ckpt.save(wd, 3, t3)
    assert ckpt.latest_step(wd) == 3
    assert not [n for n in os.listdir(wd) if n.endswith(".tmp")]


def test_torn_arrays_write_is_quarantined_not_fatal(tmp_path):
    """A save whose arrays.npz was torn mid-write (truncation chaos)
    publishes a damaged checkpoint; restore_latest must quarantine it and
    land on the previous intact step."""
    wd = str(tmp_path / "torn")
    t1, t2 = _tree(1), _tree(2)
    ckpt.save(wd, 1, t1)
    with chaos.injected(chaos.ChaosHook(truncate_npz_at=10)):
        ckpt.save(wd, 2, t2)
    restored, man = ckpt.restore_latest(wd, t1)
    assert man["step"] == 1
    assert os.path.isdir(os.path.join(wd, "step_00000002.corrupt"))
    assert ckpt.all_steps(wd) == [1]


@pytest.mark.parametrize("offset_frac", [0.0, 0.01, 0.33, 0.66, 0.999])
def test_truncation_sweep_lands_on_newest_intact(tmp_path, offset_frac):
    """Property-style: arrays.npz truncated at byte offsets spanning the
    file — restore_latest always lands on the newest intact step."""
    wd = str(tmp_path / f"tr{offset_frac}")
    t1, t2, t3 = _tree(1), _tree(2), _tree(3)
    for s, t in ((1, t1), (2, t2), (3, t3)):
        ckpt.save(wd, s, t)
    path = os.path.join(wd, "step_00000003", "arrays.npz")
    os.truncate(path, int(os.path.getsize(path) * offset_frac))
    restored, man = ckpt.restore_latest(wd, t1)
    assert man["step"] == 2
    for k in t2:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(t2[k]))
    assert os.path.isdir(os.path.join(wd, "step_00000003.corrupt"))


def test_single_bitflip_detected_and_walked_back(tmp_path):
    """Silent media corruption (one flipped bit in the npz) is caught by
    CRC verification and walked back, not restored."""
    wd = str(tmp_path / "flip")
    t1, t2 = _tree(1), _tree(2)
    ckpt.save(wd, 1, t1)
    ckpt.save(wd, 2, t2)
    path = os.path.join(wd, "step_00000002", "arrays.npz")
    chaos.flip_bit(path, os.path.getsize(path) // 2, bit=3)
    restored, man = ckpt.restore_latest(wd, t1)
    assert man["step"] == 1
    assert os.path.isdir(os.path.join(wd, "step_00000002.corrupt"))


def test_corrupt_crc_entry_walks_back(tmp_path):
    """A manifest whose CRC entry drifted from the arrays (either side
    damaged) must fail that step's restore and walk back."""
    wd = str(tmp_path / "crc")
    t1, t2 = _tree(1), _tree(2)
    ckpt.save(wd, 1, t1)
    ckpt.save(wd, 2, t2)
    man_path = os.path.join(wd, "step_00000002", "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    key = sorted(man["crc"])[0]
    man["crc"][key] ^= 0xDEADBEEF
    with open(man_path, "w") as f:
        json.dump(man, f)
    restored, got = ckpt.restore_latest(wd, t1)
    assert got["step"] == 1


def test_walkback_lands_on_legacy_migrated_checkpoint(tmp_path):
    """The walk-back must work for legacy-migrated checkpoints too: the
    newest (native grouped) step is corrupt, the older step stores
    per-leaf legacy weights — restore_latest migrates and succeeds."""
    tcfg = _tcfg()
    tree = {"w1": jax.random.normal(jax.random.key(0), (128, 128)),
            "w2": jax.random.normal(jax.random.key(1), (128, 128)),
            "bias": jnp.zeros((128,), jnp.float32)}
    gp, state = subspace.init(tree, tcfg, jax.random.key(2))
    wd = str(tmp_path / "legacy")
    ckpt.save(wd, 1, {"params": tree, "opt": state})   # legacy per-leaf
    ckpt.save(wd, 2, {"params": gp, "opt": state})     # native grouped
    path = os.path.join(wd, "step_00000002", "arrays.npz")
    os.truncate(path, os.path.getsize(path) // 2)
    restored, man = ckpt.restore_latest(wd, {"params": gp, "opt": state})
    assert man["step"] == 1
    assert isinstance(restored["params"], subspace.GroupedParams)
    for a, b in zip(_snap(restored["params"]), _snap(gp)):
        np.testing.assert_array_equal(a, b)


def test_all_corrupt_returns_fresh_start(tmp_path):
    wd = str(tmp_path / "allbad")
    t1 = _tree(1)
    for s in (1, 2):
        ckpt.save(wd, s, t1)
        p = os.path.join(wd, f"step_{s:08d}", "arrays.npz")
        os.truncate(p, 8)
    restored, man = ckpt.restore_latest(wd, t1)
    assert restored is None and man is None
    # quarantined, NOT deleted: the evidence survives
    assert sorted(n for n in os.listdir(wd) if n.endswith(".corrupt")) == \
        ["step_00000001.corrupt", "step_00000002.corrupt"]


def test_cross_method_refusal_still_raises_not_quarantines(tmp_path):
    """MethodMismatchError is a CONFIG error: restore_latest must raise,
    and must NOT quarantine the (perfectly valid) checkpoint."""
    wd = str(tmp_path / "xmethod")
    t1 = _tree(1)
    ckpt.save(wd, 1, t1, extra={"method": "lowrank_adam"})
    with pytest.raises(ckpt.MethodMismatchError):
        ckpt.restore_latest(wd, t1, expect_method="adamw")
    assert ckpt.all_steps(wd) == [1]   # untouched


def test_keep_zero_keeps_all(tmp_path):
    """keep=0 means keep ALL — the GC must never interpret it as
    'delete everything but zero'."""
    wd = str(tmp_path / "keep0")
    for s in range(5):
        ckpt.save(wd, s, _tree(s), keep=0)
    assert ckpt.all_steps(wd) == [0, 1, 2, 3, 4]


def test_all_steps_ignores_corrupt_and_tmp(tmp_path):
    wd = str(tmp_path / "ignore")
    ckpt.save(wd, 1, _tree(1))
    ckpt.save(wd, 2, _tree(2))
    ckpt.quarantine(wd, 2)
    stale = os.path.join(wd, "step_00000009.tmp")
    os.makedirs(stale)
    with open(os.path.join(stale, "manifest.json"), "w") as f:
        f.write("{}")
    assert ckpt.all_steps(wd) == [1]
    assert ckpt.latest_step(wd) == 1


def test_stale_tmp_dirs_reaped_on_restore(tmp_path):
    wd = str(tmp_path / "stale")
    ckpt.save(wd, 1, _tree(1))
    for name in ("step_00000007.tmp", "step_00000003.replaced.tmp"):
        os.makedirs(os.path.join(wd, name))
    restored, man = ckpt.restore_latest(wd, _tree(1))
    assert man["step"] == 1
    assert not [n for n in os.listdir(wd) if n.endswith(".tmp")]


def test_resave_same_step_crash_keeps_published(tmp_path):
    """Re-saving an already-published step and crashing before the rename
    must keep the ORIGINAL published checkpoint (the old code rmtree'd it
    first)."""
    wd = str(tmp_path / "resave")
    t1, t2 = _tree(1), _tree(2)
    ckpt.save(wd, 1, t1)
    with chaos.injected(chaos.ChaosHook(raise_in_save="save:pre_rename")):
        with pytest.raises(chaos.ChaosError):
            ckpt.save(wd, 1, t2)
    restored, man = ckpt.restore_latest(wd, t1)
    assert man["step"] == 1
    for k in t1:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(t1[k]))


# ---------------------------------------------------------------------------
# SIGTERM drain + handler hygiene + counter roundtrip
# ---------------------------------------------------------------------------

def test_sigterm_drains_saves_tagged_and_restores_handlers(tmp_path):
    seen = []

    def sentinel(signum, frame):
        seen.append(signum)
    prev = signal.signal(signal.SIGTERM, sentinel)
    try:
        wd = str(tmp_path / "pre")
        with chaos.injected(chaos.ChaosHook(sigterm_at_step=3)):
            tr = Trainer(CFG, _tcfg(), _loader(), workdir=wd)
            rep = tr.run(10)
        assert rep.preempted
        assert rep.steps_run == 4        # the in-flight step FINISHED
        assert ckpt.latest_step(wd) == 4
        man_path = os.path.join(wd, "step_00000004", "manifest.json")
        with open(man_path) as f:
            man = json.load(f)
        assert man["extra"]["preempted"] is True
        # teardown restored the sentinel — no handler leak into the host
        assert signal.getsignal(signal.SIGTERM) is sentinel
        assert not seen   # the Trainer's handler consumed the signal
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_health_counters_roundtrip_across_resume(tmp_path):
    tcfg = _tcfg(max_consecutive_skips=10)   # count skips, never escalate
    wd = str(tmp_path / "counters")
    with chaos.injected(chaos.ChaosHook(grad_nan_steps=(1, 3))):
        tr = Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=5)
        rep = tr.run(5)
    assert rep.skipped_steps == 2
    man_path = os.path.join(
        wd, f"step_{ckpt.latest_step(wd):08d}", "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    assert man["extra"]["health"]["skips"] == 2
    assert man["extra"]["health"]["rollbacks"] == 0
    # a resume carries the history into the report AND future manifests
    tr2 = Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=2)
    rep2 = tr2.run(2)
    assert rep2.resumed_health["skips"] == 2
    assert tr2._health_extra()["skips"] == 2


def test_chaos_env_spec_roundtrip():
    hook = chaos.from_env("nan@3,4 ; sigterm@9; truncate@128")
    assert hook.grad_nan_steps == (3, 4) and hook.grad_mode == "nan"
    assert hook.sigterm_at_step == 9 and hook.truncate_npz_at == 128
    assert chaos.from_env("") is None
    with pytest.raises(ValueError):
        chaos.from_env("frobnicate@2")
    with pytest.raises(ValueError):
        chaos.from_env("raise@save:nowhere")


def test_trainer_resumes_past_corrupt_newest(tmp_path):
    """End-to-end: the newest checkpoint is torn; a fresh Trainer resumes
    from the older intact one and keeps training."""
    tcfg = _tcfg()
    wd = str(tmp_path / "resume")
    tr1 = Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=2,
                  keep=0)
    tr1.run(6)   # checkpoints at 2, 4, 6
    path = os.path.join(wd, "step_00000006", "arrays.npz")
    os.truncate(path, os.path.getsize(path) // 3)
    tr2 = Trainer(CFG, tcfg, _loader(), workdir=wd)
    rep2 = tr2.run(2)
    assert rep2.resumed_from == 4
    assert np.all(np.isfinite(rep2.losses))
