"""Grouped MASTER WEIGHTS end-to-end (ISSUE-3 acceptance criteria).

  * the outer step on ``GroupedParams`` is a pure batched merge: its jaxpr
    contains ZERO concatenates over float leaves (no weight stack/unstack)
    and no gathers beyond the batched-QR sign fix;
  * the grouped training loop matches the per-leaf references
    (`inner_update_ref`, `outer_merge_resample_ref` on the model tree) for
    all four samplers over 3 outer cycles; LowRank-LR and GaLore steps
    match per-leaf estimates written out in the tests;
  * grouped weights checkpoint natively and round-trip; legacy per-leaf
    weight checkpoints migrate on restore (CRC-checked, drift-rejecting);
  * the Trainer carries GroupedParams through both jitted steps and
    resumes from both grouped and legacy checkpoints.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import TrainConfig
from repro.optim import subspace
from repro.train import checkpoint as ckpt

RNG = np.random.default_rng(23)

SAMPLERS = ["gaussian", "stiefel", "coordinate", "dependent_diag"]


def _tcfg(sampler="stiefel", **kw):
    base = dict(optimizer="lowrank_adam", sampler=sampler, rank=4, lazy_k=2,
                lr=1e-2, warmup_steps=0, total_steps=100,
                min_dim_for_lowrank=8, weight_decay=0.01, grad_clip=1.0,
                schedule="constant")
    base.update(kw)
    return TrainConfig(**base)


def _params():
    f = lambda *s: jnp.asarray(RNG.normal(size=s), jnp.float32)
    return {"w1": f(16, 12), "w2": f(16, 12), "w3": f(12, 10),
            "experts": f(3, 16, 12),          # stacked experts (E, k, n)
            "scan": f(2, 3, 16, 12),          # scan-stacked (L, E, k, n)
            "bias": f(12,)}


def _grads_like(trainable, seed):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda t: jnp.asarray(rng.normal(size=t.shape), t.dtype), trainable)


def _prims(closed_jaxpr):
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            out.append(eqn)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
    walk(closed_jaxpr.jaxpr)
    return out


def _assert_trees_equal(a, b, **tol):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if tol:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Layout: build once, slice lazily, ungroup only at the boundary
# ---------------------------------------------------------------------------

def test_group_params_roundtrip_and_idempotence():
    tcfg = _tcfg()
    params = _params()
    gp, state = subspace.init(params, tcfg, jax.random.key(0))
    assert isinstance(gp, subspace.GroupedParams)
    _assert_trees_equal(subspace.group_params(params, state.layout), gp)
    _assert_trees_equal(subspace.params_of(gp), params)
    assert subspace.params_of(params) is params           # raw passthrough
    # every group buffer is (G,) + member shape
    for spec, wg in zip(gp.layout.groups, gp.groups):
        assert wg.shape == (len(spec.leaf_idx),) + spec.shape


def test_packed_params_slices_grouped_weights():
    tcfg = _tcfg()
    params = _params()
    gp, state = subspace.init(params, tcfg, jax.random.key(0))
    trainable = subspace.trainable_of(gp, state)
    packed = subspace.packed_params(gp, state, trainable)
    for name in ("w1", "w2", "w3", "experts", "scan"):
        np.testing.assert_array_equal(np.asarray(packed[name].w),
                                      np.asarray(params[name]))
    assert not hasattr(packed["bias"], "w")  # dense leaf stays raw


# ---------------------------------------------------------------------------
# Jaxpr inspection: the grouped outer step never stacks/unstacks weights
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", SAMPLERS)
def test_grouped_outer_jaxpr_has_no_weight_stack_or_gather(sampler):
    """Acceptance: the jitted outer step on GroupedParams contains no
    per-leaf concatenate/gather on weight leaves: no concatenate whose
    operands are weight-shaped (a stack always concatenates ``(1,) + W``
    slices) and none of >= 3 dims at all — the only float concatenates
    allowed are the batched Madow sampler's 2-D probability-table
    bookkeeping (dependent_diag; stiefel has zero).  Gathers only from the
    batched QR sign-fix diagonal."""
    tcfg = _tcfg(sampler)
    gp, state = subspace.init(_params(), tcfg, jax.random.key(0))
    jaxpr = jax.make_jaxpr(
        lambda p, s: subspace.outer_merge_resample(p, s, tcfg))(gp, state)
    eqns = _prims(jaxpr)
    member_shapes = {spec.shape for spec in state.layout.groups}

    def weightish(shape):
        s = tuple(shape)
        return any(len(s) >= len(ms) and s[-len(ms):] == ms
                   for ms in member_shapes)

    for e in eqns:
        if e.primitive.name in ("concatenate", "gather", "scatter",
                                "dynamic_slice", "dynamic_update_slice"):
            shapes = [tuple(v.aval.shape) for v in e.invars] + \
                [tuple(v.aval.shape) for v in e.outvars]
            assert not any(weightish(s) for s in shapes), \
                f"per-leaf {e.primitive.name} on weight leaves in the " \
                f"grouped outer step: {shapes}"
    if sampler == "stiefel":
        # stronger: no float concatenate at all (uint32 = PRNG splits),
        # gathers only the batched QR sign-fix diagonal
        assert not any(e.primitive.name == "concatenate" and jnp.issubdtype(
            e.outvars[0].aval.dtype, jnp.floating) for e in eqns)
        gathers = [e for e in eqns if e.primitive.name == "gather"]
        for e in gathers:
            op = e.invars[0].aval.shape
            assert len(op) == 3 and op[-1] == op[-2], \
                f"unexpected gather over {op} in grouped outer step"
        # ONE sign-fix gather per group at most — never a per-leaf one
        assert len(gathers) <= len(state.layout.groups)


def test_grouped_inner_jaxpr_has_no_stack_or_gather(monkeypatch):
    """The inner step stays gather/concat-free with grouped weights too.

    Layout assertion, not a kernel-internal one: pin the XLA route (the
    Pallas pad-to-tile wrappers slice/pad inside the op by design)."""
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    tcfg = _tcfg("stiefel")
    gp, state = subspace.init(_params(), tcfg, jax.random.key(0))
    trainable = subspace.trainable_of(gp, state)
    grads = _grads_like(trainable, 1)
    jaxpr = jax.make_jaxpr(
        lambda g, t, p, s: subspace.inner_update(g, t, p, s, lr=1e-2,
                                                 tcfg=tcfg))(
        grads, trainable, gp, state)
    bad = [e.primitive.name for e in _prims(jaxpr)
           if e.primitive.name in ("concatenate", "gather", "scatter",
                                   "dynamic_slice", "dynamic_update_slice")]
    assert not bad, f"grouped inner step emits stack/gather work: {bad}"


# ---------------------------------------------------------------------------
# Equivalence: the grouped path == per-leaf references over 3 outer cycles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", SAMPLERS)
def test_grouped_loop_bitmatches_per_leaf_weights(sampler):
    """Full training loop (lazy_k inner steps + outer merge+resample, 3
    outer cycles): every grouped inner step matches ``inner_update_ref`` and
    every grouped outer merge's weights match ``outer_merge_resample_ref``,
    both run on the model tree ``params_of(...)`` from the same state (fp32
    tolerance: per-leaf kernel calls; the resampled V differs only by key
    schedule, so the loop carries on from the grouped result)."""
    tcfg = _tcfg(sampler)
    gp, state = subspace.init(_params(), tcfg, jax.random.key(0))
    for cycle in range(3):
        for it in range(tcfg.lazy_k):
            tr = subspace.trainable_of(gp, state)
            grads = _grads_like(tr, 100 * cycle + it)
            tree_r, tr_r, state_r, gn_r = subspace.inner_update_ref(
                grads, tr, subspace.params_of(gp), state, lr=1e-2,
                tcfg=tcfg)
            gp, tr, state, gn = subspace.inner_update(
                grads, tr, gp, state, lr=1e-2, tcfg=tcfg)
            np.testing.assert_allclose(float(gn), float(gn_r), rtol=1e-6)
            _assert_trees_equal(subspace.params_of(gp), tree_r,
                                rtol=1e-6, atol=1e-7)
            _assert_trees_equal((tr, state.dense, state.groups),
                                (tr_r, state_r.dense, state_r.groups),
                                rtol=1e-6, atol=1e-7)
        tree_r, _ = subspace.outer_merge_resample_ref(
            subspace.params_of(gp), state, tcfg)
        gp, state = subspace.outer_merge_resample(gp, state, tcfg)
        _assert_trees_equal(subspace.params_of(gp), tree_r,
                            rtol=1e-6, atol=1e-6)
        for g in state.groups:
            assert float(jnp.abs(g.b).max()) == 0.0
    assert int(state.outer_step) == 3


@pytest.mark.parametrize("sampler", SAMPLERS)
def test_grouped_matches_per_leaf_state_reference(sampler):
    """Against the per-leaf STATE reference impls: grouped inner ==
    inner_update_ref (fp32 tolerance: per-leaf kernel calls), and the
    grouped outer's merged weights == outer_merge_resample_ref's (the
    resampled V differs only by key schedule)."""
    tcfg = _tcfg(sampler)
    gp, state = subspace.init(_params(), tcfg, jax.random.key(0))
    trainable = subspace.trainable_of(gp, state)
    grads = _grads_like(trainable, 7)
    tree_r, _, state_r, _ = subspace.inner_update_ref(
        grads, trainable, subspace.params_of(gp), state, lr=1e-2, tcfg=tcfg)
    gp, _, state, _ = subspace.inner_update(
        grads, trainable, gp, state, lr=1e-2, tcfg=tcfg)
    _assert_trees_equal(subspace.params_of(gp), tree_r,
                        rtol=1e-6, atol=1e-7)
    _assert_trees_equal((state.dense, state.groups),
                        (state_r.dense, state_r.groups),
                        rtol=1e-6, atol=1e-7)
    gp2, _ = subspace.outer_merge_resample(gp, state, tcfg)
    tree2, _ = subspace.outer_merge_resample_ref(tree_r, state_r, tcfg)
    _assert_trees_equal(subspace.params_of(gp2), tree2,
                        rtol=1e-6, atol=1e-6)


def test_zo_step_grouped_matches_tree():
    """LowRank-LR: the grouped ZO step == a per-leaf antithetic two-point
    estimate from the same noise (``zo._sample_noise``), written out here
    leaf by leaf, passed through ``inner_update_ref``."""
    from repro.models.linear import LRPack, linear
    from repro.optim import zo
    tcfg = _tcfg("stiefel", optimizer="lowrank_lr")
    gp, state = subspace.init(_params(), tcfg, jax.random.key(0))

    def loss_fn(packed, batch):
        y = linear(batch, packed["w1"])
        return jnp.mean(y * y)

    batch = jnp.asarray(RNG.normal(size=(4, 16)), jnp.float32)
    key = jax.random.key(3)
    l_g, gp2, s_g, gn_g = zo.zo_inner_step(
        loss_fn, gp, state, batch, key, lr=1e-2, tcfg=tcfg)

    # per-leaf reference: W + V (B + s·σ·Z)ᵀ at every low-rank leaf,
    # W + s·σ·z at every dense leaf
    tree = subspace.params_of(gp)
    flat, treedef = jax.tree.flatten(tree)
    trainable = subspace.trainable_of(gp, state)
    noise = zo._sample_noise(state, key)
    sigma = tcfg.zo_sigma

    def perturbed(sign):
        out = list(flat)
        for di, i in enumerate(state.layout.dense_idx):
            out[i] = flat[i] + sign * sigma * noise.dense[di]
        for g, spec in enumerate(state.layout.groups):
            for j, i in enumerate(spec.leaf_idx):
                b = trainable.groups[g][j] + sign * sigma * noise.groups[g][j]
                out[i] = LRPack(flat[i], b, state.groups[g].proj[j])
        return jax.tree.unflatten(treedef, out)

    fp = loss_fn(perturbed(+1.0), batch)
    fm = loss_fn(perturbed(-1.0), batch)
    coeff = (fp - fm) / (2.0 * sigma)
    grads = jax.tree.map(lambda z: coeff * z, noise)
    tree_r, _, s_r, gn_r = subspace.inner_update_ref(
        grads, trainable, tree, state, lr=1e-2, tcfg=tcfg)
    np.testing.assert_allclose(float(l_g), float(0.5 * (fp + fm)),
                               rtol=1e-6)
    np.testing.assert_allclose(float(gn_g), float(gn_r), rtol=1e-6)
    _assert_trees_equal(subspace.params_of(gp2), tree_r,
                        rtol=1e-6, atol=1e-7)
    _assert_trees_equal((s_g.dense, s_g.groups), (s_r.dense, s_r.groups),
                        rtol=1e-6, atol=1e-7)


def _galore_ref(tree, grads, slots, step, *, lr, tcfg, refresh, layout):
    """Per-leaf GaLore step: R = Uᵀ G, Adam on R, W -= lr·(U Δ + wd·W);
    plain Adam on the dense leaves.  ``slots``: {leaf index: (U, m, v)}."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, tcfg.grad_clip / gn)
    flat, treedef = jax.tree.flatten(tree)
    flat_g = [g * scale for g in jax.tree.leaves(grads)]
    b1, b2, eps = tcfg.beta1, tcfg.beta2, tcfg.eps
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    out, new_slots = list(flat), {}
    for i, (w, g) in enumerate(zip(flat, flat_g)):
        u, m, v = slots[i]
        if i in layout.dense_idx:
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            out[i] = w - lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        else:
            if refresh:                       # top-r left singular vectors
                gram = jnp.einsum("...kn,...ln->...kl", g, g)
                u = jnp.linalg.eigh(gram)[1][..., -u.shape[-1]:]
            r = jnp.einsum("...kr,...kn->...nr", u, g)
            m = b1 * m + (1 - b1) * r
            v = b2 * v + (1 - b2) * r * r
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            out[i] = w - lr * (jnp.einsum("...kr,...nr->...kn", u, delta)
                               + tcfg.weight_decay * w)
        new_slots[i] = (u, m, v)
    return jax.tree.unflatten(treedef, out), new_slots


def test_galore_update_grouped_matches_tree():
    """GaLore's per-step weight write on stacked buffers == a per-leaf
    GaLore update written out here, for both refresh branches.  U is
    compared as the projector U Uᵀ and m as U mᵀ (an eigenvector's sign
    is free; the weight update does not depend on it)."""
    from repro.optim import galore
    tcfg = _tcfg("stiefel", weight_decay=0.01)
    tree = _params()
    gp, state = galore.init(tree, tcfg, jax.random.key(0))
    layout = state.layout
    rng = np.random.default_rng(5)
    flat_g = [jnp.asarray(rng.normal(size=x.shape), jnp.float32)
              for x in jax.tree.leaves(tree)]
    g_tree = jax.tree.unflatten(jax.tree.structure(tree), flat_g)
    g_gp = subspace.group_params(g_tree, layout)
    slots = {}
    for di, i in enumerate(layout.dense_idx):
        slots[i] = (None, state.dense[di].m, state.dense[di].v)
    for spec, slot in zip(layout.groups, state.groups):
        for j, i in enumerate(spec.leaf_idx):
            slots[i] = (slot.proj[j], slot.m[j], slot.v[j])
    for step, refresh in enumerate((True, False), start=1):
        gp, state = galore.update(g_gp, gp, state, lr=1e-2, tcfg=tcfg,
                                  refresh=refresh)
        tree, slots = _galore_ref(tree, g_tree, slots, step, lr=1e-2,
                                  tcfg=tcfg, refresh=refresh, layout=layout)
        # fp32 through two eigensolver calls on differently-formed grams
        tol = dict(rtol=1e-5, atol=1e-6)
        _assert_trees_equal(subspace.params_of(gp), tree, **tol)
        for di, i in enumerate(layout.dense_idx):
            _assert_trees_equal(state.dense[di], slots[i][1:], **tol)
        for spec, slot in zip(layout.groups, state.groups):
            for j, i in enumerate(spec.leaf_idx):
                u, m, v = slots[i]
                uu = lambda x: jnp.einsum("...kr,...lr->...kl", x, x)
                um = lambda x, y: jnp.einsum("...kr,...nr->...kn", x, y)
                _assert_trees_equal(uu(slot.proj[j]), uu(u), **tol)
                _assert_trees_equal(um(slot.proj[j], slot.m[j]), um(u, m),
                                    **tol)
                _assert_trees_equal(slot.v[j], v, **tol)


# ---------------------------------------------------------------------------
# Checkpointing: grouped round-trip + legacy per-leaf weight migration
# ---------------------------------------------------------------------------

def _state_arrays(state):
    return jax.tree.leaves((state.dense, state.groups, state.step,
                            state.outer_step))


@pytest.mark.parametrize("sampler", ["stiefel", "dependent_diag"])
def test_grouped_weights_checkpoint_roundtrip(tmp_path, sampler):
    tcfg = _tcfg(sampler)
    gp, state = subspace.init(_params(), tcfg, jax.random.key(0))
    trainable = subspace.trainable_of(gp, state)
    gp, _, state, _ = subspace.inner_update(
        _grads_like(trainable, 3), trainable, gp, state, lr=1e-2, tcfg=tcfg)
    wd = str(tmp_path / "gw")
    ckpt.save(wd, 7, {"params": gp, "opt": state})
    restored, manifest = ckpt.restore(wd, 7, {"params": gp, "opt": state})
    assert manifest["step"] == 7
    rp = restored["params"]
    assert isinstance(rp, subspace.GroupedParams)
    assert rp.layout == gp.layout and rp.treedef == gp.treedef
    _assert_trees_equal(rp, gp)
    for a, b in zip(_state_arrays(state), _state_arrays(restored["opt"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_legacy_per_leaf_weight_checkpoint_migrates(tmp_path):
    """A checkpoint that stored master weights one-record-per-leaf (the
    pre-grouped layout) restores into a GroupedParams template, re-stacked
    per group — and corruption in a legacy weight record is still caught
    through the migration."""
    tcfg = _tcfg("stiefel")
    tree = _params()
    gp, state = subspace.init(tree, tcfg, jax.random.key(0))
    wd = str(tmp_path / "legacy_w")
    ckpt.save(wd, 4, {"params": tree, "opt": state})   # legacy layout
    restored, manifest = ckpt.restore(wd, 4, {"params": gp, "opt": state})
    assert manifest["step"] == 4
    _assert_trees_equal(restored["params"], gp)
    # corruption in a legacy weight record is caught by the migration CRC
    import os
    path = os.path.join(wd, "step_00000004", "arrays.npz")
    data = dict(np.load(path))
    key = next(k for k in data if k.startswith("params") and "w1" in k)
    data[key] = data[key] + 1
    np.savez(path, **data)
    with pytest.raises(IOError):
        ckpt.restore(wd, 4, {"params": gp, "opt": state})


def test_legacy_weight_migration_rejects_layout_drift(tmp_path):
    """Restoring legacy per-leaf weights into a template whose model
    changed fails loudly instead of stacking the wrong arrays into a
    group: member-shape drift and leaf-count drift are both rejected by
    the migration itself (before any state record is even considered)."""
    tcfg = _tcfg("stiefel")
    tree = _params()
    _, state = subspace.init(tree, tcfg, jax.random.key(0))
    wd = str(tmp_path / "drift_w")
    ckpt.save(wd, 1, {"params": tree, "opt": state})
    # (a) same leaf count, different member shape -> shape check fires
    tree_w = dict(tree, w1=jnp.zeros((16, 11), jnp.float32))
    gp_w, state_w = subspace.init(tree_w, tcfg, jax.random.key(0))
    with pytest.raises(IOError, match="drift|expects"):
        ckpt.restore(wd, 1, {"params": gp_w, "opt": state_w})
    # (b) extra leaf -> leaf-count check fires
    tree_n = dict(tree, extra=jnp.zeros((4,), jnp.float32))
    gp_n, state_n = subspace.init(tree_n, tcfg, jax.random.key(0))
    with pytest.raises(IOError, match="weight leaves"):
        ckpt.restore(wd, 1, {"params": gp_n, "opt": state_n})
    # grouping-only drift (shapes intact) migrates the weights fine but the
    # STATE template still fails loudly -> no silent wrong-slot mapping
    d_tcfg = _tcfg("stiefel", min_dim_for_lowrank=11)  # w3 flips to dense
    gp_d, state_d = subspace.init(tree, d_tcfg, jax.random.key(0))
    assert gp_d.layout != state.layout
    with pytest.raises(IOError):
        ckpt.restore(wd, 1, {"params": gp_d, "opt": state_d})


# ---------------------------------------------------------------------------
# Trainer: GroupedParams is the canonical in-training representation
# ---------------------------------------------------------------------------

def _trainer_fixture(tmp_path, name, **kw):
    from repro.configs import get_config
    from repro.data.synthetic import StatelessLoader
    from repro.train.trainer import Trainer
    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                       lazy_k=3, lr=1e-3, warmup_steps=0, total_steps=100,
                       min_dim_for_lowrank=64, weight_decay=0.0,
                       schedule="constant")
    loader = StatelessLoader("lm", seed=0, batch=4, seq_len=32,
                             vocab=cfg.vocab_size)
    wd = str(tmp_path / name) if name else None
    return Trainer(cfg, tcfg, loader, workdir=wd, **kw), cfg, tcfg, loader


def test_trainer_holds_grouped_params_and_resumes(tmp_path):
    tr1, cfg, tcfg, loader = _trainer_fixture(tmp_path, "tr",
                                              checkpoint_every=4)
    assert isinstance(tr1.params, subspace.GroupedParams)
    tr1.run(4)
    # model_params ungroups at the API boundary (model-shaped tree)
    mp = tr1.model_params
    assert not isinstance(mp, subspace.GroupedParams)
    assert set(mp) == set(subspace.params_of(tr1.params))
    tr2, *_ = _trainer_fixture(tmp_path, "tr")
    assert tr2.maybe_resume() == 4
    assert isinstance(tr2.params, subspace.GroupedParams)
    _assert_trees_equal(tr2.params, tr1.params)
    for a, b in zip(_state_arrays(tr1.opt_state),
                    _state_arrays(tr2.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_resumes_legacy_ungrouped_weight_checkpoint(tmp_path):
    """A checkpoint written by the pre-grouped-weights Trainer (raw model
    tree + grouped state) resumes into today's grouped Trainer and
    continues bit-exactly with an uninterrupted run."""
    tr1, cfg, tcfg, loader = _trainer_fixture(tmp_path, "legacy_tr")
    tr1.run(4)
    # write the legacy layout by hand: ungrouped weights, same state
    ckpt.save(tr1.workdir, 4, {"params": subspace.params_of(tr1.params),
                               "opt": tr1.opt_state},
              extra={"arch": cfg.name})
    tr2, *_ = _trainer_fixture(tmp_path, "legacy_tr")
    assert tr2.maybe_resume() == 4
    _assert_trees_equal(tr2.params, tr1.params)
    rep2 = tr2.run(3)
    tr3, *_ = _trainer_fixture(tmp_path, "")
    rep3 = tr3.run(7)
    np.testing.assert_allclose(rep2.losses, rep3.losses[4:], rtol=1e-5)
