"""Mixed-precision hot path (ISSUE-5 acceptance criteria).

  * bf16-compute training tracks the fp32 reference within the documented
    tolerance over 3 outer cycles for all four registered methods;
  * estimator-mean unbiasedness (E[V V^T] = c I) is preserved under bf16
    projection draws;
  * masters and moments never silently downcast: the jitted inner/outer
    steps' jaxpr output avals keep B/m/v and the grouped master weights at
    fp32 while the packed compute views really are bf16;
  * the kernel cache compiles each (op, padded shape, dtypes) key exactly
    once across a 3-outer-cycle run with ragged groups (retrace count);
  * rank packing: small-r subspace-Adam launches are lane-aligned and
    bit-identical to the unpacked XLA route;
  * the dispatch VMEM guard sizes operands with their real dtypes (the
    fp32-itemsize-hardcode bugfix): a bf16 backward stays on Pallas where
    the same-shape fp32 one falls back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import methods
from repro.configs import TrainConfig, get_config
from repro.core import samplers
from repro.data.synthetic import StatelessLoader
from repro.kernels import dispatch
from repro.models.linear import LRPack, linear
from repro.optim import subspace
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer

RNG = np.random.default_rng(23)

CFG = get_config("llama-tiny")

# Documented bf16 tolerance: relative deviation of the training loss from
# the fp32 reference after 3 outer cycles.  bf16 carries ~3 significant
# decimal digits; with fp32 masters/moments/accumulators the divergence is
# rounding-noise-driven, not compounding, so 6% is conservative.
BF16_LOSS_RTOL = 0.06

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
# bf16 training == fp32 reference within tolerance, all four methods
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(methods.available()))
def test_bf16_training_tracks_f32_reference(name, monkeypatch):
    # the env override must not pin both runs to one dtype
    monkeypatch.delenv("REPRO_COMPUTE_DTYPE", raising=False)
    losses = {}
    for dtype in ("float32", "bfloat16"):
        tr = Trainer(CFG, _tcfg(name, compute_dtype=dtype), _loader())
        rep = tr.run(10)            # > 3 outer cycles at lazy_k=3
        assert np.isfinite(rep.losses).all()
        losses[dtype] = rep.losses
    f32, bf16 = np.asarray(losses["float32"]), np.asarray(losses["bfloat16"])
    np.testing.assert_allclose(bf16, f32, rtol=BF16_LOSS_RTOL)


def test_bf16_state_dtypes(monkeypatch):
    """bf16 runs store V (and GaLore's U) reduced; B/m/v stay fp32."""
    from repro.models import lm

    monkeypatch.delenv("REPRO_COMPUTE_DTYPE", raising=False)
    tcfg = _tcfg("lowrank_adam", compute_dtype="bfloat16")
    gp, state = methods.get("lowrank_adam").init(
        lm.init_params(CFG, jax.random.key(0)), tcfg, jax.random.key(1))
    assert state.layout.compute_dtype == "bfloat16"
    for slot in state.groups:
        assert slot.proj.dtype == jnp.bfloat16
        for a in (slot.b, slot.m, slot.v, slot.energy):
            assert a.dtype == jnp.float32
    for g in gp.groups:          # master weights keep their stored dtype
        assert g.dtype == jnp.float32


# ---------------------------------------------------------------------------
# Unbiasedness under bf16 draws
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sampler", ["gaussian", "stiefel", "coordinate"])
def test_estimator_mean_unbiased_under_bf16_draws(sampler):
    n, r, batch, c = 16, 4, 4096, 1.0
    key = jax.random.key(7)
    v16 = samplers.sample_v_batched(sampler, key, batch, n, r, c=c,
                                    dtype=jnp.bfloat16)
    assert v16.dtype == jnp.bfloat16
    mean16 = np.asarray(
        jnp.mean(jnp.einsum("bnr,bmr->bnm", v16.astype(jnp.float32),
                            v16.astype(jnp.float32)), axis=0))
    # E[V V^T] = c I survives the bf16 cast (draws are fp32, cast once)
    np.testing.assert_allclose(mean16, c * np.eye(n), atol=0.12)
    # and the cast itself moves the estimator mean only by rounding noise
    v32 = samplers.sample_v_batched(sampler, key, batch, n, r, c=c,
                                    dtype=jnp.float32)
    mean32 = np.asarray(
        jnp.mean(jnp.einsum("bnr,bmr->bnm", v32, v32), axis=0))
    np.testing.assert_allclose(mean16, mean32, atol=0.02)


# ---------------------------------------------------------------------------
# Masters / moments never silently downcast (jaxpr output avals)
# ---------------------------------------------------------------------------

def test_masters_and_moments_never_downcast_in_jaxpr(monkeypatch):
    monkeypatch.delenv("REPRO_COMPUTE_DTYPE", raising=False)
    from repro.models import lm

    tcfg = _tcfg("lowrank_adam", compute_dtype="bfloat16")
    method = methods.get("lowrank_adam")
    gp, state = method.init(lm.init_params(CFG, jax.random.key(0)), tcfg,
                            jax.random.key(1))
    batch = _loader()(0)
    inner = method.make_inner_step(CFG, tcfg)
    outer = method.make_outer_step(CFG, tcfg)

    # jaxpr-level: the traced steps' OUTPUT avals (what gets written back
    # to HBM) keep every master/moment fp32 — a silent downcast anywhere
    # in the chain would surface as a reduced-dtype output aval here.
    new_p, new_s, _ = jax.eval_shape(inner, gp, state, batch)
    op, os_ = jax.eval_shape(outer, gp, state)
    for params_out, state_out in ((new_p, new_s), (op, os_)):
        for g in params_out.groups:
            assert g.dtype == jnp.float32, "master weights downcast"
        for slot in state_out.groups:
            for a in (slot.b, slot.m, slot.v):
                assert a.dtype == jnp.float32, "B master / moments downcast"
            assert slot.proj.dtype == jnp.bfloat16
        for d in state_out.dense:
            assert d.m.dtype == d.v.dtype == jnp.float32

    # ...while the packed compute views really are bf16 (the cast boundary
    # exists where intended: read side only)
    trainable = subspace.trainable_of(gp, state)
    packed = jax.eval_shape(
        lambda t: subspace.packed_params(gp, state, t, dtype=jnp.bfloat16),
        trainable)
    packs = [x for x in jax.tree.leaves(
        packed, is_leaf=lambda x: isinstance(x, LRPack))
        if isinstance(x, LRPack)]
    assert packs
    for pk in packs:
        assert pk.w.dtype == pk.b.dtype == pk.v.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Kernel cache: one compile per (op, padded shape, dtypes) key
# ---------------------------------------------------------------------------

def _ragged_params():
    f = lambda *s: jnp.asarray(RNG.normal(size=s) * 0.1, jnp.float32)
    return {"w1": f(36, 20), "w2": f(36, 20), "w3": f(52, 28),
            "bias": f(20,)}


def _ragged_tcfg(**kw):
    return _tcfg("lowrank_adam", rank=5, lazy_k=2, min_dim_for_lowrank=8,
                 **kw)


def test_kernel_cache_one_compile_per_key_over_3_cycles(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    monkeypatch.delenv("REPRO_COMPUTE_DTYPE", raising=False)
    tcfg = _ragged_tcfg()
    params = _ragged_params()
    gp, state = subspace.init_grouped(params, tcfg, jax.random.key(0))
    x1 = jnp.asarray(RNG.normal(size=(7, 36)), jnp.float32)
    x2 = jnp.asarray(RNG.normal(size=(7, 52)), jnp.float32)

    def loss_fn(packed, batch):
        y = linear(batch["x1"], packed["w1"]) + \
            linear(batch["x1"], packed["w2"]) + packed["bias"]
        y2 = linear(batch["x2"], packed["w3"])
        return 1e-3 * (jnp.sum(y * y) + jnp.sum(y2 * y2))

    def inner(p, s, batch):
        t = subspace.trainable_of(p, s)

        def f(t_, b):
            return loss_fn(subspace.packed_params(p, s, t_), b)

        loss, grads = jax.value_and_grad(f)(t, batch)
        p2, _, s2, _ = subspace.inner_update(grads, t, p, s, lr=1e-3,
                                             tcfg=tcfg)
        return p2, s2, loss

    inner_j = jax.jit(inner)
    outer_j = jax.jit(
        lambda p, s: subspace.outer_merge_resample(p, s, tcfg))
    batch = {"x1": x1, "x2": x2}

    dispatch.clear_kernel_cache()
    for _ in range(tcfg.lazy_k):
        gp, state, _ = inner_j(gp, state, batch)
    gp, state = outer_j(gp, state)
    info1 = dispatch.kernel_cache_info()
    # every key built exactly once (ragged shapes pad to shared tiles)
    assert info1["misses"] == len(info1["keys"]) > 0
    ops_seen = {k[0] for k in info1["keys"]}
    assert {"lowrank_forward", "lowrank_backward", "subspace_adam",
            "lowrank_merge"} <= ops_seen
    # cycles 2 and 3: ZERO new compiles — the jitted steps are traced, and
    # even a forced retrace would hit the cache
    for _ in range(2):
        for _ in range(tcfg.lazy_k):
            gp, state, _ = inner_j(gp, state, batch)
        gp, state = outer_j(gp, state)
    info3 = dispatch.kernel_cache_info()
    assert info3["misses"] == info1["misses"], \
        f"kernel retrace churn: {set(info3['keys']) - set(info1['keys'])}"
    # a fresh trace of the same shapes/dtypes only produces cache hits
    # (new wrapper object => jax cannot reuse the cached jaxpr)
    jax.jit(lambda p, s, b: inner(p, s, b)).lower(gp, state, batch)
    info4 = dispatch.kernel_cache_info()
    assert info4["misses"] == info3["misses"]
    assert info4["hits"] > info3["hits"]


# ---------------------------------------------------------------------------
# Rank packing: lane-aligned small-r Adam, bit-compatible with XLA route
# ---------------------------------------------------------------------------

def test_rank_pack_plan_is_lane_aligned():
    for r in (1, 3, 5, 8, 17, 100):
        plan = dispatch.rank_pack_plan(999, r)
        assert plan.slots * plan.r_pad == dispatch.LANE
        assert plan.rows_pad % plan.slots == 0
        assert plan.r_pad >= r
    # r >= LANE: no packing
    assert dispatch.rank_pack_plan(999, 128).is_noop or \
        dispatch.rank_pack_plan(999, 128).slots == 1


@pytest.mark.parametrize("rows,r", [(37, 3), (64, 5), (129, 8), (50, 17)])
def test_rank_packed_adam_matches_xla(rows, r, monkeypatch):
    f = lambda scale=1.0: jnp.asarray(
        RNG.normal(size=(rows, r)) * scale, jnp.float32)
    b, g = f(), f(0.1)
    m, v = jnp.abs(f(0.1)), jnp.abs(f(0.01))
    kw = dict(lr=1e-3, step=3.0, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01)
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    ref_out = dispatch.subspace_adam(b, g, m, v, **kw)
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    packed_out = dispatch.subspace_adam(b, g, m, v, **kw)
    for a, e in zip(packed_out, ref_out):
        assert a.shape == (rows, r)
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=1e-6, atol=1e-7)


def test_layout_carries_pack_plans():
    tcfg = _ragged_tcfg()
    state = subspace.init(_ragged_params(), tcfg, jax.random.key(0))
    assert len(state.layout.packs) == len(state.layout.groups)
    for spec, plan in zip(state.layout.groups, state.layout.packs):
        rows = len(spec.leaf_idx) * int(
            np.prod(spec.shape[:-2], initial=1)) * spec.shape[-1]
        assert plan == dispatch.rank_pack_plan(rows, spec.rank)


# ---------------------------------------------------------------------------
# Dispatch VMEM guard sizes operands by their real dtypes (bugfix)
# ---------------------------------------------------------------------------

def test_vmem_guard_uses_real_itemsize(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_DISPATCH", raising=False)
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    shapes = (256, 6144, 2048, 32)   # (M, K, N, r)
    m, k, n, r = shapes
    f32 = dispatch._bwd_vmem_bytes(m, k, n, r, (4,) * 5)
    bf16 = dispatch._bwd_vmem_bytes(m, k, n, r, (2,) * 5)
    # the shape is chosen to straddle the budget — keep it meaningful
    assert bf16 < dispatch.VMEM_BUDGET < f32
    assert dispatch.route("lowrank_backward", shapes=shapes,
                          dtypes=("float32",) * 5) == "xla"
    assert dispatch.route("lowrank_backward", shapes=shapes,
                          dtypes=("bfloat16",) * 5) == "pallas"


# ---------------------------------------------------------------------------
# Checkpoints: fp32 <-> bf16 restore and bfloat16 npz round-trip
# ---------------------------------------------------------------------------

def test_f32_checkpoint_restores_into_bf16_run(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_COMPUTE_DTYPE", raising=False)
    wd = str(tmp_path / "mix")
    Trainer(CFG, _tcfg("lowrank_adam", compute_dtype="float32"), _loader(),
            workdir=wd, checkpoint_every=2).run(2)
    tr = Trainer(CFG, _tcfg("lowrank_adam", compute_dtype="bfloat16"),
                 _loader(), workdir=wd)
    assert tr.maybe_resume() == 2
    for slot in tr.opt_state.groups:   # restored INTO the bf16 template
        assert slot.proj.dtype == jnp.bfloat16
        assert slot.b.dtype == jnp.float32
    rep = tr.run(2)
    assert np.isfinite(rep.losses).all()


def test_bf16_leaves_roundtrip_npz(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_COMPUTE_DTYPE", raising=False)
    wd = str(tmp_path / "bf16ckpt")
    tree = {"v": jnp.asarray(RNG.normal(size=(9, 4)), jnp.bfloat16),
            "w": jnp.asarray(RNG.normal(size=(5,)), jnp.float32)}
    ckpt.save(wd, 1, tree)
    restored, manifest = ckpt.restore_latest(wd, tree)
    assert manifest["dtypes"]["v"] == "bfloat16"
    assert restored["v"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["v"]).view(np.uint16),
        np.asarray(tree["v"]).view(np.uint16))
    # ...and a bf16 training run checkpoints/resumes end to end
    wd2 = str(tmp_path / "bf16run")
    tcfg = _tcfg("lowrank_adam", compute_dtype="bfloat16")
    Trainer(CFG, tcfg, _loader(), workdir=wd2, checkpoint_every=2).run(2)
    tr = Trainer(CFG, tcfg, _loader(), workdir=wd2)
    assert tr.maybe_resume() == 2
