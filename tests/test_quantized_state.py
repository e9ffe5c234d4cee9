"""Quantized optimizer state + lowrank_lion (ISSUE-7 acceptance criteria).

  * block-quantize/dequantize round-trips within the absmax error bound
    for both codecs (linear first moments, sqrt second moments);
  * stochastic rounding to bf16 is unbiased: the mean over draws recovers
    the fp32 input far below one bf16 ulp, while deterministic
    round-to-nearest leaves an O(ulp) bias;
  * the fused q8 kernels (adam + lion, with and without SR) match the
    pure-jnp oracles bit-exactly on the int8 payloads;
  * int8-state training resumes bit-exactly from its checkpoint, and
    checkpoints restore ACROSS state dtypes both ways (fp32 archive into
    an int8 run and vice versa);
  * int8-state training tracks the fp32-state reference within the
    documented tolerance over 3 outer cycles for lowrank_adam AND
    lowrank_lion;
  * the dispatch VMEM guard sizes block-quantized operands at their
    effective ~1.03 B/element, not the 4-byte fp32 fallback;
  * lowrank_lion is a full citizen purely via registration: it appears in
    the method registry and the bench variant grids with zero consumer
    edits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import methods
from repro.configs import TrainConfig, get_config
from repro.data.synthetic import StatelessLoader
from repro.kernels import dispatch, ref
from repro.kernels._mixed import sr_bf16
from repro.optim import quant, subspace
from repro.train import checkpoint as ckpt
from repro.train.trainer import Trainer

RNG = np.random.default_rng(11)

CFG = get_config("llama-tiny")

# Documented int8-state tolerance: relative deviation of the training
# loss from the fp32-state reference after 3 outer cycles.  The sqrt
# codec keeps the second moment's ~6-decade dynamic range representable
# (linear int8 collapses small-but-live v to zero and detonates
# m/(sqrt(v)+eps)), so the divergence is rounding-noise-driven: measured
# drift on llama-tiny is ~1e-3 relative; 6% is conservative.
INT8_LOSS_RTOL = 0.06

_LR = {"lowrank_adam": 3e-3, "lowrank_lion": 3e-4}


def _tcfg(name, **kw):
    base = dict(optimizer=name, sampler="stiefel", rank=8, lazy_k=3,
                lr=_LR.get(name, 1e-3), warmup_steps=0, total_steps=100,
                min_dim_for_lowrank=64, weight_decay=0.0,
                schedule="constant", seed=0)
    base.update(kw)
    return TrainConfig(**base)


def _loader(batch=4, seq=32):
    return StatelessLoader("lm", seed=0, batch=batch, seq_len=seq,
                           vocab=CFG.vocab_size)


# ---------------------------------------------------------------------------
# Quantize / dequantize round-trip error bounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(64,), (40, 8), (3, 37, 8)])
def test_linear_roundtrip_bound(shape):
    x = jnp.asarray(RNG.normal(size=shape) * RNG.uniform(0.01, 10), jnp.float32)
    qt = quant.quantize(x)
    assert qt.q.shape == x.shape and qt.q.dtype == jnp.int8
    assert qt.scale.shape == (quant.nblocks(x.size),)
    back = quant.dequantize(qt)
    # absmax rounding: per-block error <= scale/2 = blockmax/254
    nb = qt.scale.shape[0]
    flat_err = np.abs(np.asarray(
        jnp.pad((back - x).ravel(), (0, nb * qt.block - x.size))
        ).reshape(nb, qt.block))
    bound = np.asarray(qt.scale)[:, None] / 2 + 1e-12
    assert (flat_err <= bound).all()


def test_sqrt_roundtrip_tracks_wide_dynamic_range():
    # second-moment-like data spanning ~4 decades INSIDE one block.  A
    # linear absmax code only represents ~2.1 decades of nonzero values
    # (min nonzero level = blockmax/127), so it collapses the small tail
    # to exactly zero — the m/(sqrt(v)+eps) detonation.  The sqrt codec
    # squares the representable range to ~4.2 decades and keeps every
    # element of this block alive.
    v = jnp.asarray(10.0 ** RNG.uniform(-6, -2, size=(256,)), jnp.float32)
    lin = quant.dequantize(quant.quantize(v, codec="linear"))
    sq = quant.dequantize(quant.quantize(v, codec="sqrt"))
    small = np.asarray(v) < 1e-5
    assert small.any()
    # linear collapses part of the small tail to exactly zero...
    assert (np.asarray(lin)[small] == 0).any()
    # ...sqrt keeps every element non-zero and sqrt-domain-accurate
    # (error bound: half the sqrt-domain scale = sqrt(blockmax)/254)
    assert (np.asarray(sq) > 0).all()
    np.testing.assert_allclose(np.sqrt(np.asarray(sq)),
                               np.sqrt(np.asarray(v)), rtol=0, atol=4e-4)


def test_quantize_zeros_and_zeros_like():
    z = quant.zeros((5, 7), codec="sqrt")
    assert (np.asarray(quant.dequantize(z)) == 0).all()
    x = quant.quantize(jnp.ones((4, 4)))
    zl = quant.zeros_like(x)
    assert quant.is_quantized(zl) and zl.codec == x.codec
    assert (np.asarray(zl.q) == 0).all()
    with pytest.raises(ValueError, match="codec"):
        quant.quantize(jnp.ones(4), codec="log")


# ---------------------------------------------------------------------------
# Stochastic rounding: unbiased in expectation
# ---------------------------------------------------------------------------

def test_sr_bf16_unbiased_mean_over_draws():
    n, draws = 64, 4096
    x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    bits = (jax.random.bits(jax.random.key(5), (draws, n), jnp.uint32)
            >> 16)
    rounded = jax.vmap(lambda b: sr_bf16(x, b))(bits)
    assert rounded.dtype == jnp.bfloat16
    mean = np.asarray(jnp.mean(rounded.astype(jnp.float32), axis=0))
    det_err = np.abs(np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)
                                - x))
    # deterministic cast leaves O(ulp) bias; the SR mean beats it by >10x
    assert det_err.max() > 1e-3
    np.testing.assert_allclose(mean, np.asarray(x), atol=1e-4)
    # every draw is one of the two neighbouring bf16 values
    lo = np.asarray(rounded.astype(jnp.float32)).min(0)
    hi = np.asarray(rounded.astype(jnp.float32)).max(0)
    assert ((lo <= np.asarray(x) + 1e-12) & (np.asarray(x) <= hi + 1e-12)).all()


# ---------------------------------------------------------------------------
# Fused q8 kernels match the oracles (both dispatch routes)
# ---------------------------------------------------------------------------

def _q8_operands(n=40, r=8, master=jnp.float32):
    b = jnp.asarray(RNG.normal(size=(n, r)), master)
    g = jnp.asarray(RNG.normal(size=(n, r)) * 1e-2, jnp.float32)
    m = quant.quantize(jnp.asarray(RNG.normal(size=(n, r)) * 1e-2,
                                   jnp.float32))
    v = quant.quantize(jnp.asarray(
        np.abs(RNG.normal(size=(n, r))) * 1e-4, jnp.float32), codec="sqrt")
    return b, g, m, v


@pytest.mark.parametrize("sr", [False, True])
def test_adam_q8_dispatch_matches_ref(monkeypatch, sr):
    b, g, m, v = _q8_operands(master=jnp.bfloat16 if sr else jnp.float32)
    bits = (jax.random.bits(jax.random.key(3), b.shape, jnp.uint32) >> 16
            if sr else None)
    kw = dict(lr=1e-3, step=5.0, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01)
    outs = {}
    for rt in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_DISPATCH", rt)
        outs[rt] = dispatch.subspace_adam_q8(b, g, m.q, m.scale, v.q,
                                             v.scale, bits=bits, **kw)
    for a, b2 in zip(outs["xla"], outs["pallas"]):
        # int8 payloads and b' must agree bit-exactly across routes
        if a.dtype in (jnp.int8, jnp.bfloat16):
            assert np.array_equal(np.asarray(a), np.asarray(b2))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       atol=1e-6)


@pytest.mark.parametrize("sr", [False, True])
def test_lion_q8_dispatch_matches_ref(monkeypatch, sr):
    b, g, m, _ = _q8_operands(master=jnp.bfloat16 if sr else jnp.float32)
    bits = (jax.random.bits(jax.random.key(4), b.shape, jnp.uint32) >> 16
            if sr else None)
    kw = dict(lr=1e-4, beta1=0.9, beta2=0.99, wd=0.01)
    outs = {}
    for rt in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_DISPATCH", rt)
        outs[rt] = dispatch.subspace_lion_q8(b, g, m.q, m.scale,
                                             bits=bits, **kw)
    for a, b2 in zip(outs["xla"], outs["pallas"]):
        if a.dtype in (jnp.int8, jnp.bfloat16):
            assert np.array_equal(np.asarray(a), np.asarray(b2))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       atol=1e-6)


def test_lion_fp32_dispatch_matches_ref(monkeypatch):
    n, r = 48, 8
    b = jnp.asarray(RNG.normal(size=(n, r)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(n, r)) * 1e-2, jnp.float32)
    m = jnp.asarray(RNG.normal(size=(n, r)) * 1e-2, jnp.float32)
    want = ref.subspace_lion(b, g, m, lr=1e-4, beta1=0.9, beta2=0.99,
                             wd=0.01)
    for rt in ("xla", "pallas"):
        monkeypatch.setenv("REPRO_KERNEL_DISPATCH", rt)
        got = dispatch.subspace_lion(b, g, m, lr=1e-4, wd=0.01)
        for a, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       atol=1e-6)


# ---------------------------------------------------------------------------
# VMEM-guard sizing of block-quantized operands (the _itemsize fix)
# ---------------------------------------------------------------------------

def test_route_sizes_quantized_operands_effectively():
    # ("int8", 128) sizes as payload + scale share, NOT 4-byte fp32
    assert dispatch._itemsize(("int8", 128)) == pytest.approx(1.0 + 4 / 128)
    assert dispatch._itemsize(("int8", 64)) == pytest.approx(1.0 + 4 / 64)
    assert dispatch._itemsize(jnp.int8) == 1.0
    assert dispatch._itemsize(jnp.float32) == 4.0
    sizes = dispatch._sizes(
        (jnp.bfloat16, jnp.float32, ("int8", 128), ("int8", 128)), 4, 4)
    assert sizes == (2.0, 4.0, pytest.approx(1.03125),
                     pytest.approx(1.03125))
    # descriptor tuples flow through route() without error
    assert dispatch.route("subspace_adam_q8",
                          dtypes=(jnp.bfloat16, jnp.float32,
                                  ("int8", 128), ("int8", 128))) in (
        "xla", "pallas")


# ---------------------------------------------------------------------------
# int8-state training: bit-exact resume + cross-dtype restore
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["lowrank_adam", "lowrank_lion"])
def test_int8_state_checkpoint_resume_bitexact(name, tmp_path):
    wd = str(tmp_path / name)
    tcfg = _tcfg(name, state_dtype="int8", master_dtype="bfloat16")
    Trainer(CFG, tcfg, _loader(), workdir=wd, checkpoint_every=2).run(4)
    tr2 = Trainer(CFG, tcfg, _loader(), workdir=wd)
    rep2 = tr2.run(2)
    assert rep2.resumed_from == 4
    rep3 = Trainer(CFG, tcfg, _loader()).run(6)
    np.testing.assert_allclose(rep2.losses, rep3.losses[4:], rtol=1e-5)
    # manifest records the state/master dtypes and the quant tags
    _, manifest = ckpt.restore_latest(
        wd, {"params": tr2.params, "opt": tr2.opt_state})
    assert manifest["extra"]["state_dtype"] == "int8"
    assert manifest["extra"]["master_dtype"] == "bfloat16"
    assert manifest["quant"], "quantized leaves must carry manifest tags"
    for block, codec in manifest["quant"].values():
        assert block == quant.QBLOCK and codec in ("linear", "sqrt")


def _init(tcfg):
    from repro.models import lm
    m = methods.get(tcfg.optimizer)
    return m.init(lm.init_params(CFG, jax.random.key(0)), tcfg,
                  jax.random.key(1))


def test_cross_dtype_restore_both_ways(tmp_path, monkeypatch):
    # the templates pin their state dtype via tcfg; a whole-run env
    # override (the int8 CI leg) must not flip the fp32 template
    monkeypatch.delenv("REPRO_STATE_DTYPE", raising=False)
    monkeypatch.delenv("REPRO_MASTER_DTYPE", raising=False)
    p8, o8 = _init(_tcfg("lowrank_adam", state_dtype="int8"))
    pf, of = _init(_tcfg("lowrank_adam", state_dtype="float32"))
    # non-trivial moments in the int8 state
    o8 = jax.tree.map(
        lambda x: quant.quantize(
            jnp.asarray(RNG.normal(size=x.shape) * 1e-2, jnp.float32),
            block=x.block, codec=x.codec)
        if quant.is_quantized(x) else x,
        o8, is_leaf=quant.is_quantized)

    wd = str(tmp_path / "int8")
    ckpt.save(wd, 1, {"params": p8, "opt": o8})
    # int8 archive -> fp32 template: dequantized values land in the leaf
    rf, _ = ckpt.restore(wd, 1, {"params": pf, "opt": of})
    assert all(not quant.is_quantized(x) for x in jax.tree.leaves(
        rf["opt"], is_leaf=quant.is_quantized))
    for want, got in zip(o8.groups, rf["opt"].groups):
        np.testing.assert_allclose(np.asarray(quant.dequantize(want.m)),
                                   np.asarray(got.m), atol=1e-7)
        np.testing.assert_allclose(np.asarray(quant.dequantize(want.v)),
                                   np.asarray(got.v), atol=1e-7)

    # fp32 archive -> int8 template: quantized on load, values within the
    # block-quantization error of the saved fp32 moments
    of2 = subspace.SubspaceState(
        dense=of.dense,
        groups=tuple(
            s._replace(m=jnp.asarray(RNG.normal(size=s.m.shape) * 1e-2,
                                     jnp.float32),
                       v=jnp.asarray(np.abs(RNG.normal(size=s.v.shape))
                                     * 1e-4, jnp.float32))
            for s in of.groups),
        step=of.step, outer_step=of.outer_step, key=of.key,
        layout=of.layout)
    wd2 = str(tmp_path / "fp32")
    ckpt.save(wd2, 1, {"params": pf, "opt": of2})
    r8, _ = ckpt.restore(wd2, 1, {"params": p8, "opt": o8})
    for want, got in zip(of2.groups, r8["opt"].groups):
        assert quant.is_quantized(got.m) and got.v.codec == "sqrt"
        qm = quant.quantize(want.m, block=got.m.block, codec=got.m.codec)
        assert np.array_equal(np.asarray(qm.q), np.asarray(got.m.q))
        qv = quant.quantize(want.v, block=got.v.block, codec=got.v.codec)
        assert np.array_equal(np.asarray(qv.q), np.asarray(got.v.q))


# ---------------------------------------------------------------------------
# int8-state convergence tracks fp32 state within tolerance, adam + lion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["lowrank_adam", "lowrank_lion"])
def test_int8_training_tracks_f32_state(name, monkeypatch):
    monkeypatch.delenv("REPRO_STATE_DTYPE", raising=False)
    monkeypatch.delenv("REPRO_MASTER_DTYPE", raising=False)
    losses = {}
    for sd, md in (("float32", "float32"), ("int8", "bfloat16")):
        tr = Trainer(CFG, _tcfg(name, state_dtype=sd, master_dtype=md),
                     _loader())
        rep = tr.run(10)            # > 3 outer cycles at lazy_k=3
        assert np.isfinite(rep.losses).all()
        losses[sd] = rep.losses
    f32, i8 = np.asarray(losses["float32"]), np.asarray(losses["int8"])
    np.testing.assert_allclose(i8, f32, rtol=INT8_LOSS_RTOL)
    assert i8[-1] < i8[0]            # and it actually trains


def test_int8_state_storage_dtypes(monkeypatch):
    monkeypatch.delenv("REPRO_STATE_DTYPE", raising=False)
    monkeypatch.delenv("REPRO_MASTER_DTYPE", raising=False)
    tcfg = _tcfg("lowrank_adam", state_dtype="int8",
                 master_dtype="bfloat16")
    gp, state = _init(tcfg)
    assert state.layout.state_dtype == "int8"
    assert state.layout.master_dtype == "bfloat16"
    for slot in state.groups:
        assert slot.b.dtype == jnp.bfloat16     # SR bf16 masters
        assert quant.is_quantized(slot.m) and slot.m.codec == "linear"
        assert quant.is_quantized(slot.v) and slot.v.codec == "sqrt"
    # lion: momentum only, v is a rank-consistent zero-size placeholder
    _, ls = _init(_tcfg("lowrank_lion", state_dtype="int8"))
    assert ls.layout.algo == "lion"
    for slot in ls.groups:
        assert quant.is_quantized(slot.m)
        assert not quant.is_quantized(slot.v) and slot.v.shape[-2] == 0


def test_galore_opts_out_of_quantized_state(monkeypatch):
    """GaLore's moment math runs in plain XLA (no fused q8 kernels), so it
    pins fp32 state/masters no matter what the knobs say — including the
    whole-run env override used by the int8 CI leg."""
    monkeypatch.setenv("REPRO_STATE_DTYPE", "int8")
    monkeypatch.setenv("REPRO_MASTER_DTYPE", "bfloat16")
    _, state = _init(_tcfg("galore", state_dtype="int8",
                           master_dtype="bfloat16"))
    assert state.layout.state_dtype == "float32"
    assert state.layout.master_dtype == "float32"
    for slot in state.groups:
        assert not quant.is_quantized(slot.m)
        assert not quant.is_quantized(slot.v)
        assert slot.b.dtype == jnp.float32


def test_state_dtype_env_override(monkeypatch):
    from repro.models.common import resolve_master_dtype, resolve_state_dtype
    monkeypatch.setenv("REPRO_STATE_DTYPE", "int8")
    assert resolve_state_dtype(_tcfg("lowrank_adam")) == "int8"
    monkeypatch.setenv("REPRO_STATE_DTYPE", "")
    assert resolve_state_dtype(_tcfg("lowrank_adam")) == "float32"
    monkeypatch.setenv("REPRO_STATE_DTYPE", "int4")
    with pytest.raises(ValueError, match="int4"):
        resolve_state_dtype(_tcfg("lowrank_adam"))
    monkeypatch.setenv("REPRO_MASTER_DTYPE", "bfloat16")
    assert resolve_master_dtype(_tcfg("lowrank_adam")) == "bfloat16"


# ---------------------------------------------------------------------------
# lowrank_lion: full citizen purely via registration
# ---------------------------------------------------------------------------

def test_lion_registered_and_described():
    assert "lowrank_lion" in methods.available()
    d = methods.get("lowrank_lion").describe()
    assert d["family"] == "bp"


def test_lion_in_bench_variant_grids():
    """memory_table picks lion up with zero consumer edits: its rows come
    from methods.available() via variants()."""
    import importlib.util
    import os
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "memory_table.py")
    spec = importlib.util.spec_from_file_location("memory_table", root)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    grid = mod.variants()
    assert "lowrank_lion" in grid
    assert grid["lowrank_lion"].optimizer == "lowrank_lion"


def test_lion_dry_run_lowers():
    """The jitted lion inner step lowers (dry-run compilability)."""
    from repro.data.synthetic import lm_batch
    tcfg = _tcfg("lowrank_lion", state_dtype="int8",
                 master_dtype="bfloat16")
    m = methods.get("lowrank_lion")
    params, opt = _init(tcfg)
    batch = lm_batch(0, 0, batch=2, seq_len=16, vocab=CFG.vocab_size)
    jax.jit(m.make_inner_step(CFG, tcfg)).lower(params, opt, batch)
