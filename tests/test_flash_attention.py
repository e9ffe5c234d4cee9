"""The flash-attention kernel route against today's XLA route.

``blockwise_attention`` is run on both routes (the kernel in interpret
mode) with bf16 inputs and compared, forward output and the gradients of
q, k and v, with the same function in fp32 on the XLA route.  The
kernel's gap may be at most twice the bf16 XLA route's own gap: the
tolerance is the spread that bf16 already gives today's path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels import flash_attention as fa
from repro.models.attention import blockwise_attention

D = 128


def _inputs(s, hq, hkv, seed):
    kq, kk, kv, kd = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(kq, (1, s, hq, D), jnp.float32)
    k = jax.random.normal(kk, (1, s, hkv, D), jnp.float32)
    v = jax.random.normal(kv, (1, s, hkv, D), jnp.float32)
    do = jax.random.normal(kd, (1, s, hq, D), jnp.float32)
    return q, k, v, do


def _out_and_grads(q, k, v, do):
    def loss(q, k, v):
        out = blockwise_attention(q, k, v, q_chunk=128, kv_chunk=256)
        return jnp.sum(out.astype(jnp.float32) * do), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return [np.asarray(a, np.float32) for a in (out,) + grads]


def _gaps(got, ref):
    """Largest error of each array over the largest reference entry."""
    return np.array([np.abs(g - r).max() / np.abs(r).max()
                     for g, r in zip(got, ref)])


# blocks below the shape's own, so that the grids have several blocks a
# side, the causal mask skips some and the fused backward sums 2 dQ partials
SMALL = {"FLASH_BLOCK": 256, "FLASH_FWD_COMPUTE": 128,
         "FLASH_DKV_COMPUTE": 128}
# (S, query heads, K/V heads, q's dtype, block caps): GQA ratios 1, 4 and
# 7 at two lengths at the shape's own blocks; the query in fp32, as the
# model hands it over; the fused and the split backward at small blocks
CASES = [(256, 2, 2, "bfloat16", {}), (512, 2, 2, "bfloat16", {}),
         (256, 4, 1, "bfloat16", {}), (512, 4, 1, "bfloat16", {}),
         (256, 7, 1, "bfloat16", {}), (512, 7, 1, "bfloat16", {}),
         (256, 4, 1, "float32", {}),
         (512, 4, 1, "bfloat16", SMALL),
         (512, 4, 1, "bfloat16", {**SMALL, "FLASH_FUSED_MAX_PARTIALS": 0})]


@pytest.mark.parametrize("s,hq,hkv,q_dtype,caps", CASES, ids=[
    f"S{s}-{hq}x{hkv}-{dt}" + ("-split" if "FLASH_FUSED_MAX_PARTIALS" in c
                              else "-small" if c else "")
    for s, hq, hkv, dt, c in CASES])
def test_kernel_matches_blockwise_within_bf16_spread(s, hq, hkv, q_dtype,
                                                      caps, monkeypatch):
    q, k, v, do = _inputs(s, hq, hkv, seed=s + 10 * hq)
    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "xla")
    ref = _out_and_grads(q, k, v, do)
    qc = q.astype(q_dtype)
    kc, vc = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    xla = _out_and_grads(qc, kc, vc, do)

    monkeypatch.setenv("REPRO_KERNEL_DISPATCH", "pallas")
    for name, cap in caps.items():
        monkeypatch.setattr(fa, name, cap)
    dispatch.clear_kernel_cache()
    kernel = _out_and_grads(qc, kc, vc, do)
    assert dispatch.route_log() == {("attention", (1, s, hq, hkv, D)):
                                    "pallas"}

    spread = _gaps(xla, ref)
    assert spread.max() < 2e-2, spread   # today's bf16 path, for scale
    gaps = _gaps(kernel, ref)
    assert (gaps <= 2 * spread).all(), (gaps, spread)


def test_kernel_built_in_one_trace_serves_the_next():
    # the kernel (its mask tables) is built once per shape and kept; built
    # inside a trace, it must not keep that trace's values
    fa._kernel.cache_clear()
    q = jnp.ones((1, 128, 3, D), jnp.bfloat16)
    k = jnp.ones((1, 128, 1, D), jnp.bfloat16)

    def attend(q):
        return fa.flash_attention(q, k, k, softmax_scale=D ** -0.5,
                                  interpret=True)

    out = jax.jit(attend)(q)
    dq = jax.jit(jax.grad(
        lambda q: jnp.sum(attend(q).astype(jnp.float32))))(q)
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0)
    assert dq.shape == q.shape and bool(jnp.isfinite(dq).all())


def test_kernel_names_carry_one_prefix():
    # the prefix patches splash's own namer, process-wide: every kernel it
    # names, forward and backward, starts with flash_attention_ once, and
    # patching again (a reload of the module) adds no second prefix
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash)
    names = {splash.get_kernel_name(is_mqa=False, save_residuals=False,
                                    is_segmented=False, phase=phase)
             for phase in ("fwd", "dkv", "dq")}
    assert names == {f"flash_attention_splash_mha_{phase}_no_residuals"
                     for phase in ("fwd", "dkv", "dq")}
    again = fa._prefix_names(splash.get_kernel_name)
    assert again(False, False, False, "fwd") == \
        "flash_attention_splash_mha_fwd_no_residuals"
