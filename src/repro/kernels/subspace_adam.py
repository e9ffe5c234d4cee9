"""Pallas TPU kernels: fused subspace optimizer updates on B.

One VMEM round-trip for the state arrays (b, g, m[, v]) -> outputs
instead of the ~10 elementwise HBM passes an unfused Adam emits.  The
subspace state is (n_out, r) — small — so this is latency- not bandwidth-
critical; fusing keeps the outer-loop bubble short on pods.

Four variants share the structure:

``subspace_adam``     fp32 moments, the PR 1 kernel.
``subspace_lion``     momentum-only Lion (sign update) — half the state.
``subspace_adam_q8``  int8 block-quantized m/v: operands arrive in the
                      128-lane block layout (one fp32 absmax scale per
                      row); dequant -> fp32 update -> requant happens
                      entirely in VMEM, so fp32 moments never touch HBM.
``subspace_lion_q8``  quantized momentum-only variant.

The q8 kernels optionally fuse stochastic rounding of the B master to
bf16 (``bits`` operand: uniform uint16-in-uint32 noise generated from
the step's PRNG OUTSIDE the kernel, so interpret mode and TPU lowering
share one code path).

Scalars (lr, bias corrections) are passed via scalar-prefetch (SMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mixed import sr_bf16

Array = jax.Array


def _requant(x: Array):
    """Per-row (128-lane block) absmax int8 requantization, in VMEM."""
    scale = jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def _requant_sqrt(x: Array):
    """sqrt-codec requant for second moments: absmax over sqrt(x) gives
    ~127^2 effective dynamic range, so small-but-live v entries do not
    collapse to zero and detonate ``m / (sqrt(v) + eps)``."""
    return _requant(jnp.sqrt(jnp.maximum(x, 0.0)))


def _deq(q_ref, s_ref) -> Array:
    return q_ref[...].astype(jnp.float32) * s_ref[...]


def _deq_sqrt(q_ref, s_ref) -> Array:
    y = q_ref[...].astype(jnp.float32) * s_ref[...]
    return y * y


def _adam_kernel(sc_ref, b_ref, g_ref, m_ref, v_ref,
                 bo_ref, mo_ref, vo_ref, *, beta1, beta2, eps, wd):
    lr = sc_ref[0]
    bc1 = sc_ref[1]
    bc2 = sc_ref[2]
    # Only the gradient may arrive in a reduced compute dtype — it is cast
    # up ONCE here, in VMEM; b/m/v are fp32 masters/moments in and out.
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    m = beta1 * m_ref[...].astype(jnp.float32) + (1.0 - beta1) * g
    v = beta2 * v_ref[...].astype(jnp.float32) + (1.0 - beta2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * b
    bo_ref[...] = b - lr * delta
    mo_ref[...] = m
    vo_ref[...] = v


def subspace_adam(b: Array, g: Array, m: Array, v: Array, *, lr, step,
                  beta1: float = 0.9, beta2: float = 0.999,
                  eps: float = 1e-8, wd: float = 0.0, block: int = 256,
                  interpret: bool = False):
    """b/m/v (N, r) fp32 masters/moments; g may be a reduced compute dtype
    (cast up in VMEM).  Returns (b', m', v'), always fp32."""
    N, r = b.shape
    blk = min(block, N)
    assert N % blk == 0
    step = jnp.asarray(step, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         1.0 - beta1 ** step,
                         1.0 - beta2 ** step])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // blk,),
        in_specs=[pl.BlockSpec((blk, r), lambda i, *_: (i, 0))] * 4,
        out_specs=[pl.BlockSpec((blk, r), lambda i, *_: (i, 0))] * 3,
    )
    return pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2, eps=eps,
                          wd=wd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, r), jnp.float32)] * 3,
        interpret=interpret,
        name="subspace_adam",
    )(scalars, b, g, m, v)


# ---------------------------------------------------------------------------
# Lion (momentum-only)
# ---------------------------------------------------------------------------

def _lion_kernel(sc_ref, b_ref, g_ref, m_ref, bo_ref, mo_ref,
                 *, beta1, beta2, wd):
    lr = sc_ref[0]
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    u = jnp.sign(beta1 * m + (1.0 - beta1) * g)
    bo_ref[...] = b - lr * (u + wd * b)
    mo_ref[...] = beta2 * m + (1.0 - beta2) * g


def subspace_lion(b: Array, g: Array, m: Array, *, lr,
                  beta1: float = 0.9, beta2: float = 0.99,
                  wd: float = 0.0, block: int = 256,
                  interpret: bool = False):
    """b/m (N, r) fp32 master/momentum; g may be a reduced compute dtype
    (cast up in VMEM).  Returns (b', m'), always fp32."""
    N, r = b.shape
    blk = min(block, N)
    assert N % blk == 0
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // blk,),
        in_specs=[pl.BlockSpec((blk, r), lambda i, *_: (i, 0))] * 3,
        out_specs=[pl.BlockSpec((blk, r), lambda i, *_: (i, 0))] * 2,
    )
    return pl.pallas_call(
        functools.partial(_lion_kernel, beta1=beta1, beta2=beta2, wd=wd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((N, r), jnp.float32)] * 2,
        interpret=interpret,
        name="subspace_lion",
    )(scalars, b, g, m)


# ---------------------------------------------------------------------------
# int8 block-quantized state (dequant -> fp32 update -> requant in VMEM)
# ---------------------------------------------------------------------------
#
# Quantized operands arrive pre-tiled to the 128-lane block layout: state
# reshaped (R, 128) int8 with one fp32 absmax scale per row, (R, 1).  A
# kernel block of (blk, 128) therefore owns exactly its (blk, 1) scales —
# dequant is a broadcast multiply, requant a per-row absmax, both in VMEM.

def _adam_q8_kernel(sc_ref, b_ref, g_ref, mq_ref, ms_ref, vq_ref, vs_ref,
                    *maybe_bits_then_outs, beta1, beta2, eps, wd, sr):
    if sr:
        (bits_ref, bo_ref, mq_o, ms_o, vq_o, vs_o) = maybe_bits_then_outs
    else:
        (bo_ref, mq_o, ms_o, vq_o, vs_o) = maybe_bits_then_outs
    lr = sc_ref[0]
    bc1 = sc_ref[1]
    bc2 = sc_ref[2]
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    m = beta1 * _deq(mq_ref, ms_ref) + (1.0 - beta1) * g
    v = beta2 * _deq_sqrt(vq_ref, vs_ref) + (1.0 - beta2) * g * g
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * b
    b_new = b - lr * delta
    if sr:
        bo_ref[...] = sr_bf16(b_new, bits_ref[...]).astype(bo_ref.dtype)
    else:
        bo_ref[...] = b_new.astype(bo_ref.dtype)
    mq_o[...], ms_o[...] = _requant(m)
    vq_o[...], vs_o[...] = _requant_sqrt(v)


def subspace_adam_q8(b: Array, g: Array, mq: Array, ms: Array,
                     vq: Array, vs: Array, *, lr, step,
                     beta1: float = 0.9, beta2: float = 0.999,
                     eps: float = 1e-8, wd: float = 0.0,
                     bits: Array | None = None, block: int = 256,
                     interpret: bool = False):
    """Quantized-state Adam over 128-lane blocks.

    b/g (R, 128) — b fp32 or bf16 master, g any compute dtype; mq/vq
    (R, 128) int8 with ms/vs (R, 1) fp32 scales.  ``bits`` (R, 128)
    uint32 enables fused stochastic rounding of b' (b' keeps b.dtype —
    pass a bf16 b for SR masters).  Returns
    (b', mq', ms', vq', vs').
    """
    R, L = b.shape
    blk = min(block, R)
    assert R % blk == 0
    sr = bits is not None
    step = jnp.asarray(step, jnp.float32)
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32),
                         1.0 - beta1 ** step,
                         1.0 - beta2 ** step])
    full = pl.BlockSpec((blk, L), lambda i, *_: (i, 0))
    scale = pl.BlockSpec((blk, 1), lambda i, *_: (i, 0))
    in_specs = [full, full, full, scale, full, scale]
    operands = [b, g, mq, ms, vq, vs]
    if sr:
        in_specs.append(full)
        operands.append(bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // blk,),
        in_specs=in_specs,
        out_specs=[full, full, scale, full, scale],
    )
    return pl.pallas_call(
        functools.partial(_adam_q8_kernel, beta1=beta1, beta2=beta2,
                          eps=eps, wd=wd, sr=sr),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, L), b.dtype),
                   jax.ShapeDtypeStruct((R, L), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32),
                   jax.ShapeDtypeStruct((R, L), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
        name="subspace_adam_q8",
    )(scalars, *operands)


def _lion_q8_kernel(sc_ref, b_ref, g_ref, mq_ref, ms_ref,
                    *maybe_bits_then_outs, beta1, beta2, wd, sr):
    if sr:
        (bits_ref, bo_ref, mq_o, ms_o) = maybe_bits_then_outs
    else:
        (bo_ref, mq_o, ms_o) = maybe_bits_then_outs
    lr = sc_ref[0]
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    m = _deq(mq_ref, ms_ref)
    u = jnp.sign(beta1 * m + (1.0 - beta1) * g)
    b_new = b - lr * (u + wd * b)
    if sr:
        bo_ref[...] = sr_bf16(b_new, bits_ref[...]).astype(bo_ref.dtype)
    else:
        bo_ref[...] = b_new.astype(bo_ref.dtype)
    mq_o[...], ms_o[...] = _requant(beta2 * m + (1.0 - beta2) * g)


def subspace_lion_q8(b: Array, g: Array, mq: Array, ms: Array, *, lr,
                     beta1: float = 0.9, beta2: float = 0.99,
                     wd: float = 0.0, bits: Array | None = None,
                     block: int = 256, interpret: bool = False):
    """Quantized-momentum Lion over 128-lane blocks; same operand
    contract as :func:`subspace_adam_q8` minus v.  Returns
    (b', mq', ms')."""
    R, L = b.shape
    blk = min(block, R)
    assert R % blk == 0
    sr = bits is not None
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32)])
    full = pl.BlockSpec((blk, L), lambda i, *_: (i, 0))
    scale = pl.BlockSpec((blk, 1), lambda i, *_: (i, 0))
    in_specs = [full, full, full, scale]
    operands = [b, g, mq, ms]
    if sr:
        in_specs.append(full)
        operands.append(bits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R // blk,),
        in_specs=in_specs,
        out_specs=[full, full, scale],
    )
    return pl.pallas_call(
        functools.partial(_lion_q8_kernel, beta1=beta1, beta2=beta2,
                          wd=wd, sr=sr),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, L), b.dtype),
                   jax.ShapeDtypeStruct((R, L), jnp.int8),
                   jax.ShapeDtypeStruct((R, 1), jnp.float32)],
        interpret=interpret,
        name="subspace_lion_q8",
    )(scalars, *operands)
