"""Pallas TPU kernels for the outer-iteration merge and the lift/projection.

``lowrank_merge``:  W' = W + V B^T — the Algorithm-1 line-8 weight merge.
Runs once per K inner steps over every low-rank matrix; tiled (bk, bn)
output blocks with the full rank dimension resident in VMEM, fp32
accumulation into the stored dtype.

``lowrank_project``: G_B = G^T V — the Theorem-1 lift identity, used by the
GaLore-style project-after baseline and by tests; a tall-skinny matmul
tiled over the contraction dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bf16 V draws meet the fp32 B master in the merge and the lift — all
# dots go through the shared promote-in-VMEM helper
from ._mixed import dotf as _dotf
from ._mixed import sr_bf16 as _sr_bf16

Array = jax.Array


# ---------------------------------------------------------------------------
# W + V B^T
# ---------------------------------------------------------------------------

def _merge_kernel(w_ref, v_ref, b_ref, o_ref):
    delta = _dotf(v_ref[...], b_ref[...].T)
    o_ref[...] = (w_ref[...].astype(jnp.float32) + delta).astype(o_ref.dtype)


def lowrank_merge(w: Array, v: Array, b: Array, *, bk: int = 256,
                  bn: int = 256, interpret: bool = False) -> Array:
    """w (K,N) + v (K,r) @ b (N,r)^T."""
    K, N = w.shape
    r = v.shape[1]
    bk, bn = min(bk, K), min(bn, N)
    assert K % bk == 0 and N % bn == 0
    return pl.pallas_call(
        _merge_kernel,
        grid=(K // bk, N // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), w.dtype),
        interpret=interpret,
        name="lowrank_merge",
    )(w, v, b)


# ---------------------------------------------------------------------------
# W + V B^T with stochastic rounding (reduced-precision masters)
# ---------------------------------------------------------------------------

def _merge_sr_kernel(w_ref, v_ref, b_ref, bits_ref, o_ref):
    delta = _dotf(v_ref[...], b_ref[...].T)
    acc = w_ref[...].astype(jnp.float32) + delta
    o_ref[...] = _sr_bf16(acc, bits_ref[...]).astype(o_ref.dtype)


def lowrank_merge_sr(w: Array, v: Array, b: Array, bits: Array, *,
                     bk: int = 256, bn: int = 256,
                     interpret: bool = False) -> Array:
    """w (K,N) + v (K,r) @ b (N,r)^T, stochastically rounded into w's
    (reduced) dtype: ``bits`` (K,N) uint32 uniform over [0, 2**16)
    supplies the rounding noise, so the merge is unbiased to rounding
    even when the stored masters are bf16."""
    K, N = w.shape
    r = v.shape[1]
    bk, bn = min(bk, K), min(bn, N)
    assert K % bk == 0 and N % bn == 0
    return pl.pallas_call(
        _merge_sr_kernel,
        grid=(K // bk, N // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk, r), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, r), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), w.dtype),
        interpret=interpret,
        name="lowrank_merge_sr",
    )(w, v, b, bits)


# ---------------------------------------------------------------------------
# G^T V  (lift / projection)
# ---------------------------------------------------------------------------

def _project_kernel(g_ref, v_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _dotf(g_ref[...].T, v_ref[...])

    @pl.when(k == n_k - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def lowrank_project(g: Array, v: Array, *, bn: int = 256, bk: int = 256,
                    interpret: bool = False) -> Array:
    """g (K,N), v (K,r) -> G_B = g^T v (N,r), fp32 out."""
    K, N = g.shape
    r = v.shape[1]
    bn, bk = min(bn, N), min(bk, K)
    assert N % bn == 0 and K % bk == 0
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_project_kernel, n_k=n_k),
        grid=(N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda j, k: (k, j)),
            pl.BlockSpec((bk, r), lambda j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((bn, r), lambda j, k: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((N, r), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, r), jnp.float32)],
        interpret=interpret,
        name="lowrank_project",
    )(g, v)
