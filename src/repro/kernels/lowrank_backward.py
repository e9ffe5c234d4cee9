"""Pallas TPU kernel: fused low-rank backward — dx and dB in ONE dy pass.

The inner-step backward of Algorithm 1 needs

    dx = dy W^T + (dy B) V^T        (M, K)
    dB = dy^T p                     (N, r),  p = x V saved by the forward

Unfused, autodiff schedules three independent contractions over dy — dy is
streamed from HBM three times and (dy B) once more.  This kernel makes one
pass over dy tiles: grid (M/bm, N/bn) with the FULL K dimension blocked into
VMEM, so each (bm, bn) dy tile is read exactly once and contributes

  * its j-slice of the dx row-strip accumulator  (dy w_j^T + (dy b_j) v^T),
  * its i-contribution to dB rows j              (dy^T p_i).

dx accumulates in a (bm, K) f32 scratch written at the end of each i row;
dB lives in VMEM as a resident output block because its contraction dim
(M) is the OUTER grid axis.  A wide N (an unembedding) would not fit whole,
so the grid carries a leading chunk axis: grid (C, M/bm, N/(C*bn)), and
chunk c owns dB rows [c*N/C, (c+1)*N/C) — resident for the whole i sweep
and written back once when c advances.  Each chunk then yields a partial
dx over its own N columns; those are emitted fp32 as (C, M, K) and summed
outside the kernel (C = 1 writes dx straight in dy.dtype).  VMEM cost is
therefore ~ K*(bn+r)*s + 4*(bm*K + (N/C)*r) bytes — the dispatch layer
picks C so it fits the budget and falls back to the XLA path when even
one bn-wide chunk does not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mixed import dotf as _dotf

Array = jax.Array


def _kernel(dy_ref, w_ref, v_ref, b_ref, p_ref, dx_ref, db_ref, acc_ref, *,
            n_j: int, bn: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_db():
        db_ref[...] = jnp.zeros_like(db_ref)

    dy = dy_ref[...]                                     # (bm, bn)
    # dx row-strip: dy w_j^T + (dy b_j) v^T, f32 accumulate over j
    q = _dotf(dy, b_ref[...])                            # (bm, r)
    acc_ref[...] += (
        _dotf(dy, w_ref[...].T) +
        _dotf(q, v_ref[...].T.astype(jnp.float32)))
    # dB rows for this j block: accumulate dy^T p across the i sweep
    db_ref[pl.ds(j * bn, bn), :] += _dotf(dy.T, p_ref[...].astype(dy.dtype))

    @pl.when(j == n_j - 1)
    def _fin():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def lowrank_backward(dy: Array, w: Array, v: Array, b: Array, p: Array, *,
                     bm: int = 128, bn: int = 128, n_chunks: int = 1,
                     interpret: bool = False):
    """dy (M,N), w (K,N), v (K,r), b (N,r), p (M,r) -> (dx (M,K), db (N,r)).

    db is fp32 (Adam consumes it in fp32); dx is dy.dtype.  ``n_chunks``
    splits N so that only N/n_chunks rows of dB are resident at a time.
    """
    M, N = dy.shape
    K = w.shape[0]
    r = v.shape[1]
    bm, bn = min(bm, M), min(bn, N // n_chunks)
    assert M % bm == 0 and N % (n_chunks * bn) == 0, (M, N, bm, bn,
                                                       n_chunks)
    n_j = N // (n_chunks * bn)
    nc = N // n_chunks
    dx_dtype = dy.dtype if n_chunks == 1 else jnp.float32

    grid = (n_chunks, M // bm, n_j)
    dx, db = pl.pallas_call(
        functools.partial(_kernel, n_j=n_j, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda c, i, j: (i, c * n_j + j)),
            pl.BlockSpec((K, bn), lambda c, i, j: (0, c * n_j + j)),
            pl.BlockSpec((K, r), lambda c, i, j: (0, 0)),
            pl.BlockSpec((bn, r), lambda c, i, j: (c * n_j + j, 0)),
            pl.BlockSpec((bm, r), lambda c, i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bm, K), lambda c, i, j: (c, i, 0)),
            pl.BlockSpec((nc, r), lambda c, i, j: (c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_chunks, M, K), dx_dtype),
            jax.ShapeDtypeStruct((N, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, K), jnp.float32)],
        interpret=interpret,
    )(dy, w, v, b, p)
    if n_chunks == 1:
        return dx[0], db
    return jnp.sum(dx, axis=0).astype(dy.dtype), db
