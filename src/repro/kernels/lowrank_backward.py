"""Pallas TPU kernel: fused low-rank backward, dx and dB from one kernel.

The inner-step backward of Algorithm 1 needs

    dx = dy W^T + (dy B) V^T        (M, K)
    dB = dy^T p                     (N, r),  p = x V saved by the forward

The kernel is a K-tiled matmul dx = dy W^T with the rank-r terms fused
in.  Grid (M/bm, K/bk, N/bn): each (i, k) tile of dx gathers
dy_ij w_kj^T over the innermost j axis in a (bm, bk) fp32 accumulator,
and its epilogue adds q_i v_k^T and writes dx once, in dy's dtype.

  * q = dy B (bm, r) does not depend on k: it accumulates in VMEM scratch
    during the k == 0 sweep of a row of tiles and is reused by every
    later k (the forward's ``accp``, mirrored).
  * dB sums dy_ij^T p_i over the row blocks i, the outer axis, so it stays
    resident: a (N, r) fp32 VMEM scratch gathers it from the dy tiles of
    each row's k == 0 sweep, and one DMA copies it to HBM when the last
    row's sweep is done.  Nothing but dx and dB reaches HBM, and dB takes
    no pipelined block.

Neither a full-K strip of W nor a K-wide dx accumulator has to fit
VMEM: the blocks need not be square (bm a multiple of 16, bk and bn
multiples of 128, or the whole dimension), and ``kernels/dispatch.py``
picks them from the shapes and dtypes so that the double-buffered blocks
fit its VMEM budget; the resident dB is asked of Mosaic on top of the
default scoped limit.  Mixed precision as in ``kernels/ref.py``: every
contraction accumulates fp32, dx carries dy.dtype, dB is fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mixed import dot_nt as _dot_nt
from ._mixed import dot_tn as _dot_tn
from ._mixed import dotf as _dotf

Array = jax.Array

# Mosaic's default scoped VMEM on a v5e: the dispatch layer sizes the
# pipelined blocks inside it, and the kernel asks for the resident dB on
# top (the chip holds 128 MiB of VMEM)
SCOPED_VMEM = 16 * 2 ** 20


def _kernel(dy_ref, w_ref, v_ref, b_ref, p_ref, dx_ref, db_hbm, acc_ref,
            q_ref, dbacc_ref, *, n_i: int, n_j: int, bn: int):
    i = pl.program_id(0)
    k = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dy = dy_ref[...]                                     # (bm, bn)
    acc_ref[...] += _dot_nt(dy, w_ref[...])              # dy w_kj^T

    # q = dy B and dB read the same dy tiles for every k: take them from
    # the k == 0 sweep only
    @pl.when(k == 0)
    def _sweep0():
        @pl.when(j == 0)
        def _init_q():
            q_ref[...] = jnp.zeros_like(q_ref)

        q_ref[...] += _dotf(dy, b_ref[...])
        part = _dot_tn(dy, p_ref[...])                   # dy_ij^T p_i
        rows = pl.ds(pl.multiple_of(j * bn, bn), bn)

        @pl.when(i == 0)
        def _first():
            dbacc_ref[rows, :] = part

        @pl.when(i > 0)
        def _more():
            dbacc_ref[rows, :] += part

    # the last row's k == 0 sweep completes dB: one copy to HBM
    @pl.when((i == n_i - 1) & (k == 0) & (j == n_j - 1))
    def _out():
        pltpu.sync_copy(dbacc_ref, db_hbm)

    @pl.when(j == n_j - 1)
    def _fin():
        dx_ref[...] = (acc_ref[...] + _dot_nt(q_ref[...], v_ref[...])
                       ).astype(dx_ref.dtype)


def lowrank_backward(dy: Array, w: Array, v: Array, b: Array, p: Array, *,
                     bm: int = 128, bk: int = 128, bn: int = 128,
                     interpret: bool = False):
    """dy (M,N), w (K,N), v (K,r), b (N,r), p (M,r) -> (dx (M,K), db (N,r)).

    db is fp32 (Adam consumes it in fp32); dx is dy.dtype.
    """
    M, N = dy.shape
    K = w.shape[0]
    r = v.shape[1]
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    n_i, n_j = M // bm, N // bn

    def swept(k, j):
        # past the k == 0 sweep b is not read: hold its block index at the
        # sweep's last one, so that the pipeline fetches it no more
        return jnp.where(k == 0, j, n_j - 1)

    return pl.pallas_call(
        functools.partial(_kernel, n_i=n_i, n_j=n_j, bn=bn),
        grid=(n_i, K // bk, n_j),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, k, j: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, k, j: (k, j)),
            pl.BlockSpec((bk, r), lambda i, k, j: (k, 0)),
            pl.BlockSpec((bn, r), lambda i, k, j: (swept(k, j), 0)),
            pl.BlockSpec((bm, r), lambda i, k, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, k, j: (i, k)),
            pl.BlockSpec(memory_space=pl.ANY),      # dB, copied once
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, K), dy.dtype),
            jax.ShapeDtypeStruct((N, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bm, r), jnp.float32),
                        pltpu.VMEM((N, r), jnp.float32)],
        # every axis carries state: i the resident dB, k q, j acc
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=SCOPED_VMEM + 4 * N * r),
        interpret=interpret,
        name="lowrank_backward",
    )(dy, w, v, b, p)
