"""Pallas TPU kernel: fused low-rank forward  y = x W + (x V) B^T.

The inner-step hot matmul of Algorithm 1.  Fusing the rank-r bypass into
the main matmul's K-loop means the projected activation ``p = x V`` is
produced while x tiles are already in VMEM — zero extra HBM traffic for V's
contraction (V is r columns, resident per K-tile), and the B^T term is a
(bm, r) x (r, bn) MXU call per output tile.

Tiling: grid (M/bm, N/bn, K/bk); x tile (bm, bk), w tile (bk, bn), v tile
(bk, r); f32 scratch accumulators acc (bm, bn) and accp (bm, r) in VMEM.
The blocks need not be square: bm a multiple of 16, bn and bk multiples of
128 (or the whole dimension).  ``kernels/dispatch.py`` picks them from the
shapes and dtypes — the largest that divide the padded dimensions and keep
the double-buffered working set in its VMEM budget — so that each grid step
does enough MXU work to hide its DMA and its fixed cost.  The v tile is only
read in the j == 0 slab; its block index stops changing after that slab, so
the pipeline fetches it once per row of blocks, not once per step.

Mixed precision: refs may carry different dtypes (bf16 compute slices over
fp32 masters) — every contraction promotes its operands to a common dtype
in VMEM and accumulates fp32; y/p are written in x's dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mixed import dot_nt as _dot_nt
from ._mixed import dotf as _dotf

Array = jax.Array


def _kernel(x_ref, w_ref, v_ref, b_ref, o_ref, acc_ref, accp_ref, *,
            n_k: int):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_p():
        accp_ref[...] = jnp.zeros_like(accp_ref)

    x = x_ref[...]
    acc_ref[...] += _dotf(x, w_ref[...])

    # p = x V is j-independent and the VMEM scratch persists across the
    # grid: compute it during the j == 0 slab only, reuse it afterwards.
    @pl.when(j == 0)
    def _accum_p():
        accp_ref[...] += _dotf(x, v_ref[...])

    @pl.when(k == n_k - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] + _dot_nt(
            accp_ref[...], b_ref[...])).astype(o_ref.dtype)


def _kernel_p(x_ref, w_ref, v_ref, b_ref, o_ref, p_ref, acc_ref, accp_ref, *,
              n_k: int):
    """Same as :func:`_kernel` but also emits p = x V (the custom-vjp
    residual), written out once at the end of the j == 0 slab's K sweep."""
    _kernel(x_ref, w_ref, v_ref, b_ref, o_ref, acc_ref, accp_ref, n_k=n_k)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(jnp.logical_and(j == 0, k == n_k - 1))
    def _emit_p():
        p_ref[...] = accp_ref[...].astype(p_ref.dtype)


def lowrank_forward(x: Array, w: Array, v: Array, b: Array, *,
                    bm: int = 128, bn: int = 128, bk: int = 128,
                    interpret: bool = False, return_p: bool = False):
    """x (M,K) @ [w (K,N) + v (K,r) b (N,r)^T] -> (M,N).

    ``return_p=True`` additionally returns p = x V (M,r) — the projected
    activation the training backward pass keeps as its only residual.
    """
    M, K = x.shape
    N = w.shape[1]
    r = v.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk

    grid = (M // bm, N // bn, n_k)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        # past the j == 0 slab v is not read: hold its block index at the
        # slab's last one so that the pipeline issues no further copy
        pl.BlockSpec((bk, r),
                     lambda i, j, k: (jnp.where(j == 0, k, n_k - 1), 0)),
        pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),
    ]
    scratch = [
        pltpu.VMEM((bm, bn), jnp.float32),
        pltpu.VMEM((bm, r), jnp.float32),
    ]
    # rows of blocks are independent; j carries accp and k carries acc
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))
    if not return_p:
        return pl.pallas_call(
            functools.partial(_kernel, n_k=n_k),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
            name="lowrank_forward",
        )(x, w, v, b)
    return pl.pallas_call(
        functools.partial(_kernel_p, n_k=n_k),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, r), lambda i, j, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), x.dtype),
            jax.ShapeDtypeStruct((M, r), x.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="lowrank_forward",
    )(x, w, v, b)
