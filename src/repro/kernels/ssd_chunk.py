"""Pallas TPU kernel: Mamba2 SSD intra-chunk block.

The quadratic-within-chunk part of the state-space duality algorithm — the
compute hot-spot of the ssm/hybrid architectures.  One grid step processes
one (batch, chunk, head-block): builds the (Q, Q) decay-masked score matrix
on the fly in VMEM (never in HBM), emits the chunk output and the chunk's
local end-state for the inter-chunk ``lax.scan``.

Per-tile VMEM at Q=128, bh=8, N=128, P=64: x (Q,bh,P) 256 KB f32 +
scores (bh,Q,Q) 512 KB + B/C (Q,bh,N) 2x512 KB — comfortably < 16 MB.

The CUDA original is a warp-specialised kernel; the TPU adaptation maps the
(C_i . B_j) Gram matrix and the (att @ x) combine onto MXU matmuls with the
decay mask applied between them (DESIGN.md §3).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, st_ref):
    # blocks carry a leading size-1 batch*chunk dim: x (1, Q, bh, P), ...
    Q = x_ref.shape[1]
    x = x_ref[0].astype(jnp.float32)
    dt = dt_ref[0].astype(jnp.float32)
    da = da_ref[0].astype(jnp.float32)
    bmat = b_ref[0].astype(jnp.float32)
    cmat = c_ref[0].astype(jnp.float32)

    clog = jnp.cumsum(da, axis=0)                            # (Q, bh)
    # decay L[i, j, h] = exp(clog_i - clog_j) masked to i >= j
    diff = clog[:, None, :] - clog[None, :, :]               # (Q, Q, bh)
    mask = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    L = jnp.where(mask[:, :, None], jnp.exp(diff), 0.0)

    # scores s[i, j, h] = sum_n C[i,h,n] B[j,h,n]  (per-head Gram via MXU)
    s = jax.lax.dot_general(
        cmat.transpose(1, 0, 2), bmat.transpose(1, 0, 2),
        (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # (bh, Q, Q)
    att = s * L.transpose(2, 0, 1) * dt.T[:, None, :]        # * dt_j
    # y[i,h,p] = sum_j att[h,i,j] x[j,h,p]
    y = jax.lax.dot_general(
        att, x.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # (bh, Q, P)
    y_ref[0] = y.transpose(1, 0, 2).astype(y_ref.dtype)

    # local end state: sum_j exp(clog_last - clog_j) dt_j B_j x_j^T
    wj = jnp.exp(clog[-1][None, :] - clog) * dt              # (Q, bh)
    bw = bmat * wj[:, :, None]
    st = jax.lax.dot_general(
        bw.transpose(1, 2, 0), x.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                  # (bh, N, P)
    st_ref[0] = st


def ssd_intra_chunk(x: Array, dt: Array, da: Array, b: Array, c: Array, *,
                    head_block: int = 8, interpret: bool = False):
    """Batched intra-chunk SSD.

    x (BC, Q, H, P); dt, da (BC, Q, H); b, c (BC, Q, H, N) — BC = batch *
    n_chunks flattened, heads already broadcast.  Returns
    (y (BC, Q, H, P), state (BC, H, N, P)).
    """
    BC, Q, H, P = x.shape
    N = b.shape[-1]
    bh = min(head_block, H)
    assert H % bh == 0
    grid = (BC, H // bh)
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, bh, P), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, Q, bh), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, Q, bh), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, Q, bh, N), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, Q, bh, N), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, bh, P), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((1, bh, N, P), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BC, Q, H, P), x.dtype),
            jax.ShapeDtypeStruct((BC, H, N, P), jnp.float32),
        ],
        interpret=interpret,
        name="ssd_intra_chunk",
    )(x, dt, da, b, c)
