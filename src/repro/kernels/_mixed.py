"""Shared mixed-precision helper for the Pallas kernel bodies and the
dispatch-layer XLA impls.

The casting contract (see :mod:`repro.kernels.ref`) allows operands of one
contraction to arrive in different dtypes — bf16 compute slices against
fp32 masters.  ``jax.lax.dot`` requires matching operand dtypes, so every
kernel routes its dots through :func:`dotf`: promote the narrower operand
in VMEM (one tile, not an HBM round-trip), accumulate in fp32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dotf(a: jax.Array, b: jax.Array) -> jax.Array:
    """fp32-accumulating dot tolerant of mixed operand dtypes."""
    if a.dtype != b.dtype:
        dt = jnp.promote_types(a.dtype, b.dtype)
        a, b = a.astype(dt), b.astype(dt)
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


def _dot_dims(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    dt = jnp.promote_types(a.dtype, b.dtype)
    return jax.lax.dot_general(a.astype(dt), b.astype(dt), (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """a (m, c) @ b (n, c)^T, contracting c on both operands in place (no
    transposed copy of b), fp32 accumulation as :func:`dotf`."""
    return _dot_dims(a, b, ((1,), (1,)))


def dot_tn(a: jax.Array, b: jax.Array) -> jax.Array:
    """a (c, m)^T @ b (c, n), contracting c on both operands in place,
    fp32 accumulation as :func:`dotf`."""
    return _dot_dims(a, b, ((0,), (0,)))


def sr_bf16(x: jax.Array, bits: jax.Array) -> jax.Array:
    """Stochastically round fp32 ``x`` to bf16 using ``bits``.

    ``bits`` is uint32 uniform over [0, 2**16): adding it to the fp32 bit
    pattern and truncating the low 16 mantissa bits rounds up with
    probability equal to the dropped fraction — unbiased in expectation,
    unlike round-to-nearest whose per-element bias accumulates over
    thousands of master updates.  Works identically inside Pallas kernel
    bodies (element-wise bit ops only) and in the XLA refs.
    """
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + bits.astype(jnp.uint32)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32).astype(jnp.bfloat16)
