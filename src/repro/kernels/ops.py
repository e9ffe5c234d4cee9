"""Jit'd public wrappers for the Pallas kernels.

Off the TPU kernels run in ``interpret=True`` mode — the kernel body
executes eagerly in Python, validating BlockSpec indexing and numerics
against :mod:`ref`.  On a TPU they always compile to Mosaic: interpret mode
is decided by the backend alone, so a chip run can never time the
interpreter.

These wrappers are the raw aligned-shape entry points (benchmarks, tests);
the training hot path goes through :mod:`repro.kernels.dispatch`, which
adds pad-to-tile, dtype-aware routing, rank packing and the per-
``(op, padded shape, dtypes)`` kernel cache.  All kernels accept
mixed-dtype operands (bf16 compute against fp32 masters) and accumulate
in fp32 — see the casting contract in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax

from .lowrank_forward import lowrank_forward as _fwd
from .lowrank_update import lowrank_merge as _merge, lowrank_project as _proj
from .lowrank_update import lowrank_merge_sr as _merge_sr
from .ssd_chunk import ssd_intra_chunk as _ssd
from .subspace_adam import subspace_adam as _adam
from .subspace_adam import subspace_adam_q8 as _adam_q8
from .subspace_adam import subspace_lion as _lion
from .subspace_adam import subspace_lion_q8 as _lion_q8


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def lowrank_forward(x, w, v, b, bm=128, bn=128, bk=128):
    return _fwd(x, w, v, b, bm=bm, bn=bn, bk=bk, interpret=_interpret())


@jax.jit
def lowrank_merge(w, v, b):
    return _merge(w, v, b, interpret=_interpret())


@jax.jit
def lowrank_project(g, v):
    return _proj(g, v, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "eps", "wd"))
def subspace_adam(b, g, m, v, lr, step, beta1=0.9, beta2=0.999, eps=1e-8,
                  wd=0.0):
    return _adam(b, g, m, v, lr=lr, step=step, beta1=beta1, beta2=beta2,
                 eps=eps, wd=wd, interpret=_interpret())


@jax.jit
def lowrank_merge_sr(w, v, b, bits):
    return _merge_sr(w, v, b, bits, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "wd"))
def subspace_lion(b, g, m, lr, beta1=0.9, beta2=0.99, wd=0.0):
    return _lion(b, g, m, lr=lr, beta1=beta1, beta2=beta2, wd=wd,
                 interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("beta1", "beta2", "eps", "wd"))
def subspace_adam_q8(b, g, mq, ms, vq, vs, lr, step, beta1=0.9,
                     beta2=0.999, eps=1e-8, wd=0.0, bits=None):
    return _adam_q8(b, g, mq, ms, vq, vs, lr=lr, step=step, beta1=beta1,
                    beta2=beta2, eps=eps, wd=wd, bits=bits,
                    interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "wd"))
def subspace_lion_q8(b, g, mq, ms, lr, beta1=0.9, beta2=0.99, wd=0.0,
                     bits=None):
    return _lion_q8(b, g, mq, ms, lr=lr, beta1=beta1, beta2=beta2, wd=wd,
                    bits=bits, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("head_block",))
def ssd_intra_chunk(x, dt, da, b, c, head_block=8):
    return _ssd(x, dt, da, b, c, head_block=head_block,
                interpret=_interpret())


__all__ = ["lowrank_forward", "lowrank_merge", "lowrank_merge_sr",
           "lowrank_project", "subspace_adam", "subspace_adam_q8",
           "subspace_lion", "subspace_lion_q8", "ssd_intra_chunk", "ref"]
