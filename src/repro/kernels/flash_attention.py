"""Pallas TPU kernel: causal grouped-query flash attention for training.

A thin wrapper over JAX's splash-attention kernel
(``jax.experimental.pallas.ops.tpu.splash_attention``), which already has
what the training step needs:

* grouped K/V as stored: query head ``h`` reads K/V head ``h // G``, with
  no repeated copy of K or V;
* a causal block mask: (query block, key block) pairs above the diagonal
  are skipped, not computed and masked;
* the running max, denominator and output accumulator of the online
  softmax in fp32 VMEM scratch, never in HBM;
* a custom VJP whose backward recomputes the probabilities from the saved
  log-sum-exp, in a dK/dV kernel and a dQ kernel or in one fused kernel.

The wrapper owns the layout and the scale.  The repo's layout
``q (B, S, Hq, D)``, ``k``/``v (B, S, Hkv, D)`` becomes splash's
``(H, S, D)`` per batch row, and the kernel is vmapped over the batch.
Splash takes no softmax scale, so the scale is folded into q: in fp32,
before q's one cast to the compute dtype.  A q that arrives in fp32 (the
model hands blockwise attention its rotary output unrounded) is thus
rounded once, as on the XLA path, where the scale multiplies the fp32
scores.  The output carries the compute dtype (k's).

Block sizes come from :func:`flash_blocks`, keyed on the sequence length,
the head size and the dtype.  Every ``pallas_call`` is named
``flash_attention_splash_mha_<phase>...`` (splash's own kernel names,
prefixed), so that a profiler trace tells the attention kernels apart
from the low-rank ones.  The prefix is a process-wide patch of a private
hook: importing this module replaces splash's module-level
``get_kernel_name``, which splash (as of jax 0.9.0) looks up when it
traces each kernel — the backward's too, traced after the forward has
returned, so the patch cannot be scoped to a call.  Any other splash user
in the process gets the prefix as well; applying the patch again (a
reload) leaves one prefix.  ``tests/test_flash_attention.py`` and the
kernel-name test of ``tests/test_tpu_compile.py`` pin it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash)
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_mask as _mask)

Array = jax.Array


def _prefix_names(get_name):
    """``get_name`` (splash's kernel namer, or this wrapper of it) with
    every name it gives prefixed ``flash_attention_``, once."""
    get_name = getattr(get_name, "__wrapped__", get_name)

    @functools.wraps(get_name)
    def name(*args, **kwargs) -> str:
        return f"flash_attention_{get_name(*args, **kwargs)}"
    return name


_splash.get_kernel_name = _prefix_names(_splash.get_kernel_name)


class FlashBlocks(NamedTuple):
    """The blocks :func:`flash_blocks` picks for one shape.

    ``block``: the query and key block of every kernel (the grid);
    ``fwd_compute``/``dkv_compute``: the key slice of one score tile
    inside a key block, in the forward and in the dK/dV (or fused)
    backward; ``fused``: one fused backward kernel, else dK/dV and dQ.
    """
    block: int
    fwd_compute: int
    dkv_compute: int
    fused: bool


# the largest block, and the largest key slices of the forward and of the
# dK/dV backward.  On a v5e sweep at the NeMo-12B training shape (PERF.md)
# the forward ran fastest at 1024 x 1024 blocks with 512-key slices (128
# blocks: 7x slower), the fused backward 18% faster than the split one,
# and the split one fastest at 1024 x 1024.
FLASH_BLOCK = 1024
FLASH_FWD_COMPUTE = 512
FLASH_DKV_COMPUTE = 1024
# the fused backward writes one bf16 dQ partial per key block and sums
# them after the kernel: S / block partials of q's size, a buffer
# quadratic in S.  Past this many partials the split backward (dQ
# accumulated in fp32 VMEM) takes over.
FLASH_FUSED_MAX_PARTIALS = 4


def _fit(s: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``s`` and is at most
    ``cap`` (``s`` a multiple of 128)."""
    return max(b for b in range(128, min(s, cap) + 1, 128) if s % b == 0)


def flash_blocks(s: int, d: int, dtype) -> Optional[FlashBlocks]:
    """Block sizes of the kernel at sequence length ``s``, head size ``d``
    and compute dtype ``dtype``; None where the kernel does not run.

    The kernel runs at a head size of a multiple of 128 (a whole number
    of lanes) and a sequence of a multiple of 128, in bf16, the dtype the
    blocks were swept in.  The block is the largest multiple of 128 that
    divides ``s`` under ``FLASH_BLOCK``, each key slice the largest that
    divides the block under its cap; the backward is fused while it
    leaves at most ``FLASH_FUSED_MAX_PARTIALS`` dQ partials."""
    if d % 128 or s % 128 or s <= 0 or \
            jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return None
    block = _fit(s, FLASH_BLOCK)
    return FlashBlocks(block, _fit(block, FLASH_FWD_COMPUTE),
                       _fit(block, FLASH_DKV_COMPUTE),
                       s // block <= FLASH_FUSED_MAX_PARTIALS)


@functools.cache
def _kernel(s: int, hq: int, blocks: FlashBlocks, interpret: bool):
    """The splash kernel for one (S, Hq, blocks): its causal mask's block
    tables are built once and kept.  They are built as constants even
    when the first call comes inside a trace: a traced table kept here
    would leak into every later trace."""
    b, split = blocks.block, not blocks.fused
    sizes = _splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=blocks.fwd_compute,
        block_q_dkv=b, block_kv_dkv=b,
        block_kv_dkv_compute=blocks.dkv_compute,
        block_q_dq=b if split else None, block_kv_dq=b if split else None,
        use_fused_bwd_kernel=blocks.fused)
    mask = _mask.MultiHeadMask([_mask.CausalMask((s, s))] * hq)
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(mask, block_sizes=sizes,
                                       head_shards=1, q_seq_shards=1,
                                       interpret=interpret)


def flash_attention(q: Array, k: Array, v: Array, *, softmax_scale: float,
                    interpret: bool = False) -> Array:
    """Causal self-attention ``softmax(scale · q kᵀ + causal mask) v``.

    q: (B, S, Hq, D) in any float dtype; k, v: (B, S, Hkv, D) in the
    compute dtype, ``Hq`` a multiple of ``Hkv``.  Returns (B, S, Hq, D) in
    k's dtype, computed at the blocks :func:`flash_blocks` picks.
    """
    B, S, Hq, D = q.shape
    blocks = flash_blocks(S, D, k.dtype)
    if blocks is None:
        raise ValueError(f"flash_attention: no blocks for S={S}, D={D}, "
                         f"{jnp.dtype(k.dtype).name}")
    kernel = _kernel(S, Hq, blocks, interpret)
    qs = (q.astype(jnp.float32) * softmax_scale).astype(k.dtype)
    out = jax.vmap(kernel)(qs.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
