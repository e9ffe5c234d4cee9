"""Shape- and dtype-aware kernel dispatch: every low-rank op routed to its
best impl.

The training hot path (models/linear.py, optim/subspace.py) calls the
functions in this module instead of choosing between raw Pallas kernels and
jnp expressions itself.  Per call the dispatcher picks a route:

  * ``pallas`` — the fused Pallas kernel, with automatic pad-to-tile for
    ragged operands (lane = 128, sublane = 8/16): inputs are zero-padded up
    to block multiples and outputs sliced back, so the old hard
    ``assert K % bk == 0`` never bites callers.  Off the TPU the kernels
    run in interpret mode (:func:`_interpret`).
  * ``xla`` — the pure-jnp reference path (kernels/ref.py-style expressions
    with fp32 accumulation), which XLA fuses well on CPU/GPU and which
    serves as the fallback when a Pallas kernel's VMEM working set
    (double-buffered blocks counted twice) would blow the 12 MiB budget
    under the chip's 16 MiB scoped-VMEM limit.

Route selection: ``REPRO_KERNEL_DISPATCH`` ∈ {pallas, xla, auto} overrides;
``auto`` (default) = Pallas on TPU when the shape guard passes and no
multi-device mesh is active (Mosaic kernels cannot be partitioned by the
compiler), XLA otherwise.  :func:`route_log` records every choice.  The
VMEM guard uses each operand's REAL itemsize — a bf16 workload has half
the working set of the same-shape fp32 one and must not be spuriously
routed to the XLA fallback.  ``TABLE`` maps op -> {route -> impl} and is
deliberately a plain dict so tests can monkeypatch impls to assert the hot
path really flows through here.

Attention: ``route("attention", shapes=(B, S, Hq, Hkv, D), ...)`` is
called by ``models/attention.py::blockwise_attention``, which decides
alone whether the kernel (``kernels/flash_attention.py``) computes the
call and says so in ``fits``: a call it does not takes ``xla``, the
override included — unlike the low-rank kernels, the attention kernel
does not pad.

Mixed-precision contract (mirrored by kernels/ref.py):

  * forward:  y and p carry x.dtype; the y/p accumulators are fp32.
  * backward: dx carries dy.dtype, dB is fp32 (Adam consumes it in fp32).
  * merge:    W' carries w.dtype; the V B^T accumulate is fp32 even when
    V is bf16 and B is the fp32 master.
  * subspace_adam: b/m/v are fp32 masters/moments in AND out; only the
    gradient may arrive in a reduced dtype (cast up once, in VMEM).

Tiling: the padded dims are the same for every op (M to a multiple of 16
up to 128 and of 128 beyond, N and K to multiples of 128).  The fused
forward's and backward's blocks follow from the shape and the dtypes alone
(:func:`_pick_blocks`, shared by :func:`_fwd_blocks` and
:func:`_bwd_blocks`): the largest bm (a multiple of 16), bn and bk
(multiples of 128) that divide the padded dims, stay under ``MAX_BLOCKS``
and whose double-buffered working set fits the VMEM budget — the fewest
grid steps, and on a tie the wider bn/bk.  At the 12B widths both take
512 x 1024 x 1024; small shapes take their whole padded dims.  The route guard sizes exactly those
blocks; the backward's resident dB comes on top (``BWD_DB_VMEM``).  The
merge and the projection take 256-blocks.

Kernel cache: every Pallas launch is built once per
``(op, padded shape, dtypes, blocks, statics)`` key and memoised in
``_KERNEL_CACHE`` — ragged shapes that pad to the same tile grid share one
compiled kernel instead of re-tracing per call site
(``kernel_cache_info()`` exposes hit/miss counts for the retrace tests).

Rank packing: ``r ≪ 128`` leaves the MXU/VPU lanes mostly idle (the minor
dim is padded to a full 128-lane tile on real TPUs).  For the elementwise
``subspace_adam`` the dispatcher therefore *packs* the flattened
``(rows, r)`` state into a lane-aligned ``(rows/s, s·r_pad)`` multi-slot
buffer (``s·r_pad == 128``): one full-lane kernel launch per group instead
of an r-lane-starved one.  The static plan (:class:`PackSpec`) is computed
once at ``subspace.init`` and carried in ``SubspaceLayout.packs``.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import ref
from ._mixed import dotf as _dot32
from .lowrank_backward import lowrank_backward as _pl_backward
from .lowrank_forward import lowrank_forward as _pl_forward
from .lowrank_update import lowrank_merge as _pl_merge
from .lowrank_update import lowrank_merge_sr as _pl_merge_sr
from .lowrank_update import lowrank_project as _pl_project
from .subspace_adam import subspace_adam as _pl_adam
from .subspace_adam import subspace_adam_q8 as _pl_adam_q8
from .subspace_adam import subspace_lion as _pl_lion
from .subspace_adam import subspace_lion_q8 as _pl_lion_q8

Array = jax.Array

LANE = 128           # TPU lane count: minor-dim tiling granularity
SUBLANE = 16         # sublane granularity (16 covers bf16; 8 would do f32)
VMEM_BUDGET = 12 * 2 ** 20   # the blocks' slice of the 16 MiB scoped VMEM


def _interpret() -> bool:
    """Off the TPU the Pallas kernels run in ``interpret=True`` mode (the
    kernel body executes eagerly in Python).  Decided by the backend
    alone, so a chip run always compiles to Mosaic and never times the
    interpreter."""
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad2(a: Array, rows: int, cols: int) -> Array:
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr == 0 and pc == 0:
        return a
    return jnp.pad(a, ((0, pr), (0, pc)))


def _blocks(M: int, N: int, K: Optional[int] = None):
    """Block sizes + padded dims for (M, N[, K]) with ragged-shape pad."""
    bm = min(128, _round_up(M, SUBLANE))
    bn = min(128, _round_up(N, LANE))
    out = [bm, _round_up(M, bm), bn, _round_up(N, bn)]
    if K is not None:
        bk = min(128, _round_up(K, LANE))
        out += [bk, _round_up(K, bk)]
    return out


# ---------------------------------------------------------------------------
# Route selection (dtype-aware VMEM estimates)
# ---------------------------------------------------------------------------

def _itemsize(d) -> float:
    """Effective bytes/element of one operand descriptor.

    A plain dtype sizes as itself.  A block-quantized operand is
    described as ``(payload_dtype, block)`` — e.g. ``("int8", 128)`` —
    and sizes as the int8 payload plus one fp32 scale per ``block``
    elements (1.03125 B/elt at block 128), NOT the 4-byte fp32 fallback:
    without this the VMEM guard over-counts int8 workloads ~4x and
    spuriously kicks them off the Pallas route at larger shapes (the
    same class of bug the PR 5 bf16 itemsize fix addressed).
    """
    if isinstance(d, tuple):
        payload, block = d
        return jnp.dtype(payload).itemsize + 4.0 / float(block)
    return float(jnp.dtype(d).itemsize)


def _sizes(dtypes: Sequence, n: int, itemsize: int) -> Tuple[float, ...]:
    """Per-operand effective itemsizes; ``itemsize`` fallback."""
    if dtypes:
        out = tuple(_itemsize(d) for d in dtypes)
        if len(out) == n:
            return out
    return (float(itemsize),) * n


def _fwd_tile_bytes(bm: int, bn: int, bk: int, r: int, sizes) -> int:
    """Working set of one forward grid step at blocks (bm, bn, bk).

    Per-operand itemsizes: (x, w, v, b) — y/p accumulators are fp32.
    Pipelined blocks are double-buffered, the scratch is single."""
    sx, sw, sv, sb = sizes
    blocks = (bm * bk * sx + bk * bn * sw + bk * r * sv + bn * r * sb
              + bm * bn * sx)               # + y output tile (x.dtype)
    return 2 * blocks + 4 * (bm * bn + bm * r)   # + f32 acc/accp scratch


def _bwd_tile_bytes(bm: int, bn: int, bk: int, r: int, sizes) -> int:
    """Working set of one fused-backward grid step at blocks (bm, bn, bk):
    bn blocks N (the contraction of dx) and bk blocks K (dx's columns).

    Per-operand itemsizes: (dy, w, v, b, p) — the dx tile rides dy's
    dtype, the dx accumulator and q are fp32.  Pipelined blocks are
    double-buffered, the scratch is single.  The resident dB (4 N r
    bytes) is not a block: :data:`BWD_DB_VMEM` bounds it."""
    sdy, sw, sv, sb, sp = sizes
    blocks = (bm * bn * sdy + bk * bn * sw + bk * r * sv + bn * r * sb
              + bm * r * sp
              + bm * bk * sdy)              # + dx output tile (dy.dtype)
    return 2 * blocks + 4 * (bm * bk + bm * r)   # + f32 acc and q scratch


# largest blocks (bm, bn, bk) of the fused forward and backward: on a v5e
# sweep of the forward at the 12B widths (PERF.md) they ran at 85-90% of
# the bf16 peak, the fastest block that fits VMEM_BUDGET or within 0.4%
# of it; every backward shape of those widths reaches them too
MAX_BLOCKS = (512, 1024, 1024)
# the fused backward's resident fp32 dB (N, r), asked of Mosaic on top of
# the scoped VMEM that the blocks share: a v5e holds 128 MiB of VMEM
BWD_DB_VMEM = 96 * 2 ** 20


def _divisors(n: int, step: int, cap: int) -> list:
    """Multiples of ``step`` that divide ``n`` (itself a multiple of
    ``step``), up to ``cap``, largest first."""
    return [b for b in range(min(n, cap), 0, -1)
            if b % step == 0 and n % b == 0]


def _pick_blocks(M: int, K: int, N: int, tile_bytes):
    """(bm, Mp, bn, Np, bk, Kp) of a K-tiled low-rank kernel.

    The padded dims are :func:`_blocks`' (sublane 16 for M, lane 128 for
    N and K), the same as the other ops'.  Within them the blocks are the
    largest ``bm`` (a multiple of 16), ``bn`` and ``bk`` (multiples of
    128) that divide the padded dims, stay under ``MAX_BLOCKS`` and keep
    ``tile_bytes(bm, bn, bk)`` inside ``VMEM_BUDGET``: fewest grid steps
    first, and on a tie the wider ``bn``/``bk``.  A shape that fits no
    candidate keeps the 128-blocks of :func:`_blocks`, and the route
    guard sends it to XLA.
    """
    bm, Mp, bn, Np, bk, Kp = _blocks(M, N, K)
    cm, cn, ck = MAX_BLOCKS
    best = None
    for tn in _divisors(Np, LANE, cn):
        for tk in _divisors(Kp, LANE, ck):
            for tm in _divisors(Mp, SUBLANE, cm):
                if tile_bytes(tm, tn, tk) > VMEM_BUDGET:
                    continue
                key = ((Mp // tm) * (Np // tn) * (Kp // tk), -min(tn, tk))
                if best is None or key < best[0]:
                    best = (key, (tm, tn, tk))
                break       # the largest bm that fits, for this bn, bk
    if best is not None:
        bm, bn, bk = best[1]
    return bm, Mp, bn, Np, bk, Kp


def _fwd_blocks(M: int, K: int, N: int, r: int, sizes):
    """(bm, Mp, bn, Np, bk, Kp) of the fused forward; bk blocks the
    contraction K."""
    return _pick_blocks(
        M, K, N, lambda bm, bn, bk: _fwd_tile_bytes(bm, bn, bk, r, sizes))


def _bwd_blocks(M: int, K: int, N: int, r: int, sizes):
    """(bm, Mp, bn, Np, bk, Kp) of the fused backward; bn blocks the
    contraction N of dx."""
    return _pick_blocks(
        M, K, N, lambda bm, bn, bk: _bwd_tile_bytes(bm, bn, bk, r, sizes))


def _fwd_vmem_bytes(M: int, K: int, N: int, r: int, sizes) -> int:
    """Working set of the fused forward at the blocks that
    :func:`_fwd_blocks` picks for the shape: the kernel that runs."""
    bm, _, bn, _, bk, _ = _fwd_blocks(M, K, N, r, sizes)
    return _fwd_tile_bytes(bm, bn, bk, r, sizes)


def _bwd_vmem_bytes(M: int, K: int, N: int, r: int, sizes) -> int:
    """Working set of the fused backward at the blocks that
    :func:`_bwd_blocks` picks for the shape: the kernel that runs."""
    bm, _, bn, _, bk, _ = _bwd_blocks(M, K, N, r, sizes)
    return _bwd_tile_bytes(bm, bn, bk, r, sizes)


_ROUTES: dict = {}


def route(op: str, *, shapes: Tuple[int, ...] = (),
          dtypes: Sequence = (), itemsize: int = 4,
          fits: bool = True) -> str:
    """Pick 'pallas' or 'xla' for ``op`` and record the choice in
    :func:`route_log` (keyed by ``(op, shapes)``).  ``fits=False``: the
    caller's arguments are outside what the kernel computes, whatever
    the shapes — the call takes XLA, the override included."""
    rt = _route(op, shapes, dtypes, itemsize, fits)
    _ROUTES[(op, tuple(shapes))] = rt
    return rt


def _scope(op: str, rt: str):
    """Name the routed op's work in the compiled program's op metadata
    (and so in a profiler trace): ``repro.<op>.<route>``."""
    return jax.named_scope(f"repro.{op}.{rt}")


def route_log() -> dict:
    """``{(op, shapes): route}`` for every routed call since the last
    :func:`clear_kernel_cache` — which ops took the kernel and which the
    XLA fallback (decided at trace time)."""
    return dict(_ROUTES)


def _route(op: str, shapes: Tuple[int, ...], dtypes: Sequence,
           itemsize: int, fits: bool = True) -> str:
    """Pick 'pallas' or 'xla' for ``op`` given (M, K, N, r)-style shapes.

    ``dtypes``: the op's operand dtypes in call order — the VMEM guard
    sizes each operand with its real itemsize (a bf16 working set is half
    the fp32 one; without this, bf16 workloads were spuriously routed to
    the XLA fallback).  ``itemsize`` is the uniform fallback when the
    caller has no dtypes at hand.  A call the kernel cannot compute
    (``fits`` False) takes XLA before the override is read: the low-rank
    kernels pad any shape, the attention kernel does not.
    """
    env = os.environ.get("REPRO_KERNEL_DISPATCH", "auto")
    if env not in ("pallas", "xla", "auto", ""):
        raise ValueError(
            f"REPRO_KERNEL_DISPATCH={env!r}: expected pallas, xla or auto")
    if not fits:
        return "xla"
    if env in ("pallas", "xla"):
        return env
    if jax.default_backend() != "tpu":
        return "xla"        # interpret-mode Pallas is a debug tool, not a path
    from ..sharding import ctx   # deferred: sharding imports optim
    mesh = ctx.get_mesh()
    if mesh is not None and mesh.size > 1:
        # the compiler cannot partition a Mosaic kernel across devices: a
        # kernel inside a sharded program needs a shard_map, which these
        # ops do not have yet — on a mesh every op takes the XLA schedule
        return "xla"
    if op == "lowrank_forward" and shapes:
        m, k, n, r = shapes
        sz = _sizes(dtypes, 4, itemsize)
        if r > 512 or _fwd_vmem_bytes(m, k, n, r, sz) > VMEM_BUDGET:
            return "xla"
    if op == "lowrank_backward" and shapes:
        m, k, n, r = shapes
        if _bwd_vmem_bytes(m, k, n, r, _sizes(dtypes, 5, itemsize)) \
                > VMEM_BUDGET or 4 * _round_up(n, LANE) * r > BWD_DB_VMEM:
            return "xla"
    if op == "lowrank_batch_forward" and shapes:
        m, k, n, r = shapes   # m = per-row tokens (seq), not batch*seq
        sz = _sizes(dtypes, 4, itemsize)
        # decode-shaped calls (one token per row) pad every row to a full
        # sublane tile in the vmapped kernel — the einsum schedule wins
        if m < SUBLANE or r > 512 or \
                _fwd_vmem_bytes(m, k, n, r, sz) > VMEM_BUDGET:
            return "xla"
    return "pallas"


# ---------------------------------------------------------------------------
# Kernel cache: one build/compile per (op, padded shape, dtypes, statics)
# ---------------------------------------------------------------------------

_KERNEL_CACHE: dict = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def _cached_kernel(op: str, key: tuple, build):
    """Memoised jitted Pallas wrapper for one padded-shape/dtype key.

    ``build()`` returns the array->array callable (block sizes and other
    statics already bound); it runs ONCE per key — every later call with
    the same padded shapes and dtypes reuses the jitted instance, so a
    3-outer-cycle run with ragged groups compiles each kernel exactly once
    per ``(op, padded shape, dtypes)`` (asserted in
    tests/test_mixed_precision.py).
    """
    full = (op,) + key
    fn = _KERNEL_CACHE.get(full)
    if fn is None:
        _CACHE_STATS["misses"] += 1
        fn = jax.jit(build())
        _KERNEL_CACHE[full] = fn
    else:
        _CACHE_STATS["hits"] += 1
    return fn


def kernel_cache_info() -> dict:
    return {**_CACHE_STATS, "size": len(_KERNEL_CACHE),
            "keys": tuple(_KERNEL_CACHE)}


def clear_kernel_cache() -> None:
    _KERNEL_CACHE.clear()
    _ROUTES.clear()
    _CACHE_STATS.update(hits=0, misses=0)


def _dt_names(*arrs) -> tuple:
    return tuple(jnp.dtype(a.dtype).name for a in arrs)


# ---------------------------------------------------------------------------
# Rank packing (lane-aligned multi-slot layout for small-r elementwise ops)
# ---------------------------------------------------------------------------

class PackSpec(NamedTuple):
    """Static plan packing a flattened ``(rows, r)`` state buffer into a
    lane-aligned ``(rows_pad / slots, slots * r_pad)`` multi-slot buffer.

    ``r_pad``: r zero-padded up to the next power-of-two divisor of 128;
    ``slots``: how many consecutive rows share one 128-wide lane tile
    (``slots * r_pad == 128``); ``rows_pad``: rows rounded up to a slots
    multiple.  ``slots == 1 and r_pad == r`` means packing is a no-op
    (r already lane-sized).  Elementwise semantics are unchanged — the
    zero padding updates to zero under Adam and is sliced away.
    """
    rows: int
    r: int
    r_pad: int
    slots: int
    rows_pad: int

    @property
    def is_noop(self) -> bool:
        return self.slots == 1 and self.r_pad == self.r \
            and self.rows_pad == self.rows


def rank_pack_plan(rows: int, r: int) -> PackSpec:
    """The lane-packing plan for a flattened (rows, r) elementwise buffer."""
    if r >= LANE or rows <= 0 or r <= 0:
        return PackSpec(rows, r, r, 1, rows)
    r_pad = 1
    while r_pad < r:
        r_pad *= 2
    slots = max(1, LANE // r_pad)
    return PackSpec(rows, r, r_pad, slots, _round_up(rows, slots))


def _rank_pack(a: Array, plan: PackSpec) -> Array:
    if plan.is_noop:
        return a
    a = jnp.pad(a, ((0, plan.rows_pad - plan.rows),
                    (0, plan.r_pad - plan.r)))
    return a.reshape(plan.rows_pad // plan.slots, plan.slots * plan.r_pad)


def _rank_unpack(a: Array, plan: PackSpec) -> Array:
    if plan.is_noop:
        return a
    a = a.reshape(plan.rows_pad, plan.r_pad)
    return a[:plan.rows, :plan.r]


# ---------------------------------------------------------------------------
# Pallas impls (pad-to-tile wrappers over the raw, cached kernels)
# ---------------------------------------------------------------------------

def _pallas_forward(x2: Array, w: Array, v: Array, b: Array,
                    return_p: bool):
    M, K = x2.shape
    N, r = w.shape[1], v.shape[1]
    bm, Mp, bn, Np, bk, Kp = _fwd_blocks(
        M, K, N, r, tuple(_itemsize(a.dtype) for a in (x2, w, v, b)))
    itp = _interpret()
    fn = _cached_kernel(
        "lowrank_forward",
        ((Mp, Kp, Np, r), _dt_names(x2, w, v, b), (bm, bn, bk),
         return_p, itp),
        lambda: (lambda xp, wp, vp, bp: _pl_forward(
            xp, wp, vp, bp, bm=bm, bn=bn, bk=bk, interpret=itp,
            return_p=return_p)))
    out = fn(_pad2(x2, Mp, Kp), _pad2(w, Kp, Np), _pad2(v, Kp, r),
             _pad2(b, Np, r))
    if not return_p:
        return out[:M, :N]
    y, p = out
    return y[:M, :N], p[:M]


def _pallas_backward(dy2: Array, w: Array, v: Array, b: Array, p2: Array):
    M, N = dy2.shape
    K, r = w.shape[0], v.shape[1]
    bm, Mp, bn, Np, bk, Kp = _bwd_blocks(
        M, K, N, r, tuple(_itemsize(a.dtype) for a in (dy2, w, v, b, p2)))
    itp = _interpret()
    fn = _cached_kernel(
        "lowrank_backward",
        ((Mp, Kp, Np, r), _dt_names(dy2, w, v, b, p2), (bm, bn, bk), itp),
        lambda: (lambda dyp, wp, vp, bp, pp: _pl_backward(
            dyp, wp, vp, bp, pp, bm=bm, bk=bk, bn=bn, interpret=itp)))
    dx, db = fn(_pad2(dy2, Mp, Np), _pad2(w, Kp, Np), _pad2(v, Kp, r),
                _pad2(b, Np, r), _pad2(p2, Mp, r))
    return dx[:M, :K], db[:N]


def _pallas_merge(w: Array, v: Array, b: Array) -> Array:
    K, N = w.shape
    r = v.shape[1]
    bk = min(256, _round_up(K, SUBLANE))
    bn = min(256, _round_up(N, LANE))
    Kp, Np = _round_up(K, bk), _round_up(N, bn)
    itp = _interpret()
    fn = _cached_kernel(
        "lowrank_merge",
        ((Kp, Np, r), _dt_names(w, v, b), (bk, bn), itp),
        lambda: (lambda wp, vp, bp: _pl_merge(
            wp, vp, bp, bk=bk, bn=bn, interpret=itp)))
    out = fn(_pad2(w, Kp, Np), _pad2(v, Kp, r), _pad2(b, Np, r))
    return out[:K, :N]


def _pallas_project(g: Array, v: Array) -> Array:
    K, N = g.shape
    r = v.shape[1]
    bk = min(256, _round_up(K, SUBLANE))
    bn = min(256, _round_up(N, LANE))
    Kp, Np = _round_up(K, bk), _round_up(N, bn)
    itp = _interpret()
    fn = _cached_kernel(
        "lowrank_project",
        ((Kp, Np, r), _dt_names(g, v), (bk, bn), itp),
        lambda: (lambda gp, vp: _pl_project(
            gp, vp, bn=bn, bk=bk, interpret=itp)))
    out = fn(_pad2(g, Kp, Np), _pad2(v, Kp, r))
    return out[:N]


def _pallas_adam(b2, g2, m2, v2, *, lr, step, beta1, beta2, eps, wd):
    rows, r = b2.shape
    blk = min(256, _round_up(rows, SUBLANE))
    rp = _round_up(rows, blk)
    itp = _interpret()
    fn = _cached_kernel(
        "subspace_adam",
        ((rp, r), _dt_names(b2, g2, m2, v2), blk,
         (beta1, beta2, eps, wd), itp),
        lambda: (lambda bp, gp, mp, vp, lr_, step_: _pl_adam(
            bp, gp, mp, vp, lr=lr_, step=step_, beta1=beta1, beta2=beta2,
            eps=eps, wd=wd, block=blk, interpret=itp)))
    padded = [_pad2(a, rp, r) for a in (b2, g2, m2, v2)]
    outs = fn(*padded, lr, step)
    return tuple(o[:rows] for o in outs)


def _pallas_lion(b2, g2, m2, *, lr, beta1, beta2, wd):
    rows, r = b2.shape
    blk = min(256, _round_up(rows, SUBLANE))
    rp = _round_up(rows, blk)
    itp = _interpret()
    fn = _cached_kernel(
        "subspace_lion",
        ((rp, r), _dt_names(b2, g2, m2), blk, (beta1, beta2, wd), itp),
        lambda: (lambda bp, gp, mp, lr_: _pl_lion(
            bp, gp, mp, lr=lr_, beta1=beta1, beta2=beta2, wd=wd,
            block=blk, interpret=itp)))
    padded = [_pad2(a, rp, r) for a in (b2, g2, m2)]
    outs = fn(*padded, lr)
    return tuple(o[:rows] for o in outs)


def _pallas_adam_q8(b2, g2, mq, ms, vq, vs, bits, *, lr, step,
                    beta1, beta2, eps, wd):
    R, L = b2.shape
    blk = min(256, _round_up(R, SUBLANE))
    rp = _round_up(R, blk)
    itp = _interpret()
    sr = bits is not None
    fn = _cached_kernel(
        "subspace_adam_q8",
        ((rp, L), _dt_names(b2, g2, mq, vq), blk,
         (beta1, beta2, eps, wd), sr, itp),
        lambda: (lambda bp, gp, mqp, msp, vqp, vsp, bitsp, lr_, step_:
                 _pl_adam_q8(bp, gp, mqp, msp, vqp, vsp, lr=lr_,
                             step=step_, beta1=beta1, beta2=beta2,
                             eps=eps, wd=wd, bits=bitsp, block=blk,
                             interpret=itp)))
    outs = fn(_pad2(b2, rp, L), _pad2(g2, rp, L), _pad2(mq, rp, L),
              _pad2(ms, rp, 1), _pad2(vq, rp, L), _pad2(vs, rp, 1),
              _pad2(bits, rp, L) if sr else None, lr, step)
    return tuple(o[:R] for o in outs)


def _pallas_lion_q8(b2, g2, mq, ms, bits, *, lr, beta1, beta2, wd):
    R, L = b2.shape
    blk = min(256, _round_up(R, SUBLANE))
    rp = _round_up(R, blk)
    itp = _interpret()
    sr = bits is not None
    fn = _cached_kernel(
        "subspace_lion_q8",
        ((rp, L), _dt_names(b2, g2, mq), blk, (beta1, beta2, wd), sr, itp),
        lambda: (lambda bp, gp, mqp, msp, bitsp, lr_:
                 _pl_lion_q8(bp, gp, mqp, msp, lr=lr_, beta1=beta1,
                             beta2=beta2, wd=wd, bits=bitsp, block=blk,
                             interpret=itp)))
    outs = fn(_pad2(b2, rp, L), _pad2(g2, rp, L), _pad2(mq, rp, L),
              _pad2(ms, rp, 1), _pad2(bits, rp, L) if sr else None, lr)
    return tuple(o[:R] for o in outs)


def _pallas_merge_sr(w: Array, v: Array, b: Array, bits: Array) -> Array:
    K, N = w.shape
    r = v.shape[1]
    bk = min(256, _round_up(K, SUBLANE))
    bn = min(256, _round_up(N, LANE))
    Kp, Np = _round_up(K, bk), _round_up(N, bn)
    itp = _interpret()
    fn = _cached_kernel(
        "lowrank_merge_sr",
        ((Kp, Np, r), _dt_names(w, v, b), (bk, bn), itp),
        lambda: (lambda wp, vp, bp, bitsp: _pl_merge_sr(
            wp, vp, bp, bitsp, bk=bk, bn=bn, interpret=itp)))
    out = fn(_pad2(w, Kp, Np), _pad2(v, Kp, r), _pad2(b, Np, r),
             _pad2(bits, Kp, Np))
    return out[:K, :N]


def _pallas_batch_forward(x: Array, w: Array, v: Array, b: Array) -> Array:
    """Per-row-adapter forward as a vmap over the cached 2-D kernel.

    x: (B, S, K); w: (K, N); v: (K, r); b: (B, N, r).  The batched launch
    reuses the SAME cached kernel instance as the shared-adapter forward
    (key = padded shape + dtypes), so tenant hot-swaps never retrace.
    """
    return jax.vmap(
        lambda x2, b2: _pallas_forward(x2, w, v, b2, return_p=False),
        in_axes=(0, 0))(x, b)


# ---------------------------------------------------------------------------
# XLA impls (the unfused reference schedule, fp32 accumulation)
# ---------------------------------------------------------------------------

def _xla_forward(x2: Array, w: Array, v: Array, b: Array, return_p: bool):
    p = _dot32(x2, v).astype(x2.dtype)
    y = (_dot32(x2, w)
         + _dot32(p.astype(jnp.float32), b.T.astype(jnp.float32))
         ).astype(x2.dtype)
    return (y, p) if return_p else y


def _xla_batch_forward(x: Array, w: Array, v: Array, b: Array) -> Array:
    p = jnp.einsum("bsk,kr->bsr", x, v,
                   preferred_element_type=jnp.float32)
    y = (jnp.einsum("bsk,kn->bsn", x, w,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bsr,bnr->bsn", p, b.astype(jnp.float32)))
    return y.astype(x.dtype)


def _xla_backward(dy2: Array, w: Array, v: Array, b: Array, p2: Array):
    q = _dot32(dy2, b)
    dx = (_dot32(dy2, w.T)
          + _dot32(q, v.T.astype(jnp.float32))).astype(dy2.dtype)
    db = jax.lax.dot_general(dy2, p2.astype(dy2.dtype), (((0,), (0,)),
                                                         ((), ())),
                             preferred_element_type=jnp.float32)
    return dx, db


def _xla_adam(b2, g2, m2, v2, *, lr, step, beta1, beta2, eps, wd):
    return ref.subspace_adam(b2, g2, m2, v2, lr=lr, beta1=beta1, beta2=beta2,
                             eps=eps, wd=wd, step=step)


def _xla_lion(b2, g2, m2, *, lr, beta1, beta2, wd):
    return ref.subspace_lion(b2, g2, m2, lr=lr, beta1=beta1, beta2=beta2,
                             wd=wd)


def _xla_adam_q8(b2, g2, mq, ms, vq, vs, bits, *, lr, step,
                 beta1, beta2, eps, wd):
    return ref.subspace_adam_q8(b2, g2, mq, ms, vq, vs, lr=lr, beta1=beta1,
                                beta2=beta2, eps=eps, wd=wd, step=step,
                                bits=bits)


def _xla_lion_q8(b2, g2, mq, ms, bits, *, lr, beta1, beta2, wd):
    return ref.subspace_lion_q8(b2, g2, mq, ms, lr=lr, beta1=beta1,
                                beta2=beta2, wd=wd, bits=bits)


TABLE = {
    "lowrank_forward": {"pallas": _pallas_forward, "xla": _xla_forward},
    "lowrank_batch_forward": {"pallas": _pallas_batch_forward,
                              "xla": _xla_batch_forward},
    "lowrank_backward": {"pallas": _pallas_backward, "xla": _xla_backward},
    "lowrank_merge": {"pallas": _pallas_merge, "xla": ref.lowrank_merge},
    "lowrank_merge_sr": {"pallas": _pallas_merge_sr,
                         "xla": ref.lowrank_merge_sr},
    "lowrank_project": {"pallas": _pallas_project,
                        "xla": ref.lowrank_project},
    "subspace_adam": {"pallas": _pallas_adam, "xla": _xla_adam},
    "subspace_adam_q8": {"pallas": _pallas_adam_q8, "xla": _xla_adam_q8},
    "subspace_lion": {"pallas": _pallas_lion, "xla": _xla_lion},
    "subspace_lion_q8": {"pallas": _pallas_lion_q8, "xla": _xla_lion_q8},
}


# ---------------------------------------------------------------------------
# Public ops (leading-dim handling + routing)
# ---------------------------------------------------------------------------

def lowrank_forward(x: Array, w: Array, v: Array, b: Array, *,
                    return_p: bool = False):
    """y = x W + (x V) B^T over arbitrary leading dims of x.

    ``return_p=True`` also returns p = x V (x.dtype — the only saved
    activation) for the backward residual.  Operands may be mixed-dtype
    (bf16 compute slices over fp32 masters); accumulation is fp32 and the
    outputs carry x.dtype.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N, r = w.shape[1], v.shape[1]
    x2 = x.reshape(-1, K)
    rt = route("lowrank_forward", shapes=(x2.shape[0], K, N, r),
               dtypes=(x.dtype, w.dtype, v.dtype, b.dtype))
    with _scope("lowrank_forward", rt):
        out = TABLE["lowrank_forward"][rt](x2, w, v, b, return_p)
    if not return_p:
        return out.reshape(lead + (N,))
    y, p = out
    return y.reshape(lead + (N,)), p.reshape(lead + (r,))


def lowrank_batch_forward(x: Array, w: Array, v: Array, b: Array) -> Array:
    """y[i] = x[i] W + (x[i] V) B[i]^T — one launch, one adapter per row.

    The multi-tenant serving op: ``x (batch, seq, k)`` against a shared
    base ``w (k, n)`` / projection ``v (k, r)`` and a *per-row* subspace
    stack ``b (batch, n, r)``.  The merge ``W + V B^T`` is never formed —
    each row's correction stays rank-r.  Accumulation is fp32; the output
    carries x.dtype.  Decode-shaped calls (seq < sublane) auto-route to
    the einsum schedule; larger seqs take the vmapped Pallas kernel.
    """
    if x.ndim != 3:
        raise ValueError(
            f"lowrank_batch_forward: x must be (batch, seq, k), got "
            f"{x.shape}")
    if b.ndim != 3 or b.shape[0] != x.shape[0]:
        raise ValueError(
            f"lowrank_batch_forward: b must be (batch, n, r) with batch "
            f"== x.shape[0]; got b {b.shape} vs x {x.shape}")
    B, S, K = x.shape
    N, r = w.shape[-1], v.shape[-1]
    rt = route("lowrank_batch_forward", shapes=(S, K, N, r),
               dtypes=(x.dtype, w.dtype, v.dtype, b.dtype))
    with _scope("lowrank_batch_forward", rt):
        return TABLE["lowrank_batch_forward"][rt](x, w, v, b)


def lowrank_backward(dy: Array, w: Array, v: Array, b: Array, p: Array):
    """(dx, db) for y = x W + (x V) B^T, from dy and the residual p = x V.

    dx has dy's leading dims + (K,) in dy.dtype; db is (N, r) fp32 with
    every leading (batch/seq) axis contracted.
    """
    N = dy.shape[-1]
    K, r = w.shape[0], v.shape[1]
    lead = dy.shape[:-1]
    dy2 = dy.reshape(-1, N)
    p2 = p.reshape(-1, r)
    rt = route("lowrank_backward", shapes=(dy2.shape[0], K, N, r),
               dtypes=(dy.dtype, w.dtype, v.dtype, b.dtype, p.dtype))
    with _scope("lowrank_backward", rt):
        dx, db = TABLE["lowrank_backward"][rt](dy2, w, v, b, p2)
    return dx.reshape(lead + (K,)), db


def lowrank_merge(w: Array, v: Array, b: Array) -> Array:
    """W + V B^T in fp32, any leading (expert/layer) dims, W.dtype out.

    V may be a reduced-precision draw and B the fp32 master — the delta
    accumulates in fp32 either way, so the stored weight never sees a
    double rounding.
    """
    rt = route("lowrank_merge", dtypes=(w.dtype, v.dtype, b.dtype))
    fn = TABLE["lowrank_merge"][rt]
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    with _scope("lowrank_merge", rt):
        return fn(w, v, b)


def lowrank_project(g: Array, v: Array) -> Array:
    """G^T V (N, r) fp32 — the Thm.-1 lift used by project-style baselines."""
    rt = route("lowrank_project", dtypes=(g.dtype, v.dtype))
    fn = TABLE["lowrank_project"][rt]
    for _ in range(g.ndim - 2):
        fn = jax.vmap(fn)
    with _scope("lowrank_project", rt):
        return fn(g, v)


def lowrank_merge_sr(w: Array, v: Array, b: Array, bits: Array) -> Array:
    """W + V B^T stochastically rounded into w's reduced dtype.

    Same contract as :func:`lowrank_merge` plus ``bits`` (w-shaped uint32
    uniform over [0, 2**16)) feeding the unbiased round — used when the
    stored master weights are bf16 so the once-per-K merge does not
    accumulate round-to-nearest bias across outer cycles.
    """
    rt = route("lowrank_merge_sr", dtypes=(w.dtype, v.dtype, b.dtype))
    fn = TABLE["lowrank_merge_sr"][rt]
    for _ in range(w.ndim - 2):
        fn = jax.vmap(fn)
    with _scope("lowrank_merge_sr", rt):
        return fn(w, v, b, bits)


def subspace_adam(b: Array, g: Array, m: Array, v: Array, *, lr, step,
                  beta1: float = 0.9, beta2: float = 0.999,
                  eps: float = 1e-8, wd: float = 0.0,
                  pack: Optional[PackSpec] = None):
    """Fused Adam on stacked subspace variables.

    b/m/v share shape (..., n, r) fp32 (masters/moments — never
    downcast); g may arrive in the compute dtype and is cast up in VMEM.
    Leading (group/expert) dims are folded into rows so ONE kernel launch
    covers a whole group of same-shape B leaves.  On the Pallas route a
    small rank (r < 128) is additionally *rank-packed* into a lane-aligned
    multi-slot buffer (see :class:`PackSpec`) so the launch uses full
    128-wide lanes; ``pack`` supplies the precomputed plan from
    ``SubspaceLayout.packs`` (derived on the fly when absent).  Returns
    (b', m', v') with the input shape.
    """
    shape = b.shape
    r = shape[-1]
    flat = [a.reshape(-1, r) for a in (b, g, m, v)]
    rt = route("subspace_adam",
               dtypes=(b.dtype, g.dtype, m.dtype, v.dtype))
    impl = TABLE["subspace_adam"][rt]
    plan = None
    if rt == "pallas":
        plan = pack if pack is not None else rank_pack_plan(
            flat[0].shape[0], r)
        if plan.rows != flat[0].shape[0] or plan.r != r:
            plan = rank_pack_plan(flat[0].shape[0], r)
        flat = [_rank_pack(a, plan) for a in flat]
    with _scope("subspace_adam", rt):
        nb, nm, nv = impl(*flat, lr=lr, step=step, beta1=beta1, beta2=beta2,
                          eps=eps, wd=wd)
    if plan is not None and not plan.is_noop:
        nb, nm, nv = (_rank_unpack(o, plan) for o in (nb, nm, nv))
    return nb.reshape(shape), nm.reshape(shape), nv.reshape(shape)


def subspace_lion(b: Array, g: Array, m: Array, *, lr,
                  beta1: float = 0.9, beta2: float = 0.99,
                  wd: float = 0.0, pack: Optional[PackSpec] = None):
    """Fused momentum-only Lion on stacked subspace variables.

    Same shape/packing contract as :func:`subspace_adam` minus the second
    moment: b/m (..., n, r) fp32, g any compute dtype.  Returns (b', m').
    """
    shape = b.shape
    r = shape[-1]
    flat = [a.reshape(-1, r) for a in (b, g, m)]
    rt = route("subspace_lion", dtypes=(b.dtype, g.dtype, m.dtype))
    impl = TABLE["subspace_lion"][rt]
    plan = None
    if rt == "pallas":
        plan = pack if pack is not None else rank_pack_plan(
            flat[0].shape[0], r)
        if plan.rows != flat[0].shape[0] or plan.r != r:
            plan = rank_pack_plan(flat[0].shape[0], r)
        flat = [_rank_pack(a, plan) for a in flat]
    with _scope("subspace_lion", rt):
        nb, nm = impl(*flat, lr=lr, beta1=beta1, beta2=beta2, wd=wd)
    if plan is not None and not plan.is_noop:
        nb, nm = (_rank_unpack(o, plan) for o in (nb, nm))
    return nb.reshape(shape), nm.reshape(shape)


# --- int8 block-quantized state --------------------------------------------
#
# Quantized state replaces rank packing with an even simpler lane layout:
# the WHOLE flattened buffer is tiled into (R, qblock) rows — one
# quantization block per 128-lane row (qblock defaults to LANE), trivially
# lane-aligned for any rank.  The public wrappers take LOGICAL shapes
# (b/g/mq/vq match the state's (..., n, r); ms/vs are the flat (R,) scale
# vectors quant.quantize produces) and own the tiling both ways.

def _to_blocks(a: Array, R: int, L: int) -> Array:
    flat = a.reshape(-1)
    pad = R * L - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(R, L)


def subspace_adam_q8(b: Array, g: Array, mq: Array, ms: Array,
                     vq: Array, vs: Array, *, lr, step,
                     beta1: float = 0.9, beta2: float = 0.999,
                     eps: float = 1e-8, wd: float = 0.0,
                     qblock: int = LANE, bits: Optional[Array] = None):
    """Fused Adam with int8 block-quantized moments.

    b/g/mq/vq share the logical state shape (..., n, r) — b the fp32 or
    bf16 master, g any compute dtype, mq/vq int8; ms/vs are (R,) fp32
    absmax scales (R = ceil(size / qblock)).  ``bits`` (b-shaped uint32)
    enables fused stochastic rounding of b' into b.dtype.  The dequant ->
    fp32 update -> requant round-trip runs inside the kernel, so the fp32
    moments exist only in VMEM.  Returns (b', mq', ms', vq', vs').
    """
    shape = b.shape
    size = b.size
    R = max(1, -(-size // qblock))
    rt = route("subspace_adam_q8",
               dtypes=(b.dtype, g.dtype, ("int8", qblock),
                       ("int8", qblock)))
    impl = TABLE["subspace_adam_q8"][rt]
    with _scope("subspace_adam_q8", rt):
        nb, nmq, nms, nvq, nvs = impl(
            _to_blocks(b, R, qblock), _to_blocks(g, R, qblock),
            _to_blocks(mq, R, qblock), ms.reshape(R, 1),
            _to_blocks(vq, R, qblock), vs.reshape(R, 1),
            _to_blocks(bits, R, qblock) if bits is not None else None,
            lr=lr, step=step, beta1=beta1, beta2=beta2, eps=eps, wd=wd)

    def unflat(a):
        return a.reshape(-1)[:size].reshape(shape)

    return (unflat(nb), unflat(nmq), nms.reshape(R),
            unflat(nvq), nvs.reshape(R))


def subspace_lion_q8(b: Array, g: Array, mq: Array, ms: Array, *, lr,
                     beta1: float = 0.9, beta2: float = 0.99,
                     wd: float = 0.0, qblock: int = LANE,
                     bits: Optional[Array] = None):
    """Fused Lion with int8 block-quantized momentum — the
    :func:`subspace_adam_q8` contract minus v.  Returns (b', mq', ms')."""
    shape = b.shape
    size = b.size
    R = max(1, -(-size // qblock))
    rt = route("subspace_lion_q8",
               dtypes=(b.dtype, g.dtype, ("int8", qblock)))
    impl = TABLE["subspace_lion_q8"][rt]
    with _scope("subspace_lion_q8", rt):
        nb, nmq, nms = impl(
            _to_blocks(b, R, qblock), _to_blocks(g, R, qblock),
            _to_blocks(mq, R, qblock), ms.reshape(R, 1),
            _to_blocks(bits, R, qblock) if bits is not None else None,
            lr=lr, beta1=beta1, beta2=beta2, wd=wd)

    def unflat(a):
        return a.reshape(-1)[:size].reshape(shape)

    return unflat(nb), unflat(nmq), nms.reshape(R)
