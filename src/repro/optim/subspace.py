"""LowRankLazyAdam — the paper's Algorithm 1 as a production optimizer.

Two-level structure:
  * INNER step (hot loop, runs K times per outer iteration): Adam on the
    subspace variables ``B in R^{n_out x r}`` of every low-rank leaf plus
    dense Adam on everything else (norm scales, biases, routers, SSM
    scalars).  Gradients w.r.t. B are produced by autodiff through the
    LRPack path of :mod:`repro.models.linear` — the full ``k x n_out``
    gradient is never materialised, and the DP all-reduce carries ``n_out*r``
    floats instead of ``k*n_out``.
  * OUTER step (every K steps): merge ``W += V B^T`` in fp32, resample V
    (stiefel / coordinate / gaussian / dependent_diag per Section 5),
    zero B, reset (or project) the subspace moments.

State layout — structure-of-arrays:
  The subspace state is NOT one slot per param leaf.  All low-rank leaves
  with the same weight shape and rank form a *group*, and the group's
  B/m/v are stored pre-stacked as one ``(G,) + lead + (n_out, r)`` array
  (V as ``(G,) + lead + (k, r)``, energy as ``(G, k)``) — exactly the
  batched shape the Pallas subspace-Adam and merge kernels consume.  The
  inner step therefore issues ZERO per-leaf stack/gather work: each group
  feeds :func:`repro.kernels.dispatch.subspace_adam` directly, and
  :func:`packed_params` scatters ``B[g]`` / ``V[g]`` slices into the
  model-facing tree lazily (slices of the stacked buffer, not copies).
  The index map from groups back to the param tree lives in a static
  :class:`SubspaceLayout` carried as pytree *metadata* (aux data), so it
  never turns into traced state and jit/donation see only the arrays.

Master-weight layout — grouped end-to-end:
  The master weights mirror the state: :class:`GroupedParams` keeps every
  group's member weights pre-stacked as one ``(G,) + lead + (k, n_out)``
  buffer (non-grouped leaves pass through untouched in ``dense``), built
  once by :func:`init` and carried through the whole training loop.
  ``outer_merge_resample`` is then a pure batched ``W += V B^T`` on the
  already-stacked buffer — zero per-leaf stack/unstack anywhere in the
  outer step — and the inner step / loss eval consume weight *slices*
  exactly the way :func:`packed_params` already slices B/V.
  :func:`params_of` rebuilds the model-shaped tree at the API boundary
  (checkpoint templates, serving, introspection); the per-leaf ``_ref``
  functions at the bottom of this module work on that tree and are the
  test references.

Mixed precision (the ``compute_dtype`` knob, default bf16 on TPU/GPU):
  The layout pins a compute dtype (``SubspaceLayout.compute_dtype``).  V
  buffers are *stored* in it (drawn fp32, cast once per resample) and
  :func:`packed_params` casts the B and W slices to it, so the fused
  forward/backward and the merge read half-width operands with fp32
  accumulators.  B masters, Adam moments, dense weights and the grouped
  master-weight buffers are NEVER downcast — asserted by jaxpr/aval
  inspection in tests/test_mixed_precision.py.  Small-rank groups are
  additionally rank-packed (``SubspaceLayout.packs``) into lane-aligned
  multi-slot buffers before the batched subspace-Adam launch.

Leaf classification:
  * 2-D weights with min(dim) >= min_dim_for_lowrank and not name-excluded
    -> low-rank; convention W (k, n_out): V (k, r), B (n_out, r),
    effective weight W + V B^T.
  * 3-D stacked expert weights (E, k, n_out) -> per-expert V (E, k, r),
    B (E, n_out, r) (batched sampler over the folded leading dims).
  * everything else -> DenseSlot (plain AdamW).

For ``dependent_diag`` (the LLM-scale instance-dependent mode of DESIGN.md
§7.4) each group carries an EMA estimate of diag(Sigma) over the input
dimension per member leaf, updated from subspace gradients at O(k r^2):
  diag(V dB^T dB V^T)_i = ((V M) * V).sum(-1),  M = dB^T dB.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..core import samplers
from ..kernels import dispatch, ref
from ..kernels._mixed import sr_bf16
from ..models.common import (DTYPES, resolve_compute_dtype,
                             resolve_master_dtype, resolve_state_dtype)
from ..models.linear import LRPack
from . import quant
from .adamw import clip_by_global_norm

Array = jax.Array

EXCLUDE_DEFAULT = r"(/embed/|/tok$|/pos$|router|conv_w)"


class DenseSlot(NamedTuple):
    m: Array
    v: Array


class LowRankSlot(NamedTuple):
    """Per-leaf VIEW of one group member (legacy layout).

    Only used at the edges: checkpoint migration from pre-grouped
    checkpoints, tests, and introspection via :func:`leaf_slots` — the hot
    path never materialises these.
    """
    proj: Array       # V: (k, r) or (E, k, r) — fixed within an outer iter
    b: Array          # (n_out, r) or (E, n_out, r), fp32
    m: Array          # Adam moments over b
    v: Array
    energy: Array     # (k,) EMA of diag(Sigma) (dependent_diag) or (0,)


class GroupedLowRankSlot(NamedTuple):
    """All same-shape low-rank leaves of one group, pre-stacked.

    ``proj``: (G,) + lead + (k, r); ``b``/``m``/``v``: (G,) + lead +
    (n_out, r); ``energy``: (G, k) fp32 (or (G, 0) when the sampler
    carries no energy EMA).  Axis 0 indexes group members in the order of
    the layout's ``leaf_idx``.

    Storage dtypes follow the layout: ``b`` is fp32 or (``master_dtype=
    "bfloat16"``, stochastically-rounded updates) bf16; ``m``/``v`` are
    fp32 arrays or (``state_dtype="int8"``) block-quantized
    :class:`repro.optim.quant.QuantizedTensor` nodes.  Under the
    momentum-only lion algorithm ``v`` is a zero-size ``(G,)+lead+(0, r)``
    placeholder (rank-consistent so sharding pspecs stay uniform).
    """
    proj: Array
    b: Array
    m: Any
    v: Any
    energy: Array


class GroupSpec(NamedTuple):
    """Static description of one group (hashable pytree metadata)."""
    shape: Tuple[int, ...]      # the member weight shape lead + (k, n_out)
    rank: int
    leaf_idx: Tuple[int, ...]   # member positions in params flat-leaf order


class SubspaceLayout(NamedTuple):
    """Static index map param-tree <-> grouped state (pytree metadata).

    ``compute_dtype`` (canonical name, e.g. ``"bfloat16"``) is the hot-path
    compute precision this layout was built for: V buffers are *stored* in
    it and the packed B/W slices are cast to it per step, while B masters,
    Adam moments and master weights stay fp32/param-dtype.  ``packs`` holds
    one static :class:`repro.kernels.dispatch.PackSpec` per group — the
    lane-aligned rank-packing plan the batched subspace-Adam launches use
    for small ranks (computed once here, never re-derived per step).
    """
    n_leaves: int
    dense_idx: Tuple[int, ...]
    groups: Tuple[GroupSpec, ...]
    compute_dtype: str = "float32"
    packs: Tuple[dispatch.PackSpec, ...] = ()
    # storage precision of the grouped optimizer state (new fields carry
    # defaults so pre-existing layouts/pickles keep their meaning):
    state_dtype: str = "float32"    # m/v moments: 'float32' | 'int8'
    master_dtype: str = "float32"   # B masters:   'float32' | 'bfloat16'
    qblock: int = quant.QBLOCK      # elements per int8 absmax scale block
    algo: str = "adam"              # subspace update rule: 'adam' | 'lion'


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "groups", "step", "outer_step", "key"),
    meta_fields=("layout",))
@dataclasses.dataclass(frozen=True)
class SubspaceState:
    dense: Tuple[DenseSlot, ...]           # one per dense leaf (layout order)
    groups: Tuple[GroupedLowRankSlot, ...]  # one per group (layout order)
    step: Array
    outer_step: Array
    key: Array
    layout: SubspaceLayout                 # static aux data, not traced


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("dense", "groups"),
    meta_fields=("layout", "treedef"))
@dataclasses.dataclass(frozen=True)
class GroupedParams:
    """Master weights in the grouped structure-of-arrays layout.

    ``groups[g]``: the g-th group's member weights pre-stacked as
    ``(G,) + lead + (k, n_out)`` (axis 0 in ``leaf_idx`` order — the same
    stacking as :class:`GroupedLowRankSlot`); ``dense``: the non-grouped
    leaves in ``layout.dense_idx`` order, untouched.  ``treedef`` (the
    original model tree structure) and ``layout`` ride as static pytree
    metadata so jit/donation see only the arrays.
    """
    dense: Tuple[Array, ...]
    groups: Tuple[Array, ...]
    layout: SubspaceLayout
    treedef: Any


class Trainable(NamedTuple):
    """The differentiation tree: stacked B per group, W per dense leaf."""
    dense: Tuple[Array, ...]
    groups: Tuple[Array, ...]


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
    return "/" + "/".join(out)


def is_lowrank_leaf(path: str, x, tcfg) -> bool:
    if re.search(getattr(tcfg, "lowrank_exclude", EXCLUDE_DEFAULT), path):
        return False
    if x.ndim == 2:
        return min(x.shape) >= tcfg.min_dim_for_lowrank
    if x.ndim == 3:  # stacked experts (E, k, n_out)
        return min(x.shape[1:]) >= tcfg.min_dim_for_lowrank
    if x.ndim == 4:  # scan-stacked experts (L, E, k, n_out)
        return min(x.shape[2:]) >= tcfg.min_dim_for_lowrank
    return False


def _rank_for(shape, tcfg) -> int:
    k, n_out = shape[-2], shape[-1]
    return max(1, min(tcfg.rank, min(k, n_out) // 2))


def _pack_for(spec: GroupSpec) -> dispatch.PackSpec:
    """Static rank-packing plan for one group's flattened B/m/v buffer."""
    rows = len(spec.leaf_idx)
    for d in spec.shape[:-2]:
        rows *= d
    rows *= spec.shape[-1]          # n_out rows per member
    return dispatch.rank_pack_plan(rows, spec.rank)


def build_layout(params, tcfg, algo: str = "adam",
                 quantize_state: bool = True) -> SubspaceLayout:
    """Classify leaves once; same-shape/same-rank low-rank leaves share a
    group.  Pure Python over shapes — safe under jax.eval_shape.  The
    layout also pins the run's compute dtype (resolved from
    ``tcfg.compute_dtype`` / REPRO_COMPUTE_DTYPE / the backend), the
    optimizer-state storage precision (``tcfg.state_dtype`` /
    REPRO_STATE_DTYPE and ``tcfg.master_dtype`` / REPRO_MASTER_DTYPE),
    the update rule (``algo``) and each group's rank-packing plan.

    ``quantize_state=False`` pins fp32 storage regardless of the
    ``state_dtype`` / ``master_dtype`` knobs — the opt-out for paradigms
    (GaLore) whose moment math runs in plain XLA rather than through the
    fused dequant-in-VMEM q8 kernels."""
    if algo not in ("adam", "lion"):
        raise ValueError(f"algo {algo!r}: expected 'adam' or 'lion'")
    leaves = jax.tree_util.tree_flatten_with_path(params_of(params))[0]
    dense_idx = []
    by_sig: dict = {}
    for i, (path, x) in enumerate(leaves):
        ps = _path_str(path)
        if is_lowrank_leaf(ps, x, tcfg):
            sig = (tuple(int(d) for d in x.shape), _rank_for(x.shape, tcfg))
            by_sig.setdefault(sig, []).append(i)
        else:
            dense_idx.append(i)
    groups = tuple(GroupSpec(shape=sig[0], rank=sig[1], leaf_idx=tuple(idx))
                   for sig, idx in by_sig.items())
    cdt = jnp.dtype(resolve_compute_dtype(tcfg)).name
    return SubspaceLayout(n_leaves=len(leaves), dense_idx=tuple(dense_idx),
                          groups=groups, compute_dtype=cdt,
                          packs=tuple(_pack_for(s) for s in groups),
                          state_dtype=(resolve_state_dtype(tcfg)
                                       if quantize_state else "float32"),
                          master_dtype=(resolve_master_dtype(tcfg)
                                        if quantize_state else "float32"),
                          qblock=quant.QBLOCK, algo=algo)


# ---------------------------------------------------------------------------
# Sampling (grouped: one batched draw per group; per-leaf kept for the
# reference path and checkpoint migration)
# ---------------------------------------------------------------------------

def _sample_v(name, key, k_dim, r, c, energy=None, dtype=jnp.float32):
    if name == "dependent_diag":
        e = jnp.where(jnp.sum(energy) > 0, energy,
                      jnp.ones_like(energy))  # warm-up: uniform == coordinate
        return samplers.dependent_diagonal(key, e, r, c=c, dtype=dtype)
    return samplers.sample_v(name, key, k_dim, r, c=c, dtype=dtype)


def _sample_proj(name, key, shape, r, c, energy, dtype=jnp.float32):
    """Per-leaf V for a (k, n_out) leaf or per-expert for stacked leading
    dims (reference path only — the hot path uses :func:`_sample_proj_group`)."""
    lead = shape[:-2]
    k_dim = shape[-2]
    if not lead:
        return _sample_v(name, key, k_dim, r, c, energy, dtype)
    n = 1
    for d in lead:
        n *= d
    keys = jax.random.split(key, n)
    if name == "dependent_diag":
        vs = jax.vmap(lambda kk: _sample_v(name, kk, k_dim, r, c, energy,
                                           dtype))(keys)
    else:
        vs = jax.vmap(lambda kk: _sample_v(name, kk, k_dim, r, c, None,
                                           dtype))(keys)
    return vs.reshape(lead + (k_dim, r))


def _sample_proj_group(name, key, spec: GroupSpec, n_members: int, c,
                       energy, dtype=jnp.float32):
    """One batched draw for a whole group: (G,) + lead + (k, r).

    Leading expert/layer dims fold into the sample batch; for
    ``dependent_diag`` each member's (k,) energy row is repeated across its
    own leading dims (one EMA per leaf, as in the per-leaf layout).

    Shard locality: every batched sampler splits ``key`` once per batch
    row and vmaps the single draw (see ``core.samplers``), so row g of
    the result depends only on keys[g] (+ energy row g).  Under the
    G-sharded layout of ``sharding.rules`` each device therefore draws
    exactly its local ``(G-shard) + lead`` slice of V in place — the
    resample never all-gathers V or the energy EMA.
    """
    lead = spec.shape[:-2]
    k_dim = spec.shape[-2]
    lead_n = 1
    for d in lead:
        lead_n *= d
    batch = n_members * lead_n
    kw = {}
    if name == "dependent_diag":
        e = jnp.where(jnp.sum(energy, axis=-1, keepdims=True) > 0, energy,
                      jnp.ones_like(energy))      # per-member warm-up
        kw["diag_energy"] = jnp.repeat(e, lead_n, axis=0) if lead_n > 1 else e
    v = samplers.sample_v_batched(name, key, batch, k_dim, spec.rank, c=c,
                                  dtype=dtype, **kw)
    return v.reshape((n_members,) + lead + (k_dim, spec.rank))


def _moment_zeros(shape, layout: SubspaceLayout, codec: str = "linear"):
    """A zeroed grouped moment buffer in the layout's storage precision.
    Second moments use the sqrt codec (see :mod:`repro.optim.quant`)."""
    if layout.state_dtype == "int8":
        return quant.zeros(shape, layout.qblock, codec=codec)
    return jnp.zeros(shape, jnp.float32)


def _init_state(params, tcfg, key: Array, algo: str = "adam",
                quantize_state: bool = True) -> SubspaceState:
    """Classify leaves, build the grouped layout, sample initial
    projections (one batched draw per group), zero moments.

    Storage precision follows the layout: ``state_dtype="int8"`` makes the
    grouped m/v :class:`repro.optim.quant.QuantizedTensor` nodes,
    ``master_dtype="bfloat16"`` stores B narrow (updates stochastically
    rounded).  ``algo="lion"`` keeps only the first moment — v becomes a
    zero-size ``(G,)+lead+(0, r)`` placeholder.  Dense (non-grouped)
    slots stay plain fp32 either way: they are norm scales and biases,
    not the footprint."""
    layout = build_layout(params, tcfg, algo=algo,
                          quantize_state=quantize_state)
    cdt = DTYPES[layout.compute_dtype]
    mdt = DTYPES[layout.master_dtype]
    flat_p = jax.tree.leaves(params)
    keys = jax.random.split(key, len(layout.groups) + 1)
    dense = tuple(
        DenseSlot(m=jnp.zeros(flat_p[i].shape, jnp.float32),
                  v=jnp.zeros(flat_p[i].shape, jnp.float32))
        for i in layout.dense_idx)
    groups = []
    for g, spec in enumerate(layout.groups):
        lead = spec.shape[:-2]
        k_dim, n_out = spec.shape[-2], spec.shape[-1]
        n_members = len(spec.leaf_idx)
        energy = (jnp.zeros((n_members, k_dim), jnp.float32)
                  if tcfg.sampler == "dependent_diag"
                  else jnp.zeros((n_members, 0), jnp.float32))
        # V is stored in the compute dtype (drawn in fp32, cast once):
        # it is re-sampled every outer iteration, so reduced-precision
        # storage costs one rounding, never an accumulated drift.
        proj = _sample_proj_group(tcfg.sampler, keys[g], spec, n_members,
                                  tcfg.c, energy, dtype=cdt)
        bshape = (n_members,) + lead + (n_out, spec.rank)
        b = jnp.zeros(bshape, mdt)
        m = _moment_zeros(bshape, layout)
        if layout.algo == "lion":
            v = jnp.zeros((n_members,) + lead + (0, spec.rank), jnp.float32)
        else:
            v = _moment_zeros(bshape, layout, codec="sqrt")
        groups.append(GroupedLowRankSlot(
            proj=proj, b=b, m=m, v=v, energy=energy))
    return SubspaceState(dense=dense, groups=tuple(groups),
                         step=jnp.zeros((), jnp.int32),
                         outer_step=jnp.zeros((), jnp.int32),
                         key=keys[-1], layout=layout)


# ---------------------------------------------------------------------------
# Grouped master weights: build once, slice everywhere, ungroup only at the
# API boundary
# ---------------------------------------------------------------------------

def group_params(params, layout: SubspaceLayout) -> GroupedParams:
    """Stack each group's member weights into one ``(G,)+lead+(k, n)``
    buffer (ONE stack per group, at init time — the training loop never
    stacks again).  Non-grouped leaves pass through untouched."""
    flat_p, treedef = jax.tree.flatten(params)
    return GroupedParams(
        dense=tuple(flat_p[i] for i in layout.dense_idx),
        groups=tuple(jnp.stack([flat_p[i] for i in spec.leaf_idx])
                     for spec in layout.groups),
        layout=layout, treedef=treedef)


def params_of(params):
    """Model-shaped param tree from either representation.

    For a :class:`GroupedParams` the grouped leaves are *slices* of the
    stacked buffers (lazy under jit/eval_shape — no copy until a consumer
    materialises them); raw trees (AdamW's params) pass through
    unchanged.  This is the ungroup point for API boundaries (checkpoint
    templates, serving, introspection) — the training loop itself never
    calls it.
    """
    if not isinstance(params, GroupedParams):
        return params
    out: list = [None] * params.layout.n_leaves
    for di, i in enumerate(params.layout.dense_idx):
        out[i] = params.dense[di]
    for g, spec in enumerate(params.layout.groups):
        wg = params.groups[g]
        for j, i in enumerate(spec.leaf_idx):
            out[i] = wg[j]
    return jax.tree.unflatten(params.treedef, out)


def init(params, tcfg, key: Array,
         algo: str = "adam") -> Tuple[GroupedParams, SubspaceState]:
    """The trainer entry: classify the leaves of the model tree ``params``,
    build the grouped state AND the grouped master weights from the same
    layout.

    Returns ``(grouped_params, state)`` — the in-training representation
    pair (both structure-of-arrays, both donatable).
    """
    state = _init_state(params, tcfg, key, algo=algo)
    return group_params(params, state.layout), state


# ---------------------------------------------------------------------------
# Packing and trainable extraction
# ---------------------------------------------------------------------------

def trainable_of(params, state: SubspaceState) -> Trainable:
    """The differentiation tree: the stacked B buffer of every group plus
    the W of every dense leaf.  No copies — leaves are references."""
    return Trainable(dense=params.dense,
                     groups=tuple(g.b for g in state.groups))


def packed_params(params, state: SubspaceState, trainable: Trainable,
                  dtype=None):
    """Model-facing tree: LRPack(w, B[g], V[g]) at low-rank leaves, the
    trainable value at dense leaves.

    ``B[g]`` / ``V[g]`` / ``W[g]`` are *slices* of the group's stacked
    buffer (one cast per group, then static-index slices) — under jit
    these alias the donated group buffer instead of copying it.  The base
    ``w`` of each LRPack is a slice of the stacked weight buffer the same
    way.

    ``dtype`` is the compute dtype of the packed views: all three pack
    members (W, B, V) are cast to it so the fused forward/backward reads
    reduced-precision operands with fp32 accumulation; the fp32 B masters
    and the stored master weights themselves are untouched (the cast is a
    read-side view, autodiff routes the B cotangent back up to fp32).
    """
    cast = (lambda x: x.astype(dtype)) if dtype else (lambda x: x)
    out: list = [None] * state.layout.n_leaves
    for di, i in enumerate(state.layout.dense_idx):
        out[i] = trainable.dense[di]
    for g, spec in enumerate(state.layout.groups):
        tb = cast(trainable.groups[g])
        tv = cast(state.groups[g].proj)
        wg = cast(params.groups[g])
        for j, i in enumerate(spec.leaf_idx):
            out[i] = LRPack(wg[j], tb[j], tv[j])
    return jax.tree.unflatten(params.treedef, out)


def leaf_slots(state: SubspaceState) -> list:
    """Per-leaf slot views in params flat-leaf order (introspection/tests):
    LowRankSlot slices for grouped leaves, DenseSlot for the rest."""
    out: list = [None] * state.layout.n_leaves
    for di, i in enumerate(state.layout.dense_idx):
        out[i] = state.dense[di]
    for g, spec in enumerate(state.layout.groups):
        slot = state.groups[g]
        # quantized moments dequantize to their logical fp32 view here —
        # introspection sees values, not (payload, scale) pairs
        m, v = quant.as_f32(slot.m), quant.as_f32(slot.v)
        for j, i in enumerate(spec.leaf_idx):
            out[i] = LowRankSlot(proj=slot.proj[j], b=slot.b[j],
                                 m=m[j], v=v[j],
                                 energy=slot.energy[j])
    return out


def slots_by_path(params, state: SubspaceState) -> dict:
    """{'/path/to/leaf': per-leaf slot view} (introspection/tests)."""
    leaves = jax.tree_util.tree_flatten_with_path(params_of(params))[0]
    views = leaf_slots(state)
    return {_path_str(path): views[i] for i, (path, _) in enumerate(leaves)}


# ---------------------------------------------------------------------------
# Inner step (Algorithm 1, lines 5-6) — Adam over (B, dense) trainables
# ---------------------------------------------------------------------------

def _group_energy_update(slot: GroupedLowRankSlot, g32) -> Array:
    """dependent_diag: EMA of diag(Sigma) from subspace grads, O(k r^2),
    batched over the whole group (leading expert dims averaged per member)."""
    if not slot.energy.shape[-1]:
        return slot.energy
    proj32 = slot.proj.astype(jnp.float32)   # V may be stored bf16
    mm = jnp.einsum("...nr,...ns->...rs", g32, g32)
    e = jnp.einsum("...kr,...rs,...ks->...k", proj32, mm, proj32)
    if e.ndim > 2:  # (G,) + lead + (k,): average the stacked-expert dims
        e = e.mean(axis=tuple(range(1, e.ndim - 1)))
    return 0.99 * slot.energy + 0.01 * e


def _dense_adam(slot: DenseSlot, p, g, *, lr, bc1, bc2, tcfg):
    g32 = g.astype(jnp.float32)
    m = tcfg.beta1 * slot.m + (1 - tcfg.beta1) * g32
    v = tcfg.beta2 * slot.v + (1 - tcfg.beta2) * g32 * g32
    delta = (m / bc1) / (jnp.sqrt(v / bc2) + tcfg.eps)
    if tcfg.weight_decay and p.ndim >= 2:
        delta = delta + tcfg.weight_decay * p.astype(jnp.float32)
    new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
    return new_p, DenseSlot(m, v)


def _dense_lion(slot: DenseSlot, p, g, *, lr, tcfg):
    """Momentum-only Lion on a dense leaf.  The v buffer rides along
    zeroed (dense leaves are norm scales/biases — keeping the slot shape
    uniform costs nothing and keeps pspecs/checkpoints method-agnostic)."""
    g32 = g.astype(jnp.float32)
    u = jnp.sign(tcfg.beta1 * slot.m + (1 - tcfg.beta1) * g32)
    if tcfg.weight_decay and p.ndim >= 2:
        u = u + tcfg.weight_decay * p.astype(jnp.float32)
    new_p = (p.astype(jnp.float32) - lr * u).astype(p.dtype)
    m = tcfg.beta2 * slot.m + (1 - tcfg.beta2) * g32
    return new_p, DenseSlot(m, slot.v)


def _sr_bits(key, step, gi: int, shape):
    """Per-(step, group) uint16-in-uint32 rounding noise for bf16 master
    updates — keyed from the state's PRNG so every draw is fresh and the
    jitted step stays deterministic given (key, step)."""
    k = jax.random.fold_in(jax.random.fold_in(key, step), gi)
    return jax.random.bits(k, shape, jnp.uint32) >> 16


def inner_update(grads: Trainable, trainable: Trainable, params,
                 state: SubspaceState, *, lr,
                 tcfg) -> Tuple[Any, Trainable, SubspaceState, Array]:
    """One Adam step on the trainable tree.

    Returns (new_params, new_trainable, new_state, grad_norm).  Dense leaf
    updates land in params; low-rank updates land in the groups' stacked B.

    Every group's pre-stacked B/m/v feeds ONE batched ``subspace_adam``
    call through the kernel dispatch layer (the Pallas fused-Adam kernel on
    TPU) — no per-leaf stack/gather anywhere on this path.  The grouped
    master weights stay stacked (and untouched — they only move at the
    outer merge).
    """
    grads, gn = clip_by_global_norm(grads, tcfg.grad_clip)
    step = state.step + 1
    stepf = step.astype(jnp.float32)
    bc1 = 1.0 - tcfg.beta1 ** stepf
    bc2 = 1.0 - tcfg.beta2 ** stepf

    layout = state.layout
    lion = layout.algo == "lion"
    q8 = layout.state_dtype == "int8"
    sr = layout.master_dtype == "bfloat16"

    # -- dense leaves: plain elementwise math (XLA fuses the chain) --------
    new_dense_w, new_dense = [], []
    for di, w in enumerate(params.dense):
        if lion:
            new_p, slot = _dense_lion(state.dense[di], w, grads.dense[di],
                                      lr=lr, tcfg=tcfg)
        else:
            new_p, slot = _dense_adam(state.dense[di], w, grads.dense[di],
                                      lr=lr, bc1=bc1, bc2=bc2, tcfg=tcfg)
        new_dense_w.append(new_p)
        new_dense.append(slot)

    # -- low-rank groups: one batched kernel call per group ----------------
    # weight decay acts on the *effective* weight via the outer merge;
    # inside the subspace we decay B directly (equivalent to decaying the
    # increment — standard in GaLore-style training).
    new_groups, new_tgroups = [], []
    packs = layout.packs
    for gi, (slot, g) in enumerate(zip(state.groups, grads.groups)):
        g32 = g.astype(jnp.float32)
        bits = (_sr_bits(state.key, state.step, gi, slot.b.shape)
                if sr else None)
        if q8:
            # fused dequant -> fp32 update -> requant: the int8 payload +
            # scales are all that moves; SR of b' fuses in when masters
            # are bf16
            if lion:
                nb, nmq, nms = dispatch.subspace_lion_q8(
                    slot.b, g32, slot.m.q, slot.m.scale, lr=lr,
                    beta1=tcfg.beta1, beta2=tcfg.beta2,
                    wd=float(tcfg.weight_decay), qblock=layout.qblock,
                    bits=bits)
                nm = quant.QuantizedTensor(nmq, nms, layout.qblock)
                nv = slot.v
            else:
                nb, nmq, nms, nvq, nvs = dispatch.subspace_adam_q8(
                    slot.b, g32, slot.m.q, slot.m.scale,
                    slot.v.q, slot.v.scale, lr=lr, step=stepf,
                    beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps,
                    wd=float(tcfg.weight_decay), qblock=layout.qblock,
                    bits=bits)
                nm = quant.QuantizedTensor(nmq, nms, layout.qblock)
                nv = quant.QuantizedTensor(nvq, nvs, layout.qblock,
                                           codec="sqrt")
        else:
            # fp32-state kernels output fp32 b'; SR (if any) applies to
            # the store, outside the kernel
            if lion:
                nb, nm = dispatch.subspace_lion(
                    slot.b, g32, slot.m, lr=lr, beta1=tcfg.beta1,
                    beta2=tcfg.beta2, wd=float(tcfg.weight_decay),
                    pack=packs[gi] if gi < len(packs) else None)
                nv = slot.v
            else:
                nb, nm, nv = dispatch.subspace_adam(
                    slot.b, g32, slot.m, slot.v, lr=lr, step=stepf,
                    beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps,
                    wd=float(tcfg.weight_decay),
                    pack=packs[gi] if gi < len(packs) else None)
            if sr:
                nb = sr_bf16(nb, bits).astype(slot.b.dtype)
        new_groups.append(GroupedLowRankSlot(
            proj=slot.proj, b=nb, m=nm, v=nv,
            energy=_group_energy_update(slot, g32)))
        new_tgroups.append(nb)

    new_params = GroupedParams(dense=tuple(new_dense_w),
                               groups=params.groups,
                               layout=params.layout,
                               treedef=params.treedef)
    new_trainable = Trainable(
        dense=tuple(new_dense_w),
        groups=tuple(new_tgroups))
    new_state = SubspaceState(dense=tuple(new_dense),
                              groups=tuple(new_groups), step=step,
                              outer_step=state.outer_step, key=state.key,
                              layout=state.layout)
    return new_params, new_trainable, new_state, gn


# ---------------------------------------------------------------------------
# Outer step (Algorithm 1, lines 3 & 8) — merge + resample
# ---------------------------------------------------------------------------

def outer_merge_resample(params, state: SubspaceState, tcfg):
    """W += V B^T (fp32 accumulate), resample V, zero B (+ moments).

    The pure batched form: per group ONE ``lowrank_merge`` over the
    already-stacked ``(G, ..., k, n)`` weight buffer of the
    :class:`GroupedParams` and ONE batched sampler draw — zero
    stack/unstack anywhere (asserted by jaxpr inspection in
    tests/test_grouped_params.py).

    Runs fully sharded: W/V/B share one G-axis split per group (the
    :func:`~repro.sharding.rules.state_pspecs` invariant), so the merge
    is shard-local on G, and the resample draw is per-row keyed — each
    device regenerates only its own G-shard of V.  With
    ``tcfg.fuse_outer`` this whole function lowers inside the inner step
    under a traced ``lax.cond`` (``train.steps.fuse_outer_into_inner``).
    """
    nkey, skey = jax.random.split(state.key)
    gkeys = jax.random.split(skey, max(len(state.groups), 1))
    sr_master = state.layout.master_dtype == "bfloat16"
    new_wgroups, new_groups = [], []
    for g, (spec, slot) in enumerate(zip(state.layout.groups, state.groups)):
        ws = params.groups[g]
        if sr_master and jnp.dtype(ws.dtype) == jnp.bfloat16:
            # merging into narrow stored weights: stochastic rounding
            # keeps the once-per-K accumulate unbiased across outer cycles
            mbits = _sr_bits(skey, state.outer_step, g, ws.shape)
            merged = dispatch.lowrank_merge_sr(ws, slot.proj, slot.b, mbits)
        else:
            merged = dispatch.lowrank_merge(ws, slot.proj, slot.b)
        new_wgroups.append(merged)
        proj = _sample_proj_group(tcfg.sampler, gkeys[g], spec,
                                  len(spec.leaf_idx), tcfg.c, slot.energy,
                                  dtype=slot.proj.dtype)
        b = jnp.zeros_like(slot.b)
        if tcfg.reset_moments:
            m, v = quant.zeros_like(slot.m), quant.zeros_like(slot.v)
        else:
            m, v = slot.m, slot.v  # beyond-paper: carry moments across V
        new_groups.append(GroupedLowRankSlot(proj=proj, b=b, m=m, v=v,
                                             energy=slot.energy))
    new_state = SubspaceState(dense=state.dense, groups=tuple(new_groups),
                              step=state.step,
                              outer_step=state.outer_step + 1, key=nkey,
                              layout=state.layout)
    return GroupedParams(dense=params.dense, groups=tuple(new_wgroups),
                         layout=params.layout,
                         treedef=params.treedef), new_state


# ---------------------------------------------------------------------------
# Per-leaf reference implementations (tests).  These reproduce the pre-grouped layout's behaviour: a Python
# loop over leaves, per-leaf kernel calls, per-leaf key splits.  NOT the
# hot path.  They consume the raw model tree only — ungroup with
# :func:`params_of` first when comparing against a GroupedParams run.
# ---------------------------------------------------------------------------

def inner_update_ref(grads: Trainable, trainable: Trainable, params,
                     state: SubspaceState, *, lr, tcfg):
    """Per-leaf reference of :func:`inner_update` (identical math, one
    ``ref.subspace_adam`` call and one energy einsum per member leaf)."""
    grads, gn = clip_by_global_norm(grads, tcfg.grad_clip)
    step = state.step + 1
    stepf = step.astype(jnp.float32)
    bc1 = 1.0 - tcfg.beta1 ** stepf
    bc2 = 1.0 - tcfg.beta2 ** stepf

    flat_p, pdef = jax.tree.flatten(params)
    new_flat_p = list(flat_p)
    new_dense = []
    for di, i in enumerate(state.layout.dense_idx):
        new_p, slot = _dense_adam(state.dense[di], flat_p[i],
                                  grads.dense[di], lr=lr, bc1=bc1, bc2=bc2,
                                  tcfg=tcfg)
        new_flat_p[i] = new_p
        new_dense.append(slot)

    new_groups, new_tgroups = [], []
    for slot, g in zip(state.groups, grads.groups):
        g32 = g.astype(jnp.float32)
        outs = []
        for j in range(g32.shape[0]):   # the per-leaf loop the grouped
            outs.append(ref.subspace_adam(   # layout removes
                slot.b[j], g32[j], slot.m[j], slot.v[j], lr=lr,
                beta1=tcfg.beta1, beta2=tcfg.beta2, eps=tcfg.eps,
                wd=float(tcfg.weight_decay), step=stepf))
        nb = jnp.stack([o[0] for o in outs])
        nm = jnp.stack([o[1] for o in outs])
        nv = jnp.stack([o[2] for o in outs])
        if slot.energy.shape[-1]:
            es = []
            for j in range(g32.shape[0]):
                mm = jnp.einsum("...nr,...ns->...rs", g32[j], g32[j])
                e = jnp.einsum("...kr,...rs,...ks->...k", slot.proj[j], mm,
                               slot.proj[j])
                if e.ndim > 1:
                    e = e.mean(axis=tuple(range(e.ndim - 1)))
                es.append(0.99 * slot.energy[j] + 0.01 * e)
            energy = jnp.stack(es)
        else:
            energy = slot.energy
        new_groups.append(GroupedLowRankSlot(proj=slot.proj, b=nb, m=nm,
                                             v=nv, energy=energy))
        new_tgroups.append(nb)

    new_params = jax.tree.unflatten(pdef, new_flat_p)
    new_trainable = Trainable(
        dense=tuple(new_flat_p[i] for i in state.layout.dense_idx),
        groups=tuple(new_tgroups))
    new_state = SubspaceState(dense=tuple(new_dense),
                              groups=tuple(new_groups), step=step,
                              outer_step=state.outer_step, key=state.key,
                              layout=state.layout)
    return new_params, new_trainable, new_state, gn


def outer_merge_resample_ref(params, state: SubspaceState, tcfg):
    """Per-leaf reference of :func:`outer_merge_resample`: one merge and
    one sampler draw per member leaf, ``jax.random.split(key, n_leaves)``."""
    nkey, skey = jax.random.split(state.key)
    flat_p, pdef = jax.tree.flatten(params)
    new_flat_p = list(flat_p)
    keys = jax.random.split(skey, max(state.layout.n_leaves, 1))
    new_groups = []
    for spec, slot in zip(state.layout.groups, state.groups):
        projs = []
        for j, i in enumerate(spec.leaf_idx):
            merged = dispatch.lowrank_merge(flat_p[i], slot.proj[j],
                                            slot.b[j])
            new_flat_p[i] = merged
            projs.append(_sample_proj(tcfg.sampler, keys[i], flat_p[i].shape,
                                      spec.rank, tcfg.c, slot.energy[j],
                                      dtype=slot.proj.dtype))
        b = jnp.zeros_like(slot.b)
        if tcfg.reset_moments:
            m, v = jnp.zeros_like(b), jnp.zeros_like(b)
        else:
            m, v = slot.m, slot.v
        new_groups.append(GroupedLowRankSlot(proj=jnp.stack(projs), b=b,
                                             m=m, v=v, energy=slot.energy))
    new_state = SubspaceState(dense=state.dense, groups=tuple(new_groups),
                              step=state.step,
                              outer_step=state.outer_step + 1, key=nkey,
                              layout=state.layout)
    return jax.tree.unflatten(pdef, new_flat_p), new_state


def lowrank_param_count(params, tcfg) -> dict:
    """Memory accounting: optimizer-state floats for lowrank vs dense Adam."""
    leaves = jax.tree_util.tree_flatten_with_path(params_of(params))[0]
    full = sum(int(jnp.size(x)) for _, x in leaves)
    lowrank_states = 0
    proj_states = 0
    dense_states = 0
    for path, x in leaves:
        ps = _path_str(path)
        if is_lowrank_leaf(ps, x, tcfg):
            r = _rank_for(x.shape, tcfg)
            lead = 1
            for d in x.shape[:-2]:
                lead *= d
            lowrank_states += lead * x.shape[-1] * r  # B (and its moments)
            proj_states += lead * x.shape[-2] * r     # V
        else:
            dense_states += int(jnp.size(x))
    return {"param_count": full,
            "b_count": lowrank_states,
            "v_count": proj_states,
            "dense_count": dense_states,
            "adam_state_full": 2 * full,
            "adam_state_lowrank": 2 * (lowrank_states + dense_states)}
