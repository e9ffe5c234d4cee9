"""GaLore-style projected-gradient baseline (Zhao et al., 2024).

The paper positions its estimator against GaLore: GaLore computes the FULL
gradient by backprop, then projects onto the top-r singular subspace (SVD
refreshed every K steps) and runs Adam in the subspace.  Memory: optimizer
states are (n x r) like ours, but the full (k x n) gradient IS materialised
every step and full activations ARE stored — so it saves optimizer memory
only, not gradient-estimation memory (the paper's Section 2 critique,
which this implementation makes measurable: see benchmarks/memory_table).

Shares the grouped SubspaceState machinery: per group the stacked full
gradients project through ``dispatch.lowrank_project`` (the same kernel
path the paper's optimizer uses for its Thm.-1 lift), so both optimizers
exercise identical kernels.  The projector is data-dependent (top-r left
singular vectors of the latest full gradient) instead of a random
admissible law — NOT unbiased in the paper's sense (Definition 3 isotropy
does not hold), which is exactly the theoretical gap the paper's random
projectors close.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ..kernels import dispatch
from ..models.common import compute_view as _compute_view
from ..models.common import resolve_compute_dtype
from . import subspace
from .adamw import clip_by_global_norm
from .subspace import GroupedLowRankSlot, SubspaceState, _dense_adam

Array = jax.Array


def init(params, tcfg,
         key: Array) -> Tuple[subspace.GroupedParams, SubspaceState]:
    """``(GroupedParams, state)`` from the model tree ``params``: the same
    grouped master weights and slot layout as LowRankLazyAdam, so GaLore's
    per-step weight write happens on the stacked buffers with zero
    stack/unstack.  U starts as zeros (the first refresh fills it from the
    first gradient's SVD).

    GaLore opts OUT of quantized/narrow optimizer state
    (``quantize_state=False``): its moment math below runs in plain XLA on
    the logical fp32 views, not through the fused dequant-in-VMEM q8
    kernels, so ``state_dtype``/``master_dtype`` would buy nothing here and
    the slots stay fp32 regardless of the knobs."""
    state = subspace._init_state(params, tcfg, key, quantize_state=False)
    groups = tuple(g._replace(proj=jnp.zeros_like(g.proj))
                   for g in state.groups)
    state = dataclasses.replace(state, groups=groups)
    return subspace.group_params(params, state.layout), state


def _top_r_basis(g: Array, r: int) -> Array:
    """Top-r right singular vectors of g (k x n) -> (k, r) basis.

    Computed via eigh of the (k x k)... we need the basis of the k-dim
    (input) side to match our V (k, r) convention: svd of g gives
    g = U S W^T with U (k, k); top-r columns of U span the projection.
    Uses eigh(g g^T) — O(k^2 n + k^3), run once per refresh interval.
    """
    gram = (g @ g.T).astype(jnp.float32)
    _, vecs = jnp.linalg.eigh(gram)             # ascending
    return vecs[:, -r:]                          # (k, r)


def value_and_full_grads(loss_fn, params, batch):
    """GaLore's step 1: classical full backprop (the memory cost).

    The gradient arrives in the grouped layout of the master weights (a
    ``GroupedParams`` cotangent whose ``groups[g]`` are already stacked
    ``(G,)+lead+(k, n)`` buffers), so no per-group gradient stack exists.
    """
    return jax.value_and_grad(
        lambda gp: loss_fn(subspace.params_of(gp), batch))(params)


def update(full_grads, params, state: SubspaceState, *, lr, tcfg,
           refresh) -> Tuple[Any, SubspaceState]:
    """Adam on the projected gradient; lift the update back to W.

    GaLore updates W directly every step (no lazy B accumulation):
      R = U^T G ;  Adam(R) -> delta ;  W -= lr * U @ delta.
    Per group the projection R runs as ONE batched
    ``dispatch.lowrank_project`` call over the stacked gradients, and the
    per-step weight write is a pure batched subtract on the stacked buffer
    (no stack/unstack at all).
    """
    full_grads, _ = clip_by_global_norm(full_grads, tcfg.grad_clip)
    step = state.step + 1
    b1, b2, eps = tcfg.beta1, tcfg.beta2, tcfg.eps
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    new_dense_w, new_dense = [], []
    for di, (w, g) in enumerate(zip(params.dense, full_grads.dense)):
        new_p, slot = _dense_adam(state.dense[di], w, g,
                                  lr=lr, bc1=bc1, bc2=bc2, tcfg=tcfg)
        new_dense_w.append(new_p)
        new_dense.append(slot)

    new_wgroups, new_groups = [], []
    for g_i, (spec, slot) in enumerate(zip(state.layout.groups,
                                           state.groups)):
        gs = full_grads.groups[g_i].astype(jnp.float32)
        ws = params.groups[g_i].astype(jnp.float32)
        r = spec.rank
        fn = _top_r_basis
        for _ in range(gs.ndim - 2):
            fn = jax.vmap(fn, in_axes=(0, None))
        # U is stored in the layout's compute dtype (like the subspace
        # paradigms' V): the SVD runs fp32, one cast per refresh.
        refreshed = lambda g: fn(g, r).astype(slot.proj.dtype)
        if isinstance(refresh, jax.Array):
            proj = jax.lax.cond(refresh, refreshed,
                                lambda g: slot.proj, gs)
        else:
            proj = refreshed(gs) if refresh else slot.proj
        # project: R = U^T G -> (n, r), through the shared kernel path
        # (fp32 accumulate over the possibly-reduced-precision U)
        rproj = dispatch.lowrank_project(gs, proj)
        m = b1 * slot.m + (1 - b1) * rproj
        v = b2 * slot.v + (1 - b2) * rproj * rproj
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        lifted = jnp.einsum("...kr,...nr->...kn",
                            proj.astype(jnp.float32), delta)
        if tcfg.weight_decay:
            lifted = lifted + tcfg.weight_decay * ws
        new_ws = ws - lr * lifted
        new_wgroups.append(new_ws.astype(params.groups[g_i].dtype))
        new_groups.append(GroupedLowRankSlot(proj=proj, b=slot.b, m=m, v=v,
                                             energy=slot.energy))
    new_state = dataclasses.replace(state, dense=tuple(new_dense),
                                    groups=tuple(new_groups), step=step)
    return subspace.GroupedParams(
        dense=tuple(new_dense_w), groups=tuple(new_wgroups),
        layout=params.layout, treedef=params.treedef), new_state


def make_train_step(cfg, tcfg, loss_fn=None):
    """Standalone jit-able GaLore step with an explicit ``refresh`` bool
    (the caller schedules the SVD cadence; two jitted variants is
    simplest).  The Trainer path uses :func:`make_inner_step` instead,
    which folds the cadence into the step as a traced condition —
    ``tests/test_methods.py`` asserts both are bit-identical."""
    from ..train import steps as steps_mod
    base_loss = loss_fn or steps_mod.build_loss_fn(cfg)
    cdt = resolve_compute_dtype(tcfg)
    loss_fn = lambda p, mb: base_loss(_compute_view(p, cdt), mb)

    def train_step(params, opt_state, batch, refresh: bool):
        lr = steps_mod._lr_at(tcfg, opt_state.step)
        loss, grads = value_and_full_grads(loss_fn, params, batch)
        new_p, new_s = update(grads, params, opt_state, lr=lr, tcfg=tcfg,
                              refresh=refresh)
        return new_p, new_s, {"loss": loss}

    return train_step


def make_inner_step(cfg, tcfg, loss_fn=None):
    """Trainer-facing step: ``(params, opt_state, batch) -> (params,
    opt_state, metrics)``, the Method-protocol inner signature.

    The SVD refresh fires when ``opt_state.step % lazy_k == 0`` as a
    TRACED condition (``update`` lowers it through ``lax.cond``), so one
    jitted function covers both branches — no retrace across the cadence
    and no GaLore-specific scheduling in the Trainer.  ``step`` starts at
    0 and rides in the checkpointed state, so the first call always
    refreshes (proj is initialised to zeros) and resume keeps the cadence.
    """
    from ..train import steps as steps_mod
    from .adamw import global_norm
    base_loss = loss_fn or steps_mod.build_loss_fn(cfg)
    cdt = resolve_compute_dtype(tcfg)
    loss_fn = lambda p, mb: base_loss(_compute_view(p, cdt), mb)

    def train_step(params, opt_state, batch):
        lr = steps_mod._lr_at(tcfg, opt_state.step)
        refresh = (opt_state.step % tcfg.lazy_k) == 0
        loss, grads = value_and_full_grads(loss_fn, params, batch)
        new_p, new_s = update(grads, params, opt_state, lr=lr, tcfg=tcfg,
                              refresh=refresh)
        return new_p, new_s, {"loss": loss, "grad_norm": global_norm(grads),
                              "lr": lr}

    return train_step
