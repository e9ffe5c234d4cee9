"""LowRank-LR (zeroth-order) trainer — the paper's Definition 2 / Example 3.

Forward-only training: per step, sample Z (B-shaped) per low-rank leaf and a
full-shape z per dense leaf, evaluate the loss at Theta +/- sigma * (Z V^T)
(antithetic two-point), and form the subspace gradient estimate

    g_B = (F+ - F-) / (2 sigma) * Z            (m x r per matrix)

which feeds the same lazy-update Adam machinery as LowRank-IPA.  No
backprop, no activation storage — this is the 3.83 GB row of the paper's
Table 2.

``vanilla=True`` degrades to full-space ZO (Vanilla LR baseline): every leaf
is perturbed with a full-shape Gaussian.

``params`` are the grouped master weights
(:class:`repro.optim.subspace.GroupedParams`, from ``subspace.init``):
noise, perturbation and the Adam update all act on the *trainable* buffers
(stacked B per group), so the grouped layout flows through untouched —
packing slices the stacked weight buffers lazily, exactly like the
backprop path.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import subspace
from .subspace import (SubspaceState, Trainable, packed_params,
                       trainable_of)

Array = jax.Array


def _sample_noise(state: SubspaceState, key: Array) -> Trainable:
    """One Z per trainable buffer: a stacked B-shaped draw per group and a
    W-shaped draw per dense leaf (one key per buffer, not per leaf)."""
    n_dense = len(state.dense)
    keys = jax.random.split(key, max(n_dense + len(state.groups), 1))
    dense = tuple(jax.random.normal(keys[i], slot.m.shape, jnp.float32)
                  for i, slot in enumerate(state.dense))
    groups = tuple(
        jax.random.normal(keys[n_dense + g], slot.b.shape, jnp.float32)
        for g, slot in enumerate(state.groups))
    return Trainable(dense=dense, groups=groups)


def _perturbed(params, state, trainable, noise, sigma: float, sign: float,
               dtype=None):
    """Packed params at (trainable + sign * sigma * noise)."""
    pert = jax.tree.map(lambda t, z: t + sign * sigma * z.astype(t.dtype),
                        trainable, noise)
    return packed_params(params, state, pert, dtype=dtype)


def zo_value_and_grad(loss_fn, params, state: SubspaceState, batch,
                      key: Array, sigma: float, dtype=None):
    """Antithetic two-point LowRank-LR estimate of the trainable gradient.

    Returns (loss at center approx, grad_estimate tree).
    """
    trainable = trainable_of(params, state)
    noise = _sample_noise(state, key)
    fp = loss_fn(_perturbed(params, state, trainable, noise, sigma, +1.0,
                            dtype), batch)
    fm = loss_fn(_perturbed(params, state, trainable, noise, sigma, -1.0,
                            dtype), batch)
    coeff = (fp - fm) / (2.0 * sigma)
    grads = jax.tree.map(lambda z: coeff * z, noise)
    return 0.5 * (fp + fm), grads, trainable


def zo_inner_step(loss_fn, params, state: SubspaceState, batch, key: Array,
                  *, lr, tcfg, dtype=None):
    """One LowRank-LR inner step: 2 forward passes + subspace Adam."""
    loss, grads, trainable = zo_value_and_grad(
        loss_fn, params, state, batch, key, tcfg.zo_sigma, dtype=dtype)
    new_params, _, new_state, gn = subspace.inner_update(
        grads, trainable, params, state, lr=lr, tcfg=tcfg)
    return loss, new_params, new_state, gn
