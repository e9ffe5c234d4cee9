"""Step builders: the functions that get jit'd / lowered.

``make_train_step(cfg, tcfg)`` returns the INNER step of Algorithm 1 (the
hot path the dry-run lowers); ``make_outer_step`` the merge+resample;
``make_adamw_train_step`` the Vanilla-IPA baseline; ``make_zo_train_step``
the forward-only LowRank-LR step; ``make_prefill_step`` /
``make_decode_step`` the serving paths.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, TrainConfig
from ..models import encdec, lm
from ..models.common import act_dtype, compute_view, resolve_compute_dtype
from ..optim import adamw, subspace, zo
from ..optim.schedule import SCHEDULES
from .loss import chunked_ce

Array = jax.Array

LB_COEFF = 0.01
ZLOSS_COEFF = 1e-3


def build_loss_fn(cfg: ModelConfig) -> Callable:
    """loss_fn(packed_params, batch) -> scalar (batch-mean token CE)."""

    def loss_fn(packed, batch):
        if cfg.is_encoder_decoder:
            h, aux = encdec.forward_hidden(
                packed, {"frames": batch["frames"],
                         "tokens": batch["tokens"]}, cfg)
            loss = chunked_ce(h, packed["unembed"], batch["labels"],
                              true_vocab=cfg.vocab_size,
                              chunk=cfg.loss_chunk)
            return loss
        extra = batch.get("extra_embeds")
        h, aux = lm.forward_hidden(packed, batch["tokens"], cfg,
                                   extra_embeds=extra)
        if extra is not None:  # loss only over the text region
            h = h[:, extra.shape[1]:]
        loss = chunked_ce(h, packed["unembed"], batch["labels"],
                          true_vocab=cfg.vocab_size, chunk=cfg.loss_chunk)
        if cfg.family == "moe":
            loss = loss + LB_COEFF * aux["lb_loss"] + \
                ZLOSS_COEFF * aux["router_z"]
        return loss

    return loss_fn


def _lr_at(tcfg: TrainConfig, step):
    sched = SCHEDULES.get(getattr(tcfg, "schedule", "cosine"),
                          SCHEDULES["cosine"])
    return sched(step, base_lr=tcfg.lr, warmup_steps=tcfg.warmup_steps,
                 total_steps=tcfg.total_steps)


def _pack_dtype(cfg, tcfg: Optional[TrainConfig] = None):
    """Dtype the packed (W, B, V) views are cast to for the fused
    forward/backward: the run's resolved compute dtype when reduced (the
    mixed-precision hot path — masters/moments stay fp32), else the
    model's activation dtype, else None (no cast)."""
    if tcfg is not None:
        cdt = resolve_compute_dtype(tcfg)
        if cdt != jnp.float32:
            return cdt
    dt = act_dtype(cfg)
    return dt if dt != jnp.float32 else None


# ---------------------------------------------------------------------------
# LowRank-IPA (Algorithm 1) steps
# ---------------------------------------------------------------------------

def _microbatch(batch, n: int):
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                    loss_fn: Optional[Callable] = None):
    """Inner step: subspace-Adam on (B, dense) trainables.

    ``tcfg.grad_accum > 1`` scans over microbatches (activation memory
    divided by A; gradients averaged — exactly equivalent for mean
    losses over equal splits).
    """
    loss_fn = loss_fn or build_loss_fn(cfg)
    pdt = _pack_dtype(cfg, tcfg)

    def train_step(params, opt_state: subspace.SubspaceState, batch):
        # ``params`` is a ``subspace.GroupedParams`` (from
        # ``subspace.init``) whose stacked weight buffers packed_params
        # slices lazily per leaf.
        lr = _lr_at(tcfg, opt_state.step)
        trainable = subspace.trainable_of(params, opt_state)

        def f(t, mb):
            packed = subspace.packed_params(params, opt_state, t, dtype=pdt)
            return loss_fn(packed, mb)

        a = max(1, tcfg.grad_accum)
        if a == 1:
            loss, grads = jax.value_and_grad(f)(trainable, batch)
        else:
            micro = _microbatch(batch, a)

            def acc(carry, mb):
                gsum, lsum = carry
                l, g = jax.value_and_grad(f)(trainable, mb)
                return (jax.tree.map(jnp.add, gsum, g), lsum + l), None

            zeros = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32),
                                 trainable)
            (gsum, lsum), _ = jax.lax.scan(acc, (zeros, 0.0), micro)
            grads = jax.tree.map(lambda g: g / a, gsum)
            loss = lsum / a
        with jax.named_scope("repro.update"):
            new_params, _, new_state, gn = subspace.inner_update(
                grads, trainable, params, opt_state, lr=lr, tcfg=tcfg)
        return new_params, new_state, {"loss": loss, "grad_norm": gn,
                                       "lr": lr}

    return train_step


def make_outer_step(cfg: ModelConfig, tcfg: TrainConfig):
    def outer_step(params, opt_state):
        return subspace.outer_merge_resample(params, opt_state, tcfg)
    return outer_step


def fuse_outer_into_inner(inner_step: Callable, tcfg: TrainConfig):
    """Fold the outer merge+resample into the inner step as a traced cond.

    Returns a step with the inner signature that first runs
    ``outer_merge_resample`` under ``lax.cond(step > 0 and step % lazy_k
    == 0)`` — the same ordering the Trainer uses when it dispatches the
    outer step separately (outer BEFORE the inner at the cadence
    boundary), and the same traced-cadence shape as GaLore's in-step SVD
    refresh.  One jitted program covers both branches: no retrace at the
    boundary, the params/state carry stays donated end to end, and the
    compiler schedules the resample draw (per-G-shard local, see
    ``core.samplers``) alongside the inner step's early compute instead
    of serialising it behind a host round-trip.  ``opt_state.step`` rides
    in the checkpoint, so resume keeps the cadence exactly like the
    separate-dispatch path.
    """

    def fused_step(params, opt_state, batch):
        fire = jnp.logical_and(opt_state.step > 0,
                               opt_state.step % tcfg.lazy_k == 0)
        params, opt_state = jax.lax.cond(
            fire,
            lambda args: subspace.outer_merge_resample(*args, tcfg),
            lambda args: args,
            (params, opt_state))
        return inner_step(params, opt_state, batch)

    return fused_step


# ---------------------------------------------------------------------------
# Vanilla IPA (full AdamW) baseline
# ---------------------------------------------------------------------------

def make_adamw_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                          loss_fn: Optional[Callable] = None):
    loss_fn = loss_fn or build_loss_fn(cfg)
    cdt = resolve_compute_dtype(tcfg)

    def train_step(params, opt_state: adamw.AdamWState, batch):
        # mixed precision for the dense baseline too: the loss reads a
        # reduced-precision view of the weights; the fp32/param-dtype
        # masters are what AdamW updates (grads flow back through the
        # cast, so they land in the master dtype).
        lr = _lr_at(tcfg, opt_state.step)
        loss, grads = jax.value_and_grad(
            lambda p, mb: loss_fn(compute_view(p, cdt), mb))(params, batch)
        new_params, new_state, gn = adamw.update(
            grads, opt_state, params, lr=lr, beta1=tcfg.beta1,
            beta2=tcfg.beta2, eps=tcfg.eps, weight_decay=tcfg.weight_decay,
            grad_clip=tcfg.grad_clip)
        return new_params, new_state, {"loss": loss, "grad_norm": gn,
                                       "lr": lr}

    return train_step


# ---------------------------------------------------------------------------
# LowRank-LR (forward-only ZO) step
# ---------------------------------------------------------------------------

def make_zo_train_step(cfg: ModelConfig, tcfg: TrainConfig,
                       loss_fn: Optional[Callable] = None):
    loss_fn = loss_fn or build_loss_fn(cfg)
    pdt = _pack_dtype(cfg, tcfg)

    def train_step(params, opt_state: subspace.SubspaceState, batch):
        lr = _lr_at(tcfg, opt_state.step)
        key = jax.random.fold_in(opt_state.key, opt_state.step)
        loss, new_params, new_state, gn = zo.zo_inner_step(
            loss_fn, params, opt_state, batch, key, lr=lr, tcfg=tcfg,
            dtype=pdt)
        return new_params, new_state, {"loss": loss, "grad_norm": gn,
                                       "lr": lr}

    return train_step


# ---------------------------------------------------------------------------
# Eval / serving steps
# ---------------------------------------------------------------------------

def make_eval_step(cfg: ModelConfig, loss_fn: Optional[Callable] = None):
    loss_fn = loss_fn or build_loss_fn(cfg)

    def eval_step(params, batch):
        # grouped master weights ungroup here (lazy slices), at the API
        # boundary — model code only ever sees the model-shaped tree
        return loss_fn(subspace.params_of(params), batch)

    return eval_step


def make_prefill_step(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        def prefill_step(params, batch, state):
            state = encdec.start_decode(params, batch["frames"], cfg, state)
            lg, state = encdec.decode_step(params, batch["tokens"], cfg,
                                           state)
            return lg, state
        return prefill_step

    def prefill_step(params, batch, state):
        return lm.prefill(params, batch["tokens"], cfg, state,
                          extra_embeds=batch.get("extra_embeds"))
    return prefill_step


def make_decode_step(cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        def decode_step(params, token, state):
            return encdec.decode_step(params, token, cfg, state)
        return decode_step

    def decode_step(params, token, state):
        return lm.decode_step(params, token, cfg, state)
    return decode_step


def make_paged_decode_step(cfg: ModelConfig):
    """Decode over a :class:`repro.models.lm.PagedDecodeState` — ragged
    sequences share one page arena (the serving engine's hot path)."""
    if cfg.is_encoder_decoder:
        raise NotImplementedError(
            "paged serving does not cover encoder-decoder models "
            "(cross-attention caches)")

    def decode_step(params, token, state):
        return lm.decode_step_paged(params, token, cfg, state)
    return decode_step
