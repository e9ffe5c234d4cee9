"""Traced per-step health guard: non-finite detection + loss-spike skip.

The paper's estimators make loss spikes and non-finite updates a
*designed-in* hazard (random subspace draws, ZO perturbations, the bf16
hot path), and the grouped structure-of-arrays state makes the blast
radius total — one NaN at an outer boundary poisons every group's stacked
B/m/v at once.  So detection and skip live INSIDE the jitted inner step,
for every registered method, method-agnostically:

  * the candidate step runs unconditionally;
  * ``ok`` = loss and grad-norm finite (the grad estimate's global norm is
    computed by every method already, and a non-finite gradient or update
    propagates into it) AND no EMA z-score loss spike;
  * ``lax.cond(ok, candidate, unchanged)`` — on a skip, params, opt state
    and the grouped master buffers pass through BIT-IDENTICAL (selects
    lower to ``select_n``; donation-safe: outputs may alias the donated
    inputs on either branch);
  * the EMA mean/var update feeds only on ACCEPTED losses, so an anomaly
    never poisons the detector that caught it.

No extra host sync: the step's observables (loss, skip flag, consecutive
skips, grad norm) are packed into ONE small ``metrics["health"]`` vector,
so the Trainer's existing single loss fetch now carries the whole health
readout.  The guard introduces no callbacks and no device->host transfer
inside the traced step — jaxpr-verified in tests/test_resilience.py.

Escalation (N consecutive skips -> checkpoint rollback + LR backoff +
sampler-key reseed) is HOST-side policy and lives in
:class:`repro.train.trainer.Trainer`; this module only provides the
traced detection and the carry state.

Chaos: when a :mod:`repro.train.chaos` hook is installed at trace time,
its gradient poison / loss spike injections are woven into the traced
step here (a deterministic ``step == k`` select), corrupting exactly the
tensors a real overflow would corrupt.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import chaos

Array = jax.Array

# metrics["health"] layout (one float32 vector => one host fetch per step)
H_LOSS, H_OK, H_CONSEC, H_GNORM = 0, 1, 2, 3


class HealthState(NamedTuple):
    """Device-side carry of the guard (rides next to the opt state)."""
    ema_mean: Array      # f32 EMA of accepted losses
    ema_var: Array       # f32 EMA variance of accepted losses
    good_steps: Array    # i32 accepted steps since (re)arm — warmup gate
    consec_skips: Array  # i32 consecutive skipped steps (escalation signal)
    total_skips: Array   # i32 lifetime skips (manifest/report counter)
    last_anomaly: Array  # i32 guard-step index of the last skip (-1: none)
    seen: Array          # i32 total guard steps (accepted + skipped)


def _f32(v=0.0) -> Array:
    return jnp.full((), v, jnp.float32)


def _i32(v=0) -> Array:
    return jnp.full((), v, jnp.int32)


# Every field gets its own buffer: the Trainer donates this state, and a
# donating call refuses one buffer passed as two arguments.

def init_health() -> HealthState:
    return HealthState(ema_mean=_f32(), ema_var=_f32(), good_steps=_i32(),
                       consec_skips=_i32(), total_skips=_i32(),
                       last_anomaly=_i32(-1), seen=_i32())


def after_rollback(h: HealthState) -> HealthState:
    """Re-arm after a restore+backoff: the spike detector's statistics
    belong to the old LR/projection, so reset EMA and the warmup gate;
    lifetime counters (total skips, last anomaly, steps seen) persist."""
    return h._replace(ema_mean=_f32(), ema_var=_f32(), good_steps=_i32(),
                      consec_skips=_i32())


def _is_step(idx: Array, steps) -> Array:
    hit = jnp.zeros((), jnp.bool_)
    for k in steps:
        hit = hit | (idx == jnp.int32(k))
    return hit


def _poison_tree(tree, factor: Array):
    """Multiply every floating leaf by ``factor`` (NaN/inf chaos: the
    corruption lands in the same buffers a real overflow would corrupt).
    Integer counters and PRNG keys pass through."""
    def f(x):
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x * factor.astype(x.dtype)
        return x
    return jax.tree.map(f, tree)


def logits_row_ok(rows: Array) -> Array:
    """Per-row decode-logit health: ``(batch,)`` bool, True = servable.

    A row fails when any logit is non-finite (bf16 adapter overflow) or
    when the distribution has collapsed to a constant (zero spread — all
    mass nowhere, the washed-out-adapter signature).  Pass only the real
    vocab lanes: padded lanes carry a large negative fill that would hide
    a collapse.  Traced — used inside the serving decode jit, mirroring
    :func:`guard_inner_step`'s select semantics.
    """
    finite = jnp.all(jnp.isfinite(rows), axis=-1)
    spread = (jnp.max(rows, axis=-1) - jnp.min(rows, axis=-1)) > 0
    return finite & spread


def guard_inner_step(step_fn: Callable, tcfg) -> Callable:
    """Wrap a Method inner step with the traced health guard.

    ``step_fn(params, opt_state, batch) -> (params, opt_state, metrics)``
    becomes ``guarded(params, opt_state, health, batch) -> (params,
    opt_state, health, metrics)`` with ``metrics["health"]`` the packed
    observable vector.  Any installed chaos hook is captured at trace
    time (tests install it before the Trainer jits).
    """
    hook = chaos.get()
    z_thresh = float(getattr(tcfg, "spike_zscore", 6.0))
    rho = float(getattr(tcfg, "spike_ema", 0.99))
    warmup = int(getattr(tcfg, "spike_warmup", 20))

    def guarded(params, opt_state, health: HealthState, batch):
        cand_p, cand_s, metrics = step_fn(params, opt_state, batch)
        with jax.named_scope("repro.guard"):
            return accept_or_skip(params, opt_state, health, cand_p, cand_s,
                                  metrics)

    def accept_or_skip(params, opt_state, health: HealthState, cand_p,
                       cand_s, metrics):
        loss = jnp.asarray(metrics["loss"], jnp.float32)
        gn = jnp.asarray(metrics.get("grad_norm", 0.0), jnp.float32)
        idx = health.seen

        if hook is not None:
            if hook.grad_nan_steps:
                bad = _is_step(idx, hook.grad_nan_steps)
                factor = jnp.where(bad, jnp.float32(hook.poison()),
                                   jnp.float32(1.0))
                loss, gn = loss * factor, gn * factor
                cand_p = _poison_tree(cand_p, factor)
                cand_s = _poison_tree(cand_s, factor)
            if hook.spike_scale_steps:
                sp = _is_step(idx, hook.spike_scale_steps)
                loss = loss * jnp.where(sp, jnp.float32(hook.spike_scale),
                                        jnp.float32(1.0))

        finite = jnp.isfinite(loss) & jnp.isfinite(gn)
        delta = loss - health.ema_mean
        # Arm only after warmup ACCEPTED steps (and never before the EMA
        # is seeded).  The z denominator carries a relative floor of 5% of
        # the running mean: near-zero variance (smooth loss curves) must
        # not turn ordinary fluctuations into z >> thresh false positives
        # — a spike has to clear both the noise scale AND 5% of the mean.
        armed = (health.good_steps >= warmup) & (health.good_steps > 0)
        # NaN-safe: a non-finite z never arms `spike` (comparison is False)
        z = delta * jax.lax.rsqrt(
            health.ema_var + (0.05 * health.ema_mean) ** 2 + 1e-12)
        spike = armed & (z > z_thresh)
        ok = finite & ~spike

        new_p, new_s = jax.lax.cond(
            ok, lambda: (cand_p, cand_s), lambda: (params, opt_state))

        # EMA update on accepted steps only (delta is NaN-guarded by ok).
        # The FIRST accepted loss seeds the mean directly — starting the
        # EMA at zero would make every early delta ~ the loss itself and
        # poison the variance estimate for the whole warmup.
        seeded = ok & (health.good_steps == 0)
        safe_delta = jnp.where(ok, delta, 0.0)
        new_health = HealthState(
            ema_mean=jnp.where(
                seeded, loss,
                health.ema_mean + (1.0 - rho) * safe_delta),
            ema_var=jnp.where(
                seeded, 0.0,
                jnp.where(
                    ok,
                    rho * (health.ema_var + (1.0 - rho) * delta * delta),
                    health.ema_var)),
            good_steps=health.good_steps + ok.astype(jnp.int32),
            consec_skips=jnp.where(ok, 0, health.consec_skips + 1),
            total_skips=health.total_skips + (~ok).astype(jnp.int32),
            last_anomaly=jnp.where(ok, health.last_anomaly, idx),
            seen=health.seen + 1)

        metrics = dict(metrics)
        metrics["health"] = jnp.stack([
            loss, ok.astype(jnp.float32),
            new_health.consec_skips.astype(jnp.float32), gn])
        return new_p, new_s, new_health, metrics

    return guarded


class HealthRead(NamedTuple):
    """Host-side view of one step's packed health vector."""
    loss: float
    ok: bool
    consec_skips: int
    grad_norm: float


def read_health(metrics: dict) -> HealthRead:
    """ONE device->host fetch: materialise the packed vector and unpack."""
    vec = np.asarray(metrics["health"])
    return HealthRead(loss=float(vec[H_LOSS]), ok=bool(vec[H_OK] > 0.5),
                      consec_skips=int(vec[H_CONSEC]),
                      grad_norm=float(vec[H_GNORM]))


CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call", "debug_print"})


def assert_no_host_transfer(fn: Callable, *abstract_args) -> None:
    """Jaxpr audit: the guarded step must stay transfer/callback-free —
    the guard may not smuggle a device->host sync into the hot path.
    Raises AssertionError listing the offending primitives."""
    jaxpr = jax.make_jaxpr(fn)(*abstract_args)
    offenders = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in CALLBACK_PRIMITIVES:
                offenders.append(eqn.primitive.name)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
                elif isinstance(v, (list, tuple)):
                    for w in v:
                        if hasattr(w, "jaxpr"):
                            walk(w.jaxpr)
    walk(jaxpr.jaxpr)
    assert not offenders, (
        f"health guard introduced host-transfer primitives: {offenders}")


def counters(h: HealthState, rollbacks: int) -> dict:
    """JSON-able health counters for the checkpoint manifest ``extra``."""
    return {"skips": int(h.total_skips), "rollbacks": int(rollbacks),
            "last_anomaly_step": int(h.last_anomaly)}


def tree_all_finite(tree: Any) -> Array:
    """AND of isfinite over every floating leaf (chaos-test helper)."""
    ok = jnp.ones((), jnp.bool_)
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            ok = ok & jnp.isfinite(leaf).all()
    return ok


Guarded = Tuple[Any, Any, HealthState, dict]
