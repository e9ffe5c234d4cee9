"""Training loop with the fault-tolerance machinery.

Features (per the 1000+-node posture in DESIGN.md §5):
  * auto-resume from the latest valid checkpoint (step-indexed data ⇒ the
    stream continues exactly), walking back past corrupt/truncated
    checkpoints (quarantined, never deleted);
  * periodic step-atomic, fsync-durable checkpoints (keep-k);
  * preemption hook: SIGTERM/SIGINT → finish the in-flight step,
    checkpoint tagged ``extra.preempted``, exit cleanly; the previous
    signal handlers are restored on teardown so nested Trainers (tests)
    don't leak handlers;
  * traced health guard (:mod:`repro.train.health`): every inner step is
    wrapped with non-finite + EMA z-score spike detection and
    ``lax.cond`` skip-step semantics — a bad step leaves params, grouped
    masters and opt state bit-identical;
  * host-side escalation: ``max_consecutive_skips`` skips in a row →
    restore the last good checkpoint, back off LR by
    ``rollback_backoff`` (bounded by ``max_rollbacks``), and reseed the
    method's sampler key so the offending V/perturbation draw is not
    replayed (fresh draw from the same admissible law — unbiasedness
    untouched);
  * straggler watchdog: per-step wall-clock vs a running median; slow steps
    are counted and surfaced (at scale this signal feeds the job controller
    that hot-swaps the slice — here it raises a callback);
  * lazy-update orchestration: every ``tcfg.lazy_k`` inner steps runs the
    outer merge+resample (two jitted functions; no retrace).
"""
from __future__ import annotations

import dataclasses
import signal
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ..configs.base import ModelConfig, TrainConfig
from ..models import encdec, lm
from ..models.common import (resolve_compute_dtype, resolve_master_dtype,
                             resolve_state_dtype)
from ..optim import subspace
from .. import methods
from . import chaos
from . import checkpoint as ckpt
from . import health


@dataclass
class TrainerReport:
    steps_run: int = 0
    losses: List[float] = field(default_factory=list)
    step_times: List[float] = field(default_factory=list)
    resumed_from: Optional[int] = None
    straggler_events: int = 0
    preempted: bool = False
    # -- resilience counters (mirrored into the manifest extra.health) --
    skipped_steps: int = 0            # guard-skipped steps this run
    rollbacks: int = 0                # checkpoint rollbacks this run
    lr_backoffs: List[float] = field(default_factory=list)  # LR after each
    last_anomaly_step: Optional[int] = None   # trainer step of last skip
    health_exhausted: bool = False    # max_rollbacks spent; run stopped
    resumed_health: Optional[dict] = None     # counters carried from manifest


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 loader: Callable[[int], Dict], workdir: Optional[str] = None,
                 loss_fn: Optional[Callable] = None,
                 checkpoint_every: int = 0, keep: int = 3,
                 straggler_factor: float = 3.0,
                 on_straggler: Optional[Callable] = None):
        self.cfg, self.tcfg = cfg, tcfg
        self.loader = loader
        self.workdir = workdir
        self.loss_fn = loss_fn
        self.checkpoint_every = checkpoint_every
        self.keep = keep
        self.straggler_factor = straggler_factor
        self.on_straggler = on_straggler
        self._preempt = False
        self._prev_handlers: dict = {}

        # All paradigm-specific behaviour (state construction, inner/outer
        # steps, checkpoint tag) comes from the registered Method — an
        # unknown tcfg.optimizer raises here, listing methods.available(),
        # BEFORE the expensive model param init.
        self.method = methods.get(tcfg.optimizer)

        # Resolved ONCE per run and recorded in every checkpoint manifest:
        # the hot-path compute dtype (bf16 on accelerators by default).
        # Restore casts leaves into the template's dtypes, so an fp32
        # checkpoint resumes cleanly into a bf16 run and vice versa.
        self.compute_dtype = np.dtype(resolve_compute_dtype(tcfg)).name
        self.state_dtype = resolve_state_dtype(tcfg)
        self.master_dtype = resolve_master_dtype(tcfg)

        model = encdec if cfg.is_encoder_decoder else lm
        key = jax.random.key(tcfg.seed)
        pkey, okey = jax.random.split(key)
        self.params = model.init_params(cfg, pkey)
        self.params, self.opt_state = self.method.init(
            self.params, tcfg, okey)

        self.health = health.init_health()
        self.rollbacks = 0                 # lifetime (carried via manifest)
        self.total_skips_offset = 0        # skips from previous runs
        self._shardings = None  # (params, opt, health) once shard() ran
        self._build_steps()
        self.step = 0

    def _build_steps(self):
        """(Re)jit the inner/outer steps from the CURRENT self.tcfg.
        Called at init and after an LR-backoff rollback — a retrace per
        rollback, which is fine: rollbacks are rare and bounded.

        Donate (params, opt_state[, health]) into the jitted steps so the
        grouped state and weights update in place (no double-buffering of
        the stacked B/m/v or the model).  The caller rebinds self.params /
        self.opt_state to the outputs, so the donated buffers are never
        read again.  CPU has no donation support (XLA warns and copies) —
        skip there to keep test logs clean.
        """
        tcfg = self.tcfg
        on_cpu = jax.default_backend() == "cpu"
        inner = self.method.make_inner_step(self.cfg, tcfg, self.loss_fn)
        self._guarded = bool(getattr(tcfg, "health_guard", True))
        if self._guarded:
            inner = health.guard_inner_step(inner, tcfg)
            donate = (0, 1, 2) if not on_cpu else ()
        else:
            donate = (0, 1) if not on_cpu else ()
        # after shard(): the carried state keeps its placement from step to
        # step, so no step sees new input shardings and recompiles (None
        # leaves the metrics to the compiler)
        pin_inner, pin_outer = {}, {}
        if self._shardings is not None:
            carried = self._shardings[:3 if self._guarded else 2]
            pin_inner = {"out_shardings": carried + (None,)}
            pin_outer = {"out_shardings": self._shardings[:2]}
        self._inner = jax.jit(inner, donate_argnums=donate, **pin_inner)
        outer = self.method.make_outer_step(self.cfg, tcfg)
        self._outer = (jax.jit(outer, donate_argnums=(0, 1) if not on_cpu
                               else (), **pin_outer)
                       if outer is not None else None)

    def shard(self, mesh) -> None:
        """Spread the run over ``mesh``: place params and optimizer state
        with the method's pspecs (the health state replicated), split each
        batch's rows over the data axes, and pin both jitted steps'
        outputs to that placement, so every step — the outer resample,
        whose fresh V draw has no input to inherit a sharding from,
        included — keeps the G-axis split and no step recompiles.
        Activation constraints bind to the ambient mesh
        (``sharding.ctx.set_mesh``), which the caller sets first."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..sharding import rules

        model = encdec if self.cfg.is_encoder_decoder else lm
        p_ps, o_ps = self.method.pspecs(mesh, model.param_specs(self.cfg),
                                        self.params, self.opt_state)
        self._shardings = (rules.named_shardings(mesh, p_ps),
                           rules.named_shardings(mesh, o_ps),
                           NamedSharding(mesh, P()))   # health: replicated
        self.params, self.opt_state, self.health = jax.device_put(
            (self.params, self.opt_state, self.health), self._shardings)
        base = self.loader

        def loader(step):
            batch = base(step)
            rows = rules.batch_pspec(mesh, len(next(iter(batch.values()))))
            return jax.device_put(batch, NamedSharding(mesh, P(rows)))

        self.loader = loader
        self._build_steps()

    @property
    def model_params(self):
        """Model-shaped param tree (the API boundary for eval/serving).

        Low-rank runs hold master weights grouped (`subspace.GroupedParams`)
        internally; this ungroups them into the model tree — slices of the
        stacked buffers, so it is cheap to call.
        """
        return subspace.params_of(self.params)

    # -- fault tolerance ---------------------------------------------------

    def _install_signal_handlers(self):
        def handler(signum, frame):
            self._preempt = True
        self._prev_handlers = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, handler)
            except ValueError:
                pass  # not on main thread (tests)

    def _restore_signal_handlers(self):
        """Teardown: put back whatever handled SIGTERM/SIGINT before this
        run — nested Trainers (tests, eval-in-train) must not leak our
        preemption handler past their own run()."""
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except (ValueError, TypeError):
                pass
        self._prev_handlers = {}

    def request_preemption(self):
        """Programmatic preemption (tests / controllers)."""
        self._preempt = True

    def maybe_resume(self, report: Optional[TrainerReport] = None
                     ) -> Optional[int]:
        if not self.workdir:
            return None
        template = {"params": self.params, "opt": self.opt_state}
        restored, manifest = ckpt.restore_latest(
            self.workdir, template, expect_method=self.method.checkpoint_tag)
        if restored is None:
            return None
        self.params = restored["params"]
        self.opt_state = restored["opt"]
        self.step = manifest["step"]
        carried = (manifest.get("extra") or {}).get("health")
        if carried:
            # resumes carry anomaly history: lifetime counters continue
            # across restarts instead of resetting to zero
            self.rollbacks = int(carried.get("rollbacks", 0))
            self.total_skips_offset = int(carried.get("skips", 0))
            if report is not None:
                report.resumed_health = dict(carried)
        return self.step

    def _health_extra(self) -> dict:
        h = health.counters(self.health, self.rollbacks)
        h["skips"] += self.total_skips_offset
        return h

    def save(self, preempted: bool = False):
        if not self.workdir:
            return
        extra = {"arch": self.cfg.name,
                 "method": self.method.checkpoint_tag,
                 "compute_dtype": self.compute_dtype,
                 "state_dtype": self.state_dtype,
                 "master_dtype": self.master_dtype,
                 "health": self._health_extra()}
        if preempted:
            extra["preempted"] = True
        ckpt.save(self.workdir, self.step,
                  {"params": self.params, "opt": self.opt_state},
                  keep=self.keep, extra=extra)

    def _rollback(self, report: TrainerReport):
        """Escalation after ``max_consecutive_skips`` consecutive skips:
        restore the last good checkpoint (the skip guard guarantees any
        published checkpoint IS good), back off the LR, reseed the
        method's sampler key, and re-arm the detector."""
        self.rollbacks += 1
        report.rollbacks += 1
        if self.workdir:
            template = {"params": self.params, "opt": self.opt_state}
            restored, manifest = ckpt.restore_latest(
                self.workdir, template,
                expect_method=self.method.checkpoint_tag)
            if restored is not None:
                self.params = restored["params"]
                self.opt_state = restored["opt"]
                self.step = manifest["step"]
        # else: skip semantics already left the in-memory state at the
        # last good value — rollback degrades to backoff + reseed.
        rkey = jax.random.fold_in(
            jax.random.key(self.tcfg.seed ^ 0x5EED), self.rollbacks)
        self.params, self.opt_state = self.method.reseed(
            self.params, self.opt_state, rkey, self.tcfg)
        self.tcfg = dataclasses.replace(
            self.tcfg, lr=self.tcfg.lr * self.tcfg.rollback_backoff)
        report.lr_backoffs.append(self.tcfg.lr)
        self._build_steps()   # one retrace per (rare, bounded) rollback
        self.health = health.after_rollback(self.health)

    # -- main loop ----------------------------------------------------------

    def run(self, num_steps: int, log_every: int = 0) -> TrainerReport:
        self._install_signal_handlers()
        report = TrainerReport()
        report.resumed_from = self.maybe_resume(report)
        try:
            return self._run(num_steps, log_every, report)
        finally:
            self._restore_signal_handlers()

    def _run(self, num_steps: int, log_every: int,
             report: TrainerReport) -> TrainerReport:
        times: List[float] = []
        target = self.step + num_steps
        while self.step < target:
            t0 = time.perf_counter()
            if (self._outer is not None and self.step > 0 and
                    self.step % self.tcfg.lazy_k == 0):
                self.params, self.opt_state = jax.block_until_ready(
                    self._outer(self.params, self.opt_state))
            chaos.maybe_sigterm(self.step)   # fault injection (tests only)
            batch = self.loader(self.step)
            if self._guarded:
                self.params, self.opt_state, self.health, metrics = \
                    self._inner(self.params, self.opt_state, self.health,
                                batch)
                # ONE device->host fetch: the packed health vector carries
                # loss + skip flag + consecutive-skip count + grad norm
                hr = health.read_health(metrics)
                loss = hr.loss
                if not hr.ok:
                    report.skipped_steps += 1
                    report.last_anomaly_step = self.step
                if hr.consec_skips >= self.tcfg.max_consecutive_skips:
                    if self.rollbacks >= self.tcfg.max_rollbacks:
                        # resilience budget exhausted: stop cleanly with
                        # the last good state (skip semantics kept it
                        # intact) instead of spinning forever
                        report.health_exhausted = True
                        self.save()
                        break
                    self._rollback(report)
                    continue   # re-run from the restored step
            else:
                self.params, self.opt_state, metrics = self._inner(
                    self.params, self.opt_state, batch)
                loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            times.append(dt)
            report.losses.append(loss)
            report.step_times.append(dt)
            # straggler watchdog
            if len(times) >= 8:
                med = float(np.median(times[-64:]))
                if dt > self.straggler_factor * med:
                    report.straggler_events += 1
                    if self.on_straggler:
                        self.on_straggler(self.step, dt, med)
            self.step += 1
            report.steps_run += 1
            if log_every and self.step % log_every == 0:
                print(f"step {self.step:6d} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms)")
            if self.checkpoint_every and \
                    self.step % self.checkpoint_every == 0:
                self.save()
            if self._preempt:
                # preemption drain: the in-flight step above COMPLETED
                # before we got here — save it, tag the manifest, exit
                self.save(preempted=True)
                report.preempted = True
                break
        return report
