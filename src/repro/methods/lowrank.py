"""The paper's own paradigms: LowRank-IPA (Algorithm 1) and LowRank-LR.

Both share the grouped structure-of-arrays machinery of
:mod:`repro.optim.subspace` — grouped master weights + grouped subspace
state built once by ``subspace.init``, batched kernels through the
dispatch layer, and the lazy outer merge+resample — and differ only in how
the subspace gradient ``g_B`` is produced: autodiff through the LRPack
path (IPA) vs the antithetic two-point forward-only estimate (LR/ZO).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from ..optim import subspace
from ..sharding import rules
from ..train import steps as steps_mod
from .base import Method
from .registry import register


class _LowRankBase(Method):
    """Shared init / outer step / sharding of the subspace paradigms."""

    def init(self, params, tcfg, key):
        # Master weights live GROUPED for the whole run (same
        # structure-of-arrays layout as the state): both jitted steps
        # consume weight slices lazily and the outer merge is a pure
        # batched W += V B^T on the stacked buffer.
        return subspace.init(params, tcfg, key)

    def make_outer_step(self, cfg, tcfg) -> Optional[Callable]:
        if getattr(tcfg, "fuse_outer", False):
            return None  # folded into the inner step; Trainer skips outer
        return steps_mod.make_outer_step(cfg, tcfg)

    def _maybe_fuse(self, step: Callable, tcfg) -> Callable:
        """Wrap the inner step with the traced-cond outer when
        ``tcfg.fuse_outer`` — bit-identical to separate dispatch
        (tests/test_fused_outer.py) with one fewer program launch."""
        if getattr(tcfg, "fuse_outer", False):
            return steps_mod.fuse_outer_into_inner(step, tcfg)
        return step

    def pspecs(self, mesh, specs, params_abs, opt_abs):
        return rules.grouped_param_pspecs(mesh, specs, params_abs), \
            rules.state_pspecs(mesh, specs, opt_abs)

    def reseed(self, params, opt_state, key, tcfg):
        """Anomaly-rollback reseed: swap in the fresh key, then run one
        outer merge+resample — function-preserving (W += V Bᵀ, B zeroed)
        and the offending V draw is replaced by a fresh draw from the
        paradigm's own admissible law (Haar–Stiefel by default), so
        unbiasedness is untouched."""
        state = dataclasses.replace(opt_state, key=key)
        return subspace.outer_merge_resample(params, state, tcfg)


@register("lowrank_adam")
class LowRankAdamMethod(_LowRankBase):
    name = "lowrank_adam"
    family = "bp"

    def make_inner_step(self, cfg, tcfg,
                        loss_fn: Optional[Callable] = None) -> Callable:
        return self._maybe_fuse(
            steps_mod.make_train_step(cfg, tcfg, loss_fn), tcfg)

    def describe(self):
        return {**super().describe(),
                "gradient": "IPA: autodiff w.r.t. B (n x r, full grad "
                            "never materialised)",
                "optimizer_state": "subspace m/v over B + V per group",
                "projection": "random admissible V, resampled every "
                              "lazy_k steps",
                "compute": "packed W/B/V slices + stored V in "
                           "compute_dtype; fp32 B masters, moments and "
                           "merge accumulate"}


@register("lowrank_lr")
class LowRankLRMethod(_LowRankBase):
    name = "lowrank_lr"
    family = "zo"

    def make_inner_step(self, cfg, tcfg,
                        loss_fn: Optional[Callable] = None) -> Callable:
        return self._maybe_fuse(
            steps_mod.make_zo_train_step(cfg, tcfg, loss_fn), tcfg)

    def describe(self):
        return {**super().describe(),
                "gradient": "likelihood-ratio/ZO: antithetic 2-point "
                            "forward-only estimate (no activations stored)",
                "optimizer_state": "subspace m/v over B + V per group",
                "projection": "random admissible V, resampled every "
                              "lazy_k steps"}
