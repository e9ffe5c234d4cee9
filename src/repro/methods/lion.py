"""LowRank-Lion: the momentum-only subspace paradigm.

Same Algorithm-1 structure as ``lowrank_adam`` — grouped masters, lazy
outer merge+resample, batched kernels through the dispatch layer — but
the subspace update on B is the sign-based Lion rule

    u  = sign(beta1 * m + (1 - beta1) * g_B)
    B' = B - lr * (u + wd * B)
    m' = beta2 * m + (1 - beta2) * g_B

which keeps ONE moment instead of Adam's two: the subspace optimizer
state halves again on top of whatever ``state_dtype``/``master_dtype``
compress (the v slot degenerates to a zero-size placeholder).  One
registration is the whole integration — the Trainer, dry-run lowering,
checkpoints, sharding pspecs and both benchmark tables pick the method
up from the registry with zero consumer edits.

Note Lion's usual hyper-parameter shifts vs Adam: lr typically 3-10x
smaller, beta2 around 0.99 (the method uses ``tcfg.beta1``/``beta2``
verbatim — set them per the Lion recipe when selecting this method).
"""
from __future__ import annotations

from typing import Callable, Optional

from ..optim import subspace
from ..train import steps as steps_mod
from .lowrank import _LowRankBase
from .registry import register


@register("lowrank_lion")
class LowRankLionMethod(_LowRankBase):
    name = "lowrank_lion"
    family = "bp"

    def init(self, params, tcfg, key):
        return subspace.init(params, tcfg, key, algo="lion")

    def make_inner_step(self, cfg, tcfg,
                        loss_fn: Optional[Callable] = None) -> Callable:
        # the generic train step: the lion branch lives inside
        # subspace.inner_update, keyed off the layout's algo tag
        return self._maybe_fuse(
            steps_mod.make_train_step(cfg, tcfg, loss_fn), tcfg)

    def describe(self):
        return {**super().describe(),
                "gradient": "IPA: autodiff w.r.t. B (n x r, full grad "
                            "never materialised)",
                "optimizer_state": "subspace m ONLY over B + V per group "
                                   "(momentum-only: half the Adam "
                                   "footprint)",
                "projection": "random admissible V, resampled every "
                              "lazy_k steps",
                "compute": "sign-based Lion update; packed W/B/V slices "
                           "in compute_dtype, state storage per "
                           "state_dtype/master_dtype"}
