"""GaLore: full-backprop gradient projected onto a data-dependent basis.

Trainer-selectable through the registry: runs on the same grouped master
weights / grouped state layout as the paper's own paradigms (the per-step
weight write is a pure batched subtract on the stacked buffers).  The SVD
refresh cadence is folded INTO the inner step as a traced
``step % lazy_k == 0`` condition (``optim.galore.make_inner_step``), so
the Trainer needs no GaLore-specific outer scheduling — one jitted
function, no retrace, and resume keeps the cadence because ``step`` rides
in the checkpointed state.
"""
from __future__ import annotations

from typing import Callable, Optional

from ..optim import galore
from ..sharding import rules
from .base import Method
from .registry import register


@register("galore")
class GaLoreMethod(Method):
    name = "galore"
    family = "bp"

    def init(self, params, tcfg, key):
        return galore.init(params, tcfg, key)

    def make_inner_step(self, cfg, tcfg,
                        loss_fn: Optional[Callable] = None) -> Callable:
        return galore.make_inner_step(cfg, tcfg, loss_fn)

    # no outer step: projection refresh happens inside the inner step
    # (it needs that step's full gradient for the SVD)

    def pspecs(self, mesh, specs, params_abs, opt_abs):
        # identical state layout to the subspace paradigms
        return rules.grouped_param_pspecs(mesh, specs, params_abs), \
            rules.state_pspecs(mesh, specs, opt_abs)

    def describe(self):
        return {**super().describe(),
                "gradient": "full backprop (k x n materialised), then "
                            "projected U^T G",
                "optimizer_state": "subspace m/v over projected grad + U "
                                   "per group",
                "projection": "top-r singular basis of the full gradient, "
                              "SVD-refreshed every lazy_k steps (data-"
                              "dependent; not unbiased in the paper's "
                              "Definition-3 sense)",
                "compute": "weight read-view + stored U in compute_dtype; "
                           "fp32 SVD, projection and moments"}
