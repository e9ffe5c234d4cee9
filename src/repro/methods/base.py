"""The Method protocol: one gradient-estimation paradigm, end to end.

A ``Method`` owns everything the rest of the system needs to train with a
paradigm — state construction, the jit-able inner/outer steps, sharding
rules for its state, a checkpoint tag, and a self-description for the
paper's comparison tables.  Consumers never branch on ``tcfg.optimizer``;
they call these five hooks through ``methods.get(tcfg.optimizer)``:

  * ``Trainer``           — init / make_inner_step / make_outer_step /
                            checkpoint_tag
  * ``launch.cells``      — init (under ``jax.eval_shape``) + pspecs for
                            the dry-run lowering
  * ``train.checkpoint``  — checkpoint_tag (cross-method resume refusal)
  * benchmark tables      — init + make_inner_step + describe
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple


class Method(abc.ABC):
    """One gradient-estimation paradigm (strategy object, stateless)."""

    #: registry name == the ``tcfg.optimizer`` string
    name: str = ""
    #: gradient family: "bp" (backprop/IPA) or "zo" (forward-only/LR)
    family: str = "bp"

    @property
    def checkpoint_tag(self) -> str:
        """Tag written into checkpoint manifests; a resume under a method
        with a different tag is refused (the state trees are not
        interchangeable)."""
        return self.name

    @abc.abstractmethod
    def init(self, params, tcfg, key) -> Tuple[Any, Any]:
        """Build the paradigm's training state from a model param tree.

        Returns ``(params, opt_state)`` — ``params`` may be re-represented
        (e.g. grouped structure-of-arrays master weights); the pair is the
        canonical donated carry of both jitted steps.  Must be safe under
        ``jax.eval_shape`` (the dry-run lowers cells abstractly).
        """

    @abc.abstractmethod
    def make_inner_step(self, cfg, tcfg,
                        loss_fn: Optional[Callable] = None) -> Callable:
        """The jit-able hot-path step:
        ``step(params, opt_state, batch) -> (params, opt_state, metrics)``
        with ``metrics["loss"]`` always present."""

    def make_outer_step(self, cfg, tcfg) -> Optional[Callable]:
        """The every-``lazy_k``-steps step
        (``step(params, opt_state) -> (params, opt_state)``), or ``None``
        when the paradigm has no outer phase (runs everything per-step)."""
        return None

    @abc.abstractmethod
    def pspecs(self, mesh, specs, params_abs, opt_abs) -> Tuple[Any, Any]:
        """PartitionSpec trees ``(param_pspecs, opt_pspecs)`` matching the
        structures ``init`` returns, for the dry-run / production mesh.

        ``specs`` is the model's ``ParamSpec`` tree; ``params_abs`` /
        ``opt_abs`` the abstract shapes of this method's state (from
        ``jax.eval_shape`` over ``init``).  Feed the results to
        ``sharding.rules.named_shardings``.
        """

    def reseed(self, params, opt_state, key, tcfg) -> Tuple[Any, Any]:
        """Rotate the paradigm's stochastic draw state after an anomaly
        rollback, so a bad V/perturbation draw is not replayed verbatim
        when the Trainer restores the last good checkpoint.

        Default: replace an ``opt_state.key`` PRNG leaf when the state
        carries one (dataclass or NamedTuple), else a no-op — correct for
        paradigms with no sampling (dense AdamW) or a data-dependent
        projection (GaLore's SVD refresh re-derives itself).  Subspace
        paradigms override this to also draw a fresh projection.
        """
        if hasattr(opt_state, "key"):
            try:
                return params, dataclasses.replace(opt_state, key=key)
            except TypeError:
                pass
            if hasattr(opt_state, "_replace"):
                return params, opt_state._replace(key=key)
        return params, opt_state

    def describe(self) -> Dict[str, str]:
        """Human/table-facing description (the memory table).

        Subclasses override the defaults; every key here is part of the
        contract, so a minimally-registered method (just the three
        abstract hooks) still renders in every consumer listing.
        """
        return {"name": self.name, "family": self.family,
                "checkpoint_tag": self.checkpoint_tag,
                "gradient": "(undescribed)",
                "optimizer_state": "(undescribed)",
                "projection": "(undescribed)",
                "compute": "tcfg.compute_dtype (auto: bf16 on TPU/GPU) "
                           "reads; fp32 masters/moments"}

    def __repr__(self) -> str:  # registry listings
        return f"<Method {self.name} ({self.family})>"
