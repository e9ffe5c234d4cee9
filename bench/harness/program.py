"""Drive the program under test: build its Trainer on the benchmark's
weights, run the checked first steps, warm every program the window can
reach, and read what the comparison needs before the window starts.

This module is the only one that imports the program's training code.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as wmod
from .data import Loader

CHECKED_STEPS = 3


class Run:
    """One Trainer, built once, driven through set-up and the window."""

    def __init__(self, cell, cfg, tcfg, seed: int, lowrank: dict):
        from repro.models import lm
        from repro.train.trainer import Trainer

        self.cfg, self.tcfg = cfg, tcfg
        self.paths = [p for p, _, _ in wmod.layout(cfg)]
        v_dtype = jnp.dtype(tcfg.compute_dtype)
        flat, vs = wmod.make(cfg, seed, lowrank, v_dtype)
        params = wmod.tree(cfg, flat)
        del flat
        # The Trainer takes its weights from the benchmark, not from its
        # own initialiser: the reference regenerates the same ones.
        made = lm.init_params
        lm.init_params = lambda _cfg, _key: params
        try:
            self.trainer = Trainer(cfg, tcfg, Loader(seed, cell.traffic,
                                                     cfg.vocab_size))
        finally:
            lm.init_params = made
        del params
        self._inject_projections(vs, lowrank)

    def _inject_projections(self, vs: dict, lowrank: dict) -> None:
        tr = self.trainer
        st = tr.opt_state
        groups, seen = [], set()
        for spec, slot in zip(st.layout.groups, st.groups):
            members = [self.paths[i] for i in spec.leaf_idx]
            seen.update(members)
            proj = jnp.stack([vs[m] for m in members]).astype(slot.proj.dtype)
            if proj.shape != slot.proj.shape:
                raise RuntimeError(f"projection shape {proj.shape} != "
                                   f"{slot.proj.shape} for {members}")
            groups.append(slot._replace(proj=proj))
        if seen != set(lowrank):
            raise RuntimeError(
                "the program trains other leaves low-rank than the "
                f"benchmark: {sorted(seen ^ set(lowrank))}")
        tr.opt_state = dataclasses.replace(st, groups=tuple(groups))

    # -- readings for the comparison -------------------------------------

    def _per_leaf(self, lowrank_arrays, dense_arrays) -> dict:
        """{path: per-layer (or scalar) fp32 norms} on the host."""
        st = self.trainer.opt_state
        out = {}
        for spec, arr in zip(st.layout.groups, lowrank_arrays):
            axes = tuple(range(arr.ndim - 2, arr.ndim))
            norms = np.asarray(jnp.sqrt(jnp.sum(
                jnp.square(arr.astype(jnp.float32)), axis=axes)))
            for j, i in enumerate(spec.leaf_idx):
                out[self.paths[i]] = norms[j]
        for di, i in enumerate(st.layout.dense_idx):
            arr = dense_arrays[di].astype(jnp.float32)
            path = self.paths[i]
            axes = (tuple(range(1, arr.ndim)) if wmod.is_layered(path)
                    else tuple(range(arr.ndim)))
            out[path] = np.asarray(jnp.sqrt(jnp.sum(jnp.square(arr),
                                                    axis=axes)))
        return out

    def checked_steps(self) -> dict:
        """Steps 1-3 through the window's own call and loader; returns the
        losses, the first gradient as the optimizer got it (from the first
        moment after one step: m1 = (1 - beta1) g) and the change of every
        trainable after the three steps, all as per-leaf norms."""
        tr = self.trainer
        beta1 = self.tcfg.beta1
        dense0 = [jnp.copy(d) for d in tr.params.dense]
        losses, skipped = [], 0
        rep = tr.run(1)
        losses += rep.losses
        skipped += rep.skipped_steps
        st = tr.opt_state
        grads = self._per_leaf(
            [g.m / (1 - beta1) for g in st.groups],
            [d.m / (1 - beta1) for d in st.dense])
        rep = tr.run(CHECKED_STEPS - 1)
        losses += rep.losses
        skipped += rep.skipped_steps
        change = self._per_leaf(
            [g.b for g in tr.opt_state.groups],
            [a.astype(jnp.float32) - b.astype(jnp.float32)
             for a, b in zip(tr.params.dense, dense0)])
        del dense0
        return {"losses": losses, "grads": grads, "change": change,
                "skipped": skipped}

    def warm_outer(self) -> None:
        """Run the outer merge+resample once, so that a window that crosses
        a multiple of lazy_k finds it compiled."""
        tr = self.trainer
        if tr._outer is not None:
            tr.params, tr.opt_state = jax.block_until_ready(
                tr._outer(tr.params, tr.opt_state))

    def compiled_programs(self) -> int:
        tr = self.trainer
        return tr._inner._cache_size() + (
            tr._outer._cache_size() if tr._outer is not None else 0)

    def window(self, seconds: float, clock) -> dict:
        """Whole steps until ``seconds`` have passed; the window closes at
        the end of the step that crossed it."""
        tr = self.trainer
        losses, skipped = [], 0
        t0 = clock()
        while True:
            with jax.profiler.TraceAnnotation("bench.trainer_run"):
                rep = tr.run(1)
            losses += rep.losses
            skipped += rep.skipped_steps
            if clock() - t0 >= seconds:
                break
        t1 = clock()
        bad = sum(1 for x in losses if not math.isfinite(x))
        return {"t0": t0, "t1": t1, "steps": len(losses), "losses": losses,
                "failed": skipped + bad}

    def close(self) -> None:
        del self.trainer


def routes() -> dict:
    from repro.kernels import dispatch

    return dispatch.route_log()
