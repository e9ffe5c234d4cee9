"""Quickstart: train a tiny LLaMA with the paper's optimal low-rank
estimator (Stiefel LowRank-IPA + lazy updates) and inspect what the
optimizer is doing.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro import methods
from repro.configs import TrainConfig, get_config
from repro.data.synthetic import StatelessLoader
from repro.models import lm
from repro.optim import subspace
from repro.train.trainer import Trainer

# --- methods: every gradient-estimation paradigm is a registered Method ----
# tcfg.optimizer resolves through repro.methods.get(name): the Method owns
# state construction, the jitted inner/outer steps, sharding pspecs and the
# checkpoint tag, so the Trainer / dry-run / benchmark tables never branch
# on the name.  A new paradigm is one @methods.register("name") class away:
#
#     @methods.register("my_method")
#     class MyMethod(methods.Method):
#         name = "my_method"
#         def init(self, params, tcfg, key): ...          # build state
#         def make_inner_step(self, cfg, tcfg, loss_fn=None): ...
#         def pspecs(self, mesh, specs, params_abs, opt_abs): ...
#
# and TrainConfig(optimizer="my_method") trains, lowers in the dry-run and
# checkpoints (cross-method resume is refused via the manifest tag).
print(f"registered methods: {', '.join(methods.available())}")
for name in methods.available():
    d = methods.get(name).describe()
    print(f"  {name:13s} [{d['family']}] {d['gradient']}")

cfg = get_config("llama-tiny")
tcfg = TrainConfig(
    optimizer="lowrank_adam",   # Algorithm 1 (IPA family)
    sampler="stiefel",          # Theorem-2-optimal Haar-Stiefel projector
    rank=16,                    # r
    c=1.0,                      # strong unbiasedness
    lazy_k=20,                  # K inner steps per projection resample
    lr=3e-3, warmup_steps=10, total_steps=100,
    min_dim_for_lowrank=64, weight_decay=0.0, seed=0,
    # --- mixed precision: the hot-path compute dtype ---------------------
    # "auto" (the default) = bf16 on TPU/GPU, fp32 on CPU.  Set
    # compute_dtype="bfloat16" (or REPRO_COMPUTE_DTYPE=bfloat16) to force
    # the bf16 hot path anywhere: the packed W/B/V slices and the stored
    # projections are read/written at half width (the roofline win — every
    # hot-path op is memory-bound), while B masters, Adam moments and the
    # master weights stay fp32 and every kernel accumulates in fp32.
    compute_dtype="auto",
    # --- quantized optimizer state ---------------------------------------
    # state_dtype="int8" (or REPRO_STATE_DTYPE=int8) stores the subspace
    # Adam/Lion moments block-quantized: int8 payload + one fp32 absmax
    # scale per 128 elements, with the dequant -> fp32 update -> requant
    # round-trip fused inside the kernels (the fp32 moments exist only in
    # VMEM).  First moments use a linear code; second moments a sqrt code
    # (squared dynamic range — a linear int8 code collapses small-but-live
    # v to zero and detonates m/(sqrt(v)+eps)).  Pair it with
    # master_dtype="bfloat16" (REPRO_MASTER_DTYPE) to also halve the B
    # masters, updated with stochastic rounding (unbiased, PRNG-keyed per
    # step) so round-to-nearest bias cannot accumulate: together they cut
    # the inner step's optimizer-state HBM bytes by ~66% (int8 moments
    # alone: ~50%).  int8-state training tracks the fp32-state loss within
    # 6% over 3 outer cycles (documented tolerance, tested for
    # lowrank_adam AND lowrank_lion); checkpoints restore ACROSS state
    # dtypes in both directions.
    state_dtype="float32", master_dtype="float32",
    # --- resilience: the traced health guard + host escalation ------------
    # Every inner step is wrapped (inside the SAME jitted program — no
    # extra host sync) with non-finite detection on loss/grads/update and
    # an EMA z-score loss-spike detector; a bad step is SKIPPED via
    # lax.cond, leaving params and the grouped state bit-identical.
    # max_consecutive_skips skips in a row escalate on the host: restore
    # the last good checkpoint, multiply the LR by rollback_backoff,
    # reseed the sampler key (fresh Haar–Stiefel draw — unbiasedness
    # untouched), at most max_rollbacks times.  health_guard=False
    # restores the unguarded step.
    health_guard=True, spike_zscore=6.0, spike_warmup=20,
    max_consecutive_skips=3, rollback_backoff=0.5, max_rollbacks=3)

from repro.models.common import resolve_compute_dtype  # noqa: E402
import numpy as np  # noqa: E402
print(f"compute dtype: {np.dtype(resolve_compute_dtype(tcfg)).name} "
      f"(masters/moments stay fp32)")

# --- what the optimizer stores (paper Table 2's mechanism) -----------------
params = lm.init_params(cfg, jax.random.key(0))
acct = subspace.lowrank_param_count(params, tcfg)
print(f"params                 : {acct['param_count']:>10,}")
print(f"Adam state, full       : {acct['adam_state_full']:>10,} floats")
print(f"Adam state, low-rank   : {acct['adam_state_lowrank']:>10,} floats "
      f"({acct['adam_state_full']/acct['adam_state_lowrank']:.1f}x smaller)")

# --- the projector satisfies the Theorem-2 optimality condition ------------
_, state = subspace.init(params, tcfg, jax.random.key(1))
print(f"\ngrouped state: {len(state.groups)} groups over "
      f"{sum(len(s.leaf_idx) for s in state.layout.groups)} low-rank leaves")
v = state.groups[0].proj
while v.ndim > 2:       # stacked projections: inspect one member's V
    v = v[0]
n, r = v.shape[-2], v.shape[-1]
vtv = v.T @ v
print(f"\nV^T V == (c n / r) I_r?  max dev "
      f"{float(jnp.abs(vtv - (n/r)*jnp.eye(r)).max()):.2e} "
      f"(n={n}, r={r})")

# --- train -----------------------------------------------------------------
loader = StatelessLoader("lm", seed=0, batch=8, seq_len=64,
                         vocab=cfg.vocab_size)
trainer = Trainer(cfg, tcfg, loader)

# Master weights live GROUPED during training (same structure-of-arrays
# layout as the optimizer state): each group of same-shape matrices is one
# stacked buffer, so the outer merge W += V B^T runs batched with zero
# per-leaf stack/unstack.  Ungroup only at the API boundary:
print(f"\nmaster weights: {len(trainer.params.groups)} stacked group "
      f"buffers + {len(trainer.params.dense)} dense leaves "
      f"(trainer.model_params gives the model-shaped tree)")
assert set(trainer.model_params) == set(params)

report = trainer.run(60, log_every=10)
print(f"\nloss {report.losses[0]:.3f} -> {report.losses[-1]:.3f} "
      f"over {report.steps_run} steps "
      f"({1e3*sum(report.step_times)/len(report.step_times):.0f} ms/step)")
# the health guard rode along inside the jitted step the whole time:
print(f"health: {report.skipped_steps} skipped steps, "
      f"{report.rollbacks} rollbacks"
      + (f" (lr backed off to {trainer.tcfg.lr:g})" if report.rollbacks
         else ""))
assert report.losses[-1] < report.losses[0]
assert report.skipped_steps == 0 and report.rollbacks == 0
print("quickstart OK")
