#!/usr/bin/env python
"""Bring-up smoke run on a TPU chip: the trainer and the serving engine at
llama-100m full width (12 layers, d 640, ff 1712, vocab 32128) with the
compiled Pallas kernels, through the entry points a user calls.

Phases, in order, in this one process (nothing here starts a child that
touches JAX):

  device    the first device is a TPU and no route, interpret, dtype or
            chaos override is set in the environment; never a CPU fallback
  train     Trainer + lowrank_adam, Stiefel r=128, seq 256, batch 64
            (16,384 tokens per step), lazy_k 4, 10 inner steps so the
            outer merge+resample runs twice, fp32 state, donation on;
            then the K-tiled fused backward of the unembedding alone
  train_q8  the same with int8 moments and bf16 stochastically rounded
            masters set through TrainConfig: 5 steps at lazy_k 2
  serve     Engine with two tenant adapters from an AdapterStore, 4
            requests of 128 prompt tokens and 32 new tokens each, greedy;
            then the batched (one adapter per row) prefill kernel alone

Each training phase checks finite losses, no guard skip or rollback, that
the low-rank ops ran as compiled (not interpreted) Pallas kernels with no
XLA fallback, and that its per-step losses agree with a second Trainer
built from the same seeds on the XLA route.  Times, rates and memory are
read on the chip.  Any failed phase fails the run; the last line of
stdout is one JSON object only when every phase passed.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # only the sharded (data=2, model=2)
                                     # training path and its one-chip run
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

# Environment knobs that would change what this run measures: the kernel
# route, Pallas interpret mode, the hot-path / state / master dtypes (the
# int8 phase sets them through TrainConfig, which the env would override)
# and fault injection.
REFUSED_ENV = ("REPRO_KERNEL_DISPATCH", "REPRO_PALLAS_INTERPRET",
               "REPRO_COMPUTE_DTYPE", "REPRO_STATE_DTYPE",
               "REPRO_MASTER_DTYPE", "REPRO_CHAOS")

# Per-step loss agreement between two routes (or between four chips and
# one), relative.  Compute is bf16, which keeps 8 significand bits: one
# rounding moves a value by up to 2^-8 (3.9e-3).  The Pallas kernels and
# the XLA reference round the projected activation p = xV and each layer
# output at different points and sum in different orders (so do sharded
# reductions), but the loss is a mean over 16,384 tokens in which those
# roundings average out, so the gap stays well under one bf16 unit; half
# a unit admits that.  A wrong kernel does not stay inside it: the
# untrained model sits near 10.9 nats and a broken forward or dx moves
# that by whole nats or to NaN.
LOSS_RTOL = 2e-3

SEQ, BATCH, RANK = 256, 64, 128


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def refuse(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def smoke_model():
    from repro.configs.llama_paper import LLAMA_100M
    return LLAMA_100M


def train_config(steps: int, lazy_k: int, **kw):
    from repro.configs.base import TrainConfig
    return TrainConfig(optimizer="lowrank_adam", sampler="stiefel",
                       rank=RANK, lazy_k=lazy_k, lr=1e-3, warmup_steps=2,
                       total_steps=steps, seed=0, **kw)


def train_run(cfg, tcfg, steps: int, batch: int = BATCH, seq: int = SEQ,
              setup=None):
    """A fresh Trainer from ``tcfg.seed`` (never reusing arrays another
    run donated), ``steps`` steps on the seeded synthetic LM stream."""
    from repro.data.synthetic import StatelessLoader
    from repro.train.trainer import Trainer

    loader = StatelessLoader("lm", seed=tcfg.seed, batch=batch,
                             seq_len=seq, vocab=cfg.vocab_size)
    tr = Trainer(cfg, tcfg, loader)
    if setup is not None:
        setup(tr)
    return tr, tr.run(steps)


def check_report(rep, steps: int, lazy_k: int, min_outer: int) -> None:
    import math
    check(rep.steps_run == steps == len(rep.losses),
          f"ran {rep.steps_run} of {steps} steps")
    check(all(math.isfinite(x) for x in rep.losses),
          f"non-finite loss: {rep.losses}")
    check(rep.skipped_steps == 0 and rep.rollbacks == 0
          and not rep.health_exhausted,
          f"health guard: {rep.skipped_steps} skipped, {rep.rollbacks} "
          f"rollbacks, exhausted={rep.health_exhausted}")
    outer = sum(1 for s in range(1, steps) if s % lazy_k == 0)
    check(outer >= min_outer, f"only {outer} outer steps")


def check_compiled(ops) -> None:
    """Each op in ``ops`` ran as a compiled Pallas kernel; no low-rank op
    was interpreted or sent to the XLA fallback."""
    from repro.kernels import dispatch

    keys = dispatch.kernel_cache_info()["keys"]
    compiled = {k[0] for k in keys if k[-1] is False}
    interpreted = sorted({k[0] for k in keys if k[-1] is not False})
    check(not interpreted, f"interpret-mode kernels: {interpreted}")
    check(set(ops) <= compiled,
          f"not compiled as Pallas: {sorted(set(ops) - compiled)}")
    # attention is routed too: llama-100m's 64-wide heads keep the XLA
    # scan (the flash kernel takes heads of a multiple of 128 lanes)
    xla = sorted(k for k, rt in dispatch.route_log().items()
                 if rt == "xla" and k[0] != "attention")
    check(not xla, f"low-rank ops routed to XLA: {xla}")
    print(f"  compiled Pallas kernels: {len(keys)} "
          f"({', '.join(sorted(compiled))})")


def compare_losses(got, ref, what: str) -> float:
    check(len(got) == len(ref), f"{what}: {len(got)} vs {len(ref)} steps")
    worst = max(abs(a - b) / max(1.0, abs(b)) for a, b in zip(got, ref))
    print(f"  losses vs {what}: max relative gap {worst:.3e} "
          f"(tolerance {LOSS_RTOL:g})")
    check(worst <= LOSS_RTOL, f"losses disagree with {what}: {got} vs {ref}")
    return worst


def print_peak() -> None:
    """The device's peak since the process started (earlier phases
    included): the runtime does not reset it between phases."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print("  peak device memory in this process so far: "
          + ("not reported" if peak is None else f"{peak / 2**20:.0f} MiB"))


def _times(rep, lazy_k: int) -> str:
    t = rep.step_times
    outer = {s for s in range(1, len(t)) if s % lazy_k == 0}
    inner = [t[s] for s in range(1, len(t)) if s not in outer]
    med = statistics.median(inner)
    return (f"first step {t[0]:.2f} s (compile + run); steady inner step "
            f"median {med * 1e3:.1f} ms over {len(inner)} steps "
            f"({BATCH * SEQ / med:.0f} tokens/s); steps with an outer "
            f"merge+resample: "
            + ", ".join(f"{t[s]:.2f} s" for s in sorted(outer)))


def train_phase(steps: int, lazy_k: int, ops, min_outer: int, **tcfg_kw):
    from repro.kernels import dispatch

    cfg, tcfg = smoke_model(), train_config(steps, lazy_k, **tcfg_kw)
    dispatch.clear_kernel_cache()
    tr, rep = train_run(cfg, tcfg, steps)
    # more than one program per step means a step recompiled mid-run
    n_progs = (tr._inner._cache_size(), tr._outer._cache_size())
    del tr
    print(f"  losses (Pallas): {[round(x, 4) for x in rep.losses]}")
    print(f"  on the chip: {_times(rep, lazy_k)}; compiled programs: "
          f"inner {n_progs[0]}, outer {n_progs[1]}")
    print_peak()
    check_report(rep, steps, lazy_k, min_outer)
    check_compiled(ops)

    os.environ["REPRO_KERNEL_DISPATCH"] = "xla"
    try:
        dispatch.clear_kernel_cache()
        tr, ref = train_run(cfg, tcfg, steps)
        del tr
    finally:
        del os.environ["REPRO_KERNEL_DISPATCH"]
    check(set(dispatch.route_log().values()) == {"xla"},
          "the reference run did not take the XLA route")
    print(f"  losses (XLA):    {[round(x, 4) for x in ref.losses]}")
    print(f"  on the chip, XLA route: {_times(ref, lazy_k)}")
    compare_losses(rep.losses, ref.losses, "the XLA route")


def phase_train():
    train_phase(10, 4, ("lowrank_forward", "lowrank_backward",
                        "subspace_adam", "lowrank_merge"), min_outer=2)
    check_backward()


def phase_train_q8():
    train_phase(5, 2, ("lowrank_forward", "lowrank_backward",
                       "subspace_adam_q8", "lowrank_merge_sr"),
                min_outer=2, state_dtype="int8", master_dtype="bfloat16")


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

N_REQ, PROMPT, NEW, TENANTS = 4, 128, 32, 2


def adapter_store(cfg, seed: int = 0):
    """Two tenants with random adapters over one shared random V, built as
    examples/serve.py does, at the training rank."""
    import numpy as np
    from repro.configs.base import TrainConfig
    from repro.serve import AdapterStore

    store = AdapterStore(cfg, TrainConfig(optimizer="lowrank_adam",
                                          rank=RANK), max_tenants=TENANTS)
    rng = np.random.default_rng(seed)
    projs = [0.02 * rng.standard_normal(v.shape, np.float32)
             for v in store.projs]
    for t in range(TENANTS):
        bs = [0.02 * rng.standard_normal(b.shape[:-3] + b.shape[-2:],
                                         np.float32)
              for b in store.b_full]
        # the first tenant pins the shared V (stored in the model dtype)
        store.add_tenant(f"tenant{t}", bs, projs if t == 0 else None)
    return store


def serve_round(eng, prompts, tag: str):
    from repro.serve import Request

    for i, p in enumerate(prompts):
        eng.submit(Request(rid=f"{tag}{i}", prompt=p, max_new=NEW,
                           tenant=f"tenant{i % TENANTS}"))
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    rids = [f"{tag}{i}" for i in range(len(prompts))]
    check(all(eng.reasons.get(r) == "completed" for r in rids),
          f"requests not completed: {eng.reasons}")
    check(not eng.errors and not eng.disabled_tenants(),
          f"quarantined: {eng.errors}, disabled {eng.disabled_tenants()}")
    toks = [out[r] for r in rids]
    check(all(len(t) == NEW for t in toks), "short outputs")
    check(all(((t >= 0) & (t < eng.cfg.vocab_size)).all() for t in toks),
          "token ids outside the vocabulary")
    return toks, dt


def phase_serve():
    import jax
    import numpy as np
    from repro.kernels import dispatch
    from repro.models import lm
    from repro.serve import Engine, EngineConfig

    cfg = smoke_model()
    params = lm.init_params(cfg, jax.random.key(0))
    store = adapter_store(cfg)
    prompts = np.asarray(jax.random.randint(
        jax.random.key(1), (N_REQ, PROMPT), 0, cfg.vocab_size), np.int32)
    ecfg = EngineConfig(max_batch=N_REQ, max_len=256, max_out=NEW)

    dispatch.clear_kernel_cache()
    eng = Engine(params, cfg, adapters=store, engine_cfg=ecfg)
    first, dt_cold = serve_round(eng, prompts, "cold")
    again, dt = serve_round(eng, prompts, "warm")
    check(eng.traces == 1, f"decode traced {eng.traces} times")
    check(all((a == b).all() for a, b in zip(first, again)),
          "greedy outputs changed between two identical rounds")
    routes = dispatch.route_log()
    check(routes.get(("lowrank_forward", (PROMPT, 640, 640, RANK)))
          == "pallas", f"prefill did not take the Pallas forward: {routes}")
    # decode feeds one token per row, which the guard sends to the XLA
    # einsum (a row would fill one sublane of a padded tile), and the
    # 64-wide heads keep the XLA attention; nothing else may leave the
    # kernels
    xla = sorted(k for k, rt in routes.items() if rt == "xla"
                 and k[0] != "attention"
                 and not (k[0] == "lowrank_batch_forward" and k[1][0] == 1))
    check(not xla, f"serving ops routed to XLA: {xla}")
    check_no_interpreted()
    n = N_REQ * NEW
    print(f"  on the chip: first round {dt_cold:.2f} s (compile + run); "
          f"second round {n} tokens in {dt * 1e3:.1f} ms = {n / dt:.1f} "
          f"tokens/s at batch {N_REQ}; engine.traces = {eng.traces}")
    print("  routes: " + ", ".join(
        f"{op}{list(s)}={rt}" for (op, s), rt in sorted(routes.items())))
    print_peak()

    os.environ["REPRO_KERNEL_DISPATCH"] = "xla"
    try:
        ref_eng = Engine(params, cfg, adapters=store, engine_cfg=ecfg)
        ref, _ = serve_round(ref_eng, prompts, "xla")
    finally:
        del os.environ["REPRO_KERNEL_DISPATCH"]
    same = sum(int((a == b).sum()) for a, b in zip(first, ref))
    lead = sum(int(np.argmin(np.append(a == b, False))) for a, b in
               zip(first, ref))
    # not gated: random weights give near-tied logits, so one bf16
    # rounding can flip a greedy pick and the continuation after it
    print(f"  tokens agreeing with the XLA-route engine: {same}/{n} "
          f"position-wise, {lead}/{n} before each request's first "
          f"divergence")
    check_batch_forward()


def check_no_interpreted() -> None:
    """Kernels were built since the last cache clear, none in interpret
    mode (the last key field)."""
    from repro.kernels import dispatch

    keys = dispatch.kernel_cache_info()["keys"]
    check(keys and all(k[-1] is False for k in keys),
          f"kernels not compiled: {keys}")


# A kernel's agreement with an fp32 reference, relative to the output's
# largest magnitude: inputs and outputs are bf16 (2^-8 relative rounding
# each), the kernels accumulate in fp32, so a correct kernel stays within
# a few units of 2^-8; a wrong block index or chunk offset is off by O(1).
KERNEL_RTOL = 2e-2


def _bf16_operands(key, *shapes):
    """Seeded bf16 operands, each scaled by 1/sqrt(its first dim)."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.key(key), len(shapes))
    return [(jax.random.normal(k, s) / s[0] ** 0.5).astype(jnp.bfloat16)
            for k, s in zip(keys, shapes)]


def check_kernel(what: str, op: str, shapes, got, ref) -> None:
    """``op`` at ``shapes`` took the compiled Pallas kernel and each of
    ``got`` is within ``KERNEL_RTOL`` of its fp32 reference in ``ref``."""
    import jax.numpy as jnp
    from repro.kernels import dispatch

    check(dispatch.route_log() == {(op, shapes): "pallas"},
          f"{what} routes: {dispatch.route_log()}")
    check_no_interpreted()
    errs = [float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))
                  / jnp.max(jnp.abs(r))) for g, r in zip(got, ref)]
    print(f"  {what}: compiled, max error "
          + ", ".join(f"{e:.3e}" for e in errs)
          + f" of the largest output (tolerance {KERNEL_RTOL:g})")
    check(max(errs) <= KERNEL_RTOL, f"{what} error {errs}")


def check_backward() -> None:
    """The fused backward at the llama-100m unembedding (16,384 rows,
    640 -> 32256 padded vocab), on the blocks the dispatch layer picks
    from the shape: 32 row blocks gather the 16 MiB resident dB, which
    the last one writes out; dx and dB against an fp32 reference."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch

    m, k, n = BATCH * SEQ, 640, 32256
    dy, w, v, b, p = _bf16_operands(3, (m, n), (k, n), (k, RANK),
                                    (n, RANK), (m, RANK))
    dispatch.clear_kernel_cache()
    dx, db = jax.jit(dispatch.lowrank_backward)(dy, w, v, b, p)
    (key,) = dispatch.kernel_cache_info()["keys"]
    dy, w, v, b, p = (a.astype(jnp.float32) for a in (dy, w, v, b, p))
    hi = jax.lax.Precision.HIGHEST
    ref_dx = (jnp.dot(dy, w.T, precision=hi)
              + jnp.dot(jnp.dot(dy, b, precision=hi), v.T, precision=hi))
    bm, bn, bk = key[3]
    check_kernel(f"fused backward, unembedding, blocks bm {bm} bk {bk} "
                 f"bn {bn}",
                 "lowrank_backward", (m, k, n, RANK), (dx, db),
                 (ref_dx, jnp.dot(dy.T, p, precision=hi)))


def check_batch_forward() -> None:
    """The vmapped one-adapter-per-row kernel meant for batched prefill
    (the Engine prefills one request at a time today), at 4 rows x 128
    tokens against the llama-100m up-projection: compiled on the chip and
    within bf16 rounding of an fp32 reference."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import dispatch

    k, n = 640, 1712
    x, w, v, b = _bf16_operands(2, (N_REQ, PROMPT, k), (k, n), (k, RANK),
                                (N_REQ, n, RANK))
    dispatch.clear_kernel_cache()
    y = jax.jit(dispatch.lowrank_batch_forward)(x, w, v, b)
    x, w, v, b = (a.astype(jnp.float32) for a in (x, w, v, b))
    hi = jax.lax.Precision.HIGHEST
    ref = (jnp.einsum("bsk,kn->bsn", x, w, precision=hi)
           + jnp.einsum("bsk,kr,bnr->bsn", x, v, b, precision=hi))
    check_kernel(f"batched prefill kernel ({N_REQ} rows x {PROMPT} "
                 f"tokens, one adapter per row)", "lowrank_batch_forward",
                 (PROMPT, k, n, RANK), (y,), (ref,))


# ---------------------------------------------------------------------------
# Four chips: the sharded training path against one chip
# ---------------------------------------------------------------------------

FOUR_STEPS, FOUR_LAZY_K = 4, 3    # inner 0-2, outer merge+resample, inner 3


def _leaves(tr) -> dict:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(
        {"params": tr.params, "opt": tr.opt_state})[0]
    return {jax.tree_util.keystr(p): leaf for p, leaf in flat}


def g_split_names(tr) -> list:
    """Paths of the grouped buffers whose pspec splits the G axis (0)."""
    out = []
    for name, leaf in _leaves(tr).items():
        spec = getattr(leaf.sharding, "spec", None)
        if "groups" in name and spec and spec[0] is not None:
            out.append(name)
    return out


def check_spread(tr, names, n_dev: int, when: str) -> None:
    """Each named buffer is held in G-slices by ``n_dev`` devices."""
    check(names, f"{when}: no grouped buffer has its G axis split")
    leaves = _leaves(tr)
    for name in names:
        a = leaves[name]
        shards = a.addressable_shards
        check(len({s.device for s in shards}) == n_dev,
              f"{when}: {name} lives on "
              f"{sorted({str(s.device) for s in shards})}")
        check(len({s.index[0] for s in shards}) > 1
              and all(s.data.shape[0] < a.shape[0] for s in shards),
              f"{when}: {name} is not split along G across devices")
    a = leaves[names[0]]
    print(f"  {when}: {len(names)} G-split buffers, each spread over "
          f"{n_dev} devices (e.g. {names[0]} {a.shape} -> "
          f"{a.addressable_shards[0].data.shape} per device)")


def four_chip_phase(cfg, tcfg, steps: int, batch: int, seq: int,
                    devices) -> None:
    import jax
    from repro.sharding import ctx as shard_ctx

    _, one = train_run(cfg, tcfg, steps, batch, seq)
    check_report(one, steps, tcfg.lazy_k, min_outer=1)
    print(f"  one chip:   losses {[round(x, 4) for x in one.losses]}")
    mesh = jax.make_mesh(
        (2, 2), ("data", "model"), devices=devices[:4],
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    names = []
    shard_ctx.set_mesh(mesh)
    try:
        def setup(t):
            t.shard(mesh)
            names.extend(g_split_names(t))
            check_spread(t, names, 4, "placed")

        tr, four = train_run(cfg, tcfg, steps, batch, seq, setup=setup)
        check_spread(tr, names, 4, "after the outer step")
    finally:
        shard_ctx.set_mesh(None)
    check_report(four, steps, tcfg.lazy_k, min_outer=1)
    print(f"  four chips: losses {[round(x, 4) for x in four.losses]}")
    print(f"  on the chips: {_times(four, tcfg.lazy_k)}; every step: "
          + ", ".join(f"{t:.3f} s" for t in four.step_times))
    compare_losses(four.losses, one.losses, "one chip")


def phase_four_chips():
    import jax

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    four_chip_phase(smoke_model(), train_config(FOUR_STEPS, FOUR_LAZY_K),
                    FOUR_STEPS, BATCH, SEQ, devs)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded (data=2, model=2) training "
                         "path on four chips and its one-chip comparison")
    args = ap.parse_args(argv)

    set_env = [v for v in REFUSED_ENV if os.environ.get(v)]
    if set_env:
        refuse(f"unset {', '.join(set_env)}: the smoke run measures the "
               f"default kernel routes and dtypes")
    if not os.path.isdir(os.path.join(SRC, "repro")):
        refuse(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax
    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}")
    if dev.platform != "tpu":
        refuse(f"JAX found no TPU (platform {dev.platform!r}); this run "
               f"never falls back to another device")
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"compile cache: {cache} ({'warm' if warm else 'cold'})")

    phases = ([("four_chips", phase_four_chips)] if args.four_chips else
              [("train", phase_train), ("train_q8", phase_train_q8),
               ("serve", phase_serve)])
    failed = []
    for name, fn in phases:
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
            verdict = "PASS"
        except Exception:
            traceback.print_exc()
            failed.append(name)
            verdict = "FAIL"
        print(f"[{name}] {verdict} in {time.perf_counter() - t0:.1f} s",
              flush=True)
    if failed:
        print(f"failed phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
