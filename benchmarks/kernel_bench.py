"""Fused-vs-unfused timings for the low-rank hot-path kernels.

Per op shape it times:
  * ``unfused_compiled`` — the jitted XLA reference schedule (kernels/ref.py
    expressions; what the hot path ran before the dispatch layer);
  * ``fused_interpret``  — the Pallas kernel in interpret mode (numerics
    route on CPU; NOT a perf number, recorded to track interpreter drift);
  * ``fused_compiled``   — the compiled Pallas kernel (TPU only; None when
    this host has no TPU).

plus one end-to-end inner-train-step timing (the Algorithm-1 hot loop with
every op routed through kernels/dispatch.py) against the same step with the
dispatch table pinned to the XLA route.  Results land in
``BENCH_kernels.json`` next to the repo root, seeding the perf trajectory;
each op entry carries its roofline arithmetic-intensity record
(analysis/roofline.lowrank_kernel_entry).

Usage:  PYTHONPATH=src python benchmarks/kernel_bench.py [--out PATH]
        REPRO_BENCH_FAST=0 for the full shape sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import roofline
from repro.kernels import dispatch, ref
from repro.kernels.lowrank_backward import lowrank_backward as pl_backward
from repro.kernels.lowrank_forward import lowrank_forward as pl_forward
from repro.kernels.lowrank_update import lowrank_merge as pl_merge
from repro.kernels.subspace_adam import subspace_adam as pl_adam

FAST = os.environ.get("REPRO_BENCH_FAST", "1") != "0"

# (M, K, N, r): tokens x in-dim x out-dim x rank, MXU-aligned
OP_SHAPES = [
    (256, 256, 256, 16),
    (256, 512, 512, 32),
    (512, 512, 1024, 64),
] + ([] if FAST else [(1024, 1024, 4096, 128)])


def _timeit(fn, *args, iters: int = 5) -> float:
    out = jax.block_until_ready(fn(*args))     # warmup / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _arrs(m, k, n, r, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return dict(x=f(m, k), w=f(k, n), v=f(k, r), b=f(n, r), dy=f(m, n),
                p=f(m, r), g=f(n, r), mom=jnp.abs(f(n, r)) * 0.1,
                vel=jnp.abs(f(n, r)) * 0.01)


def _unfused_fns():
    """The dispatch layer's own XLA-route impls, jitted — so the baseline
    is definitionally the schedule the hot path falls back to."""
    import functools
    fwd = jax.jit(functools.partial(dispatch._xla_forward, return_p=False))
    bwd = jax.jit(dispatch._xla_backward)
    merge = jax.jit(ref.lowrank_merge)
    adam = jax.jit(lambda b, g, m, v: ref.subspace_adam(
        b, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, wd=0.0,
        step=10.0))
    return fwd, bwd, merge, adam


def bench_ops() -> list:
    on_tpu = jax.default_backend() == "tpu"
    fwd_u, bwd_u, merge_u, adam_u = _unfused_fns()
    rows = []
    for (m, k, n, r) in OP_SHAPES:
        a = _arrs(m, k, n, r)
        interp_iters = 1      # interpret mode is python-speed
        ops = {
            "lowrank_forward": (
                lambda itp: pl_forward(a["x"], a["w"], a["v"], a["b"],
                                       interpret=itp),
                lambda: fwd_u(a["x"], a["w"], a["v"], a["b"])),
            "lowrank_backward": (
                lambda itp: pl_backward(a["dy"], a["w"], a["v"], a["b"],
                                        a["p"], interpret=itp),
                lambda: bwd_u(a["dy"], a["w"], a["v"], a["b"], a["p"])),
            "lowrank_merge": (
                lambda itp: pl_merge(a["w"], a["v"], a["b"], interpret=itp),
                lambda: merge_u(a["w"], a["v"], a["b"])),
            "subspace_adam": (
                lambda itp: pl_adam(a["b"], a["g"], a["mom"], a["vel"],
                                    lr=1e-3, step=10.0, interpret=itp),
                lambda: adam_u(a["b"], a["g"], a["mom"], a["vel"])),
        }
        for name, (fused, unfused) in ops.items():
            fused_compiled_ms = None
            if on_tpu:
                # one jit instance reused across timed iterations — a fresh
                # jax.jit per call would retrace and time the compiler
                jf = jax.jit(lambda fused=fused: fused(False))
                fused_compiled_ms = 1e3 * _timeit(jf, iters=10)
            ent_f32 = roofline.lowrank_kernel_entry(name, m, k, n, r,
                                                    itemsize=4)
            ent_bf16 = roofline.lowrank_kernel_entry(name, m, k, n, r,
                                                     itemsize=2)
            row = {
                "op": name, "shape": {"m": m, "k": k, "n": n, "r": r},
                "backend": jax.default_backend(),
                "unfused_compiled_ms":
                    1e3 * _timeit(lambda: unfused(), iters=10),
                "fused_interpret_ms":
                    1e3 * _timeit(lambda: fused(True), iters=interp_iters),
                "fused_compiled_ms": fused_compiled_ms,
                "roofline": ent_f32,
                # bf16-vs-fp32 bytes accessed (roofline-derived, per-
                # operand dtypes: dB / Adam state stay fp32 by contract)
                "bytes_accessed": {
                    "f32_fused": ent_f32["bytes_fused"],
                    "bf16_fused": ent_bf16["bytes_fused"],
                    "bf16_vs_f32_fused":
                        ent_bf16["bytes_fused"] / ent_f32["bytes_fused"],
                    "bf16_by_dtype": ent_bf16["bytes_by_dtype"]["fused"],
                },
            }
            rows.append(row)
            print(f"{name} {m}x{k}x{n} r={r}: "
                  f"unfused {row['unfused_compiled_ms']:.3f} ms, "
                  f"interp {row['fused_interpret_ms']:.1f} ms, "
                  f"compiled {row['fused_compiled_ms']}")
    return rows


def bench_train_step() -> dict:
    """End-to-end inner step: dispatch-routed vs XLA-pinned (same step).

    The step comes from the registered Method (init + inner step exactly
    as the Trainer runs them, grouped master weights included), so the
    recorded ``method`` provenance tag is true by construction.
    """
    from repro import methods
    from repro.configs import TrainConfig, get_config
    from repro.data.synthetic import lm_batch
    from repro.models import lm

    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                       lazy_k=10, lr=1e-3, warmup_steps=0, total_steps=100,
                       min_dim_for_lowrank=64, schedule="constant")
    method = methods.get(tcfg.optimizer)
    params, opt = method.init(lm.init_params(cfg, jax.random.key(0)), tcfg,
                              jax.random.key(1))
    batch_n, seq = 4, 64
    batch = lm_batch(0, 0, batch=batch_n, seq_len=seq, vocab=cfg.vocab_size)
    step = jax.jit(method.make_inner_step(cfg, tcfg))

    # Roofline-derived bytes of this grouped inner step under bf16 vs fp32
    # compute (host-independent: pure traffic model over the real layout —
    # the acceptance gate for the mixed-precision hot path lives on this)
    lead = lambda s: int(np.prod(s[:-2])) if len(s) > 2 else 1
    groups = [(spec.shape[-2], spec.shape[-1], spec.rank,
               len(spec.leaf_idx) * lead(spec.shape))
              for spec in opt.layout.groups]
    tokens = batch_n * seq
    bytes_f32 = roofline.lowrank_inner_step_bytes(groups, tokens, "f32")
    bytes_bf16 = roofline.lowrank_inner_step_bytes(groups, tokens, "bf16")

    # Optimizer-state traffic under the state_dtype/master_dtype knobs:
    # fp32-state baseline vs the profile REPRO_STATE_DTYPE=int8 ships
    # (int8 m/v payloads + per-block fp32 scales + stochastically rounded
    # bf16 B masters).  int8 moments with fp32 masters land at ~49.5%
    # (below the floor) — the quantized profile pairs both knobs.  The
    # >= 50% state-bytes floor in check_regression.py gates this record.
    state_f32 = roofline.lowrank_inner_step_bytes(
        groups, tokens, "bf16", state_dtype="float32",
        master_dtype="float32")
    state_i8 = roofline.lowrank_inner_step_bytes(
        groups, tokens, "bf16", state_dtype="int8",
        master_dtype="bfloat16")

    def run():
        p, o, metr = step(params, opt, batch)
        return metr["loss"]

    # The guarded step (health non-finite + spike detection fused into the
    # same jitted program) — its ms-ratio vs the raw step is the "the
    # guard is free" acceptance gate in check_regression.py
    from repro.train import health as health_mod
    hstate = health_mod.init_health()
    gstep = jax.jit(health_mod.guard_inner_step(
        method.make_inner_step(cfg, tcfg), tcfg))

    def run_guarded():
        p, o, h, metr = gstep(params, opt, hstate, batch)
        return metr["health"]

    prev = os.environ.get("REPRO_KERNEL_DISPATCH")
    try:
        os.environ["REPRO_KERNEL_DISPATCH"] = "xla"
        xla_ms = 1e3 * _timeit(run, iters=5)
        guarded_ms = 1e3 * _timeit(run_guarded, iters=5)
        routed_ms = xla_ms
        if jax.default_backend() == "tpu":
            os.environ.pop("REPRO_KERNEL_DISPATCH", None)
            step = jax.jit(method.make_inner_step(cfg, tcfg))
            routed_ms = 1e3 * _timeit(run, iters=5)
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNEL_DISPATCH", None)
        else:
            os.environ["REPRO_KERNEL_DISPATCH"] = prev
    return {"arch": "llama-tiny", "batch": batch_n, "seq": seq,
            "backend": jax.default_backend(),
            # provenance: which registered method produced these columns
            # (bench-smoke's methods-registry gate checks this)
            "method": method.name,
            # provenance: the compute dtype the timed step actually ran at
            "compute_dtype": opt.layout.compute_dtype,
            # provenance: how the timed step stored its optimizer state
            "state_dtype": opt.layout.state_dtype,
            "master_dtype": opt.layout.master_dtype,
            "inner_step_xla_ms": xla_ms,
            "inner_step_dispatch_ms": routed_ms,
            # health-guarded step on the same route: the skip-step guard
            # must be ~free (gated at <= 25% overhead in check_regression)
            "inner_step_guarded_ms": guarded_ms,
            "inner_bytes_by_dtype": {
                "float32": bytes_f32["bytes"],
                "bfloat16": bytes_bf16["bytes"],
                "bf16_breakdown": bytes_bf16["by_dtype"],
                # fraction of HBM traffic the bf16 hot path removes
                "reduction": 1.0 - bytes_bf16["bytes"] / bytes_f32["bytes"],
            },
            # roofline-derived optimizer-state bytes (B + moments + scales)
            # of one inner step: fp32-state baseline vs the int8 profile
            "state_bytes_by_dtype": {
                "float32": state_f32["state_bytes"],
                "int8": state_i8["state_bytes"],
                "int8_profile": {
                    "state_dtype": state_i8["state_dtype"],
                    "master_dtype": state_i8["master_dtype"],
                    "state_block": state_i8["state_block"],
                },
                # fraction of state traffic the int8+bf16 profile removes
                "reduction":
                    1.0 - state_i8["state_bytes"] / state_f32["state_bytes"],
            }}


def bench_grouped_state() -> dict:
    """Structure-of-arrays state AND master weights vs per-leaf layouts.

    ``grouped_*`` runs the hot path (GroupedParams + pre-stacked group
    buffers straight into the batched kernels; the outer step is a pure
    batched merge on the stacked weights — zero stack/unstack);
    ``tree_outer_ms`` the raw-model-tree compat path (same batched merge
    but with the per-group weight stack/unstack the grouped masters
    retire — the pre-ISSUE-3 hot path, i.e. the "before" number);
    ``weight_stack_unstack_ms`` isolates exactly that retired cost (one
    jitted stack + unstack round-trip of all master weights);
    ``ungrouped_*`` the per-leaf reference (``subspace.inner_update_ref``
    / ``outer_merge_resample_ref``): one kernel call, one energy einsum
    and one sampler draw per leaf.  All are jitted, so deltas are pure
    layout.
    """
    from repro.configs import TrainConfig, get_config
    from repro.models import lm
    from repro.optim import subspace

    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                       lazy_k=10, lr=1e-3, warmup_steps=0, total_steps=100,
                       min_dim_for_lowrank=64, schedule="constant")
    method_name = tcfg.optimizer  # provenance tag stays true by construction
    params = lm.init_params(cfg, jax.random.key(0))
    state = subspace.init(params, tcfg, jax.random.key(1))
    gp = subspace.group_params(params, state.layout)
    trainable = subspace.trainable_of(gp, state)
    rng = np.random.default_rng(3)
    grads = jax.tree.map(
        lambda t: jnp.asarray(rng.normal(size=t.shape) * 1e-2, t.dtype),
        trainable)

    inner_g = jax.jit(lambda g, t, p, s: subspace.inner_update(
        g, t, p, s, lr=1e-3, tcfg=tcfg))
    inner_u = jax.jit(lambda g, t, p, s: subspace.inner_update_ref(
        g, t, p, s, lr=1e-3, tcfg=tcfg))
    outer_g = jax.jit(lambda p, s: subspace.outer_merge_resample(p, s, tcfg))
    outer_t = jax.jit(lambda p, s: subspace.outer_merge_resample(p, s, tcfg))
    outer_u = jax.jit(lambda p, s: subspace.outer_merge_resample_ref(
        p, s, tcfg))
    stack_rt = jax.jit(lambda p: subspace.params_of(
        subspace.group_params(p, state.layout)))

    # Per-call interleaved min: scheduler noise on shared CPU hosts swamps
    # back-to-back block timings, and whichever candidate runs second in a
    # block inherits warm caches.  Alternate single calls (order flipped
    # every round) and keep each candidate's best observation.
    cands = {
        "grouped_inner_ms": (inner_g, (grads, trainable, gp, state)),
        "ungrouped_inner_ms": (inner_u, (grads, trainable, params, state)),
        "grouped_outer_ms": (outer_g, (gp, state)),
        "tree_outer_ms": (outer_t, (params, state)),
        "ungrouped_outer_ms": (outer_u, (params, state)),
        "weight_stack_unstack_ms": (stack_rt, (params,)),
    }
    best = {k: float("inf") for k in cands}
    for fn, args in cands.values():
        jax.block_until_ready(fn(*args))          # compile outside timing
    names = list(cands)
    # ~1 ms/call: 150 rounds cost under a second (the 4 jit compiles above
    # dominate this section), so fast mode keeps full statistical quality;
    # the full sweep buys extra samples for the noise floor.
    for rep in range(150 if FAST else 400):
        order = names if rep % 2 == 0 else names[::-1]
        for k in order:
            fn, args = cands[k]
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            best[k] = min(best[k], 1e3 * (time.perf_counter() - t0))

    def _cost(jitted, *args):
        c = jitted.lower(*args).compile().cost_analysis()
        if isinstance(c, list):
            c = c[0]
        return {"flops": c.get("flops"), "bytes": c.get("bytes accessed")}

    # compiled-work ground truth (noise-free): the grouped inner step does
    # IDENTICAL flops/bytes to the per-leaf layout — any ms delta is host
    # scheduling noise, not extra work
    hlo = {
        "grouped_inner": _cost(inner_g, grads, trainable, gp, state),
        "ungrouped_inner": _cost(inner_u, grads, trainable, params, state),
        "grouped_outer": _cost(outer_g, gp, state),
        "tree_outer": _cost(outer_t, params, state),
        "ungrouped_outer": _cost(outer_u, params, state),
    }
    out = {
        "arch": "llama-tiny", "backend": jax.default_backend(),
        # provenance: every timing column here exercises this method's
        # machinery (bench-smoke's methods-registry gate)
        "method": method_name,
        # provenance: the grouped inner/outer ratio gate only compares
        # same-dtype runs (check_regression skips on a tag mismatch)
        "compute_dtype": state.layout.compute_dtype,
        # provenance: how this section's state was stored
        "state_dtype": state.layout.state_dtype,
        "master_dtype": state.layout.master_dtype,
        "n_groups": len(state.groups),
        "n_lowrank_leaves": sum(len(s.leaf_idx)
                                for s in state.layout.groups),
        **best,
        "hlo_cost": hlo,
    }
    print(f"grouped state ({out['n_lowrank_leaves']} leaves in "
          f"{out['n_groups']} groups): "
          f"inner {out['grouped_inner_ms']:.3f} vs "
          f"{out['ungrouped_inner_ms']:.3f} ms, "
          f"outer {out['grouped_outer_ms']:.3f} (grouped W) vs "
          f"{out['tree_outer_ms']:.3f} (tree W) vs "
          f"{out['ungrouped_outer_ms']:.3f} ms (per-leaf), "
          f"W stack/unstack alone {out['weight_stack_unstack_ms']:.3f} ms")
    return out


def bench_serve() -> dict:
    """Multi-tenant serving: engine tokens/sec + lazy-vs-merged decode bytes.

    Times the continuous-batching engine end to end (prefill + batched
    paged decode, two tenants with distinct B adapters answered by one
    fused ``W + V Bᵀ`` forward per step) and records the roofline-derived
    weight-stream bytes of one decode step, lazy vs the merged-per-tenant
    alternative — the host-independent column check_regression.py floors.
    """
    from repro.configs import TrainConfig, get_config
    from repro.models import lm
    from repro.serve import AdapterStore, Engine, EngineConfig, Request

    cfg = get_config("llama-tiny")
    tcfg = TrainConfig(optimizer="lowrank_adam", sampler="stiefel", rank=8,
                       lazy_k=10, lr=1e-3, warmup_steps=0, total_steps=100,
                       min_dim_for_lowrank=64, schedule="constant")
    n_tenants, n_req, prompt_len, gen = 2, 4, 16, 8
    params = lm.init_params(cfg, jax.random.key(0))
    store = AdapterStore(cfg, tcfg, max_tenants=n_tenants)
    rng = np.random.default_rng(7)
    projs = [0.02 * rng.standard_normal(v.shape).astype(np.float32)
             for v in store.projs]
    for t in range(n_tenants):
        bs = [0.02 * rng.standard_normal(
            b.shape[:-3] + b.shape[-2:]).astype(np.float32)
            for b in store.b_full]
        store.add_tenant(f"tenant{t}", bs, projs)
    toks = np.asarray(jax.random.randint(
        jax.random.key(1), (n_req, prompt_len), 0, cfg.vocab_size))

    def time_engine(guard):
        ecfg = EngineConfig(page_size=8, max_batch=n_req,
                            max_len=prompt_len + gen, max_out=gen,
                            guard=guard)
        eng = Engine(params, cfg, adapters=store, engine_cfg=ecfg)

        def submit_all(tag):
            for i in range(n_req):
                eng.submit(Request(f"{tag}{i}", toks[i], gen,
                                   tenant=f"tenant{i % n_tenants}"))

        submit_all("warm")
        eng.run()                             # compile prefill + decode
        iters = 3 if FAST else 10
        best_s = float("inf")
        for it in range(iters):
            submit_all(f"r{it}-")
            t0 = time.perf_counter()
            out = eng.run()
            best_s = min(best_s, time.perf_counter() - t0)
        return eng, best_s, out

    # unguarded reference vs the traced row-health guard (PR 10): the
    # guard adds a per-row finite/collapse check + masked write-back and
    # ONE fetched fault vector per step — check_regression caps its
    # overhead and requires the guarded program to stay single-trace
    raw_eng, raw_s, out = time_engine(guard=False)
    g_eng, g_s, _ = time_engine(guard=True)
    ecfg = raw_eng.ecfg
    n_tok = sum(len(v) for v in out.values())
    best_s = raw_s
    eng = g_eng

    lead = lambda s: int(np.prod(s[:-2])) if len(s) > 2 else 1
    groups = [(spec.shape[-2], spec.shape[-1], spec.rank,
               len(spec.leaf_idx) * lead(spec.shape))
              for spec in store.layout.groups]
    sb = roofline.serve_decode_bytes(
        groups, batch=n_req, tenants=n_tenants,
        compute_dtype="bf16" if store.layout.compute_dtype != "float32"
        else "f32")
    out_rec = {
        "arch": "llama-tiny", "backend": jax.default_backend(),
        # provenance: whose checkpoints these adapters would come from
        "method": tcfg.optimizer,
        "compute_dtype": store.layout.compute_dtype,
        "tenants": n_tenants, "batch": n_req,
        "prompt_len": prompt_len, "gen": gen,
        "page_size": ecfg.page_size, "num_pages": eng.num_pages,
        "decode_traces": eng.traces,
        "tokens_per_s": n_tok / best_s,
        "decode_step_ms": 1e3 * best_s / gen,
        "decode_step_guarded_ms": 1e3 * g_s / gen,
        # roofline-derived weight-stream bytes of ONE batched decode step:
        # lazy (W + V + per-row B) vs merged-per-tenant (T full W copies)
        "serve_bytes": sb,
    }
    print(f"serve ({n_tenants} tenants, batch {n_req}): "
          f"{out_rec['tokens_per_s']:.0f} tok/s, "
          f"lazy {sb['lazy_bytes'] / 2**20:.1f} MiB vs merged "
          f"{sb['merged_bytes'] / 2**20:.1f} MiB per step "
          f"({sb['reduction'] * 100:.0f}% reduction), "
          f"guarded {out_rec['decode_step_guarded_ms']:.3f} vs "
          f"{out_rec['decode_step_ms']:.3f} ms/step, "
          f"traces={out_rec['decode_traces']}")
    return out_rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_kernels.json"))
    args = p.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # grouped-state comparison first: it is the most noise-sensitive and
    # deserves the freshest process state (interpret-mode Pallas runs in
    # bench_ops leave the allocator in a different regime)
    from repro import methods
    from repro.models.common import resolve_compute_dtype
    grouped_state = bench_grouped_state()
    rec = {"backend": jax.default_backend(), "fast": FAST,
           # the compute dtype this host resolves to (REPRO_COMPUTE_DTYPE /
           # auto); per-section tags record what each section actually ran
           "compute_dtype": np.dtype(resolve_compute_dtype()).name,
           # the registry snapshot the per-section "method" tags must
           # resolve against (asserted by check_regression.py in CI)
           "methods_available": list(methods.available()),
           "ops": bench_ops(), "train_step": bench_train_step(),
           "grouped_state": grouped_state, "serve": bench_serve()}
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"train step: {rec['train_step']}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    main()
