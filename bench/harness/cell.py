"""One run of one cell: set-up, the measured window, the comparison, and
the metrics the cell reports."""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import compare, spec
from .peaks import peaks_for


class NoChip(SystemExit):
    pass


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """The devices as JAX reports them; refuses a non-TPU platform, fewer
    chips than the cell asks for, and a kind missing from the peaks
    table."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {dev.platform!r}); "
                         f"the benchmark never runs elsewhere")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    peaks = peaks_for(dev.device_kind if require_tpu else "TPU v5 lite")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs), "peaks": peaks, "device": dev}


def _enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def metric_names(bench: dict, cell: str, trace: bool) -> list:
    """The metrics this cell reports in this kind of run, as
    BENCHMARK.json lists them."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return [m["name"] for m in e2e]
    moved = {m["name"] for m in e2e}
    return [m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in moved]


def read_metric(name: str, ctx: dict):
    """Run ``metrics/<name>.py``'s ``read(ctx)``; None where it finds
    nothing to read."""
    path = spec.BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


def reference_readings(cell, cfg, tcfg, seed: int, lowrank: dict,
                       **how) -> dict:
    """The reference's readings for the checked steps of this seed
    (``how``: ``quant`` for the control, ``half_batch`` for that fault)."""
    import jax.numpy as jnp

    from . import program, reference, weights
    from .data import Loader, lm_batch

    flat, vs = weights.make(cfg, seed, lowrank, jnp.dtype(tcfg.compute_dtype))
    loader = Loader(seed, cell.traffic, cfg.vocab_size)
    batches = [lm_batch(loader.key, s, **loader.kw)
               for s in range(program.CHECKED_STEPS)]
    return reference.run(flat, vs, lowrank, batches,
                         reference.model_dims(cfg), reference.optimizer(tcfg),
                         **how)


def run_cell(cell, seed: int, seconds: float, trace: bool, bench: dict,
             start: float, *, require_tpu: bool = True) -> dict:
    """Everything one run does; returns the result line's object."""
    import jax

    from . import flops, program, weights

    clock = time.perf_counter
    dev = device_info(cell.chips, require_tpu)
    cfg = spec.model_config(cell)
    tcfg = spec.train_config(cell, seed & 0x7FFFFFFF)
    lowrank = weights.lowrank_leaves(cfg, tcfg.rank,
                                     tcfg.min_dim_for_lowrank)

    # -- set-up: the one Trainer, its checked first steps, warm programs --
    run = program.Run(cell, cfg, tcfg, seed, lowrank)
    checked = run.checked_steps()
    run.warm_outer()
    compiled_before = run.compiled_programs()

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host spans, not every Python call
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = run.window(seconds, clock)
    if trace:
        jax.profiler.stop_trace()
    setup_s = win["t0"] - start
    window_s = win["t1"] - win["t0"]
    compiled_after = run.compiled_programs()
    stats = dev["device"].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    routes = program.routes()
    run.close()
    del run
    gc.collect()

    # -- the reference, after the window, on the same weights and batches --
    t_ref = clock()
    ref = reference_readings(cell, cfg, tcfg, seed, lowrank)
    ref_s = clock() - t_ref
    read = compare.readings(checked, ref)
    ok, checks = compare.verdict(read, cell.limits)
    failed = win["failed"] + checked["skipped"]
    correct = ok and failed == 0

    tokens = win["steps"] * cell.tokens_per_step
    ctx = {
        "cell": cell, "cfg": cfg, "tcfg": tcfg, "lowrank": lowrank,
        "peaks": dev["peaks"], "chips": cell.chips,
        "setup_s": setup_s, "window_s": window_s, "steps": win["steps"],
        "tokens": tokens, "tokens_per_s": tokens / window_s,
        "peak_bytes": peak, "routes": routes,
        "window_compiles": compiled_after - compiled_before,
        "flops_per_token": flops.model_flops_per_token(
            cfg, lowrank, cell.seq, cell.traffic["method"]),
        "trace": None,
    }
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from . import trace as tmod
        red = tmod.reduce_dir(trace_dir)
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    units = {x["name"]: x["unit"]
             for x in bench["end_to_end"] + bench["per_layer"]}
    for name in metric_names(bench, cell.name, trace):
        value = read_metric(name, ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    print(f"bench: {cell.name} seed {seed}: {win['steps']} steps in "
          f"{window_s:.3f} s, set-up {setup_s:.3f} s, reference "
          f"{ref_s:.3f} s, losses {checked['losses']} vs "
          f"{ref['losses']}", file=sys.stderr)
    for name in compare.NAMES:
        limit = (f"limit {checks[name]['limit']:g}" if name in checks
                 else "not compared")
        print(f"bench: {name} = {read[name][0]:.6g} ({limit}; worst at "
              f"{read[name][1]})", file=sys.stderr)
    result = {"correct": bool(correct), "attempted": win["steps"],
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None, start: float | None = None) -> int:
    import argparse

    start = time.perf_counter() if start is None else start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import repro.configs  # noqa: F401  (the program under test)
    bench = spec.benchmark()
    if args.workload not in {w["name"] for w in bench["workloads"]}:
        raise SystemExit(f"BENCHMARK.json has no cell {args.workload!r}")
    cell = spec.load_cell(args.workload)
    _enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), bench,
                      start)
    sys.stderr.flush()
    print(json.dumps(_finite(result), allow_nan=False))
    return 0


def _finite(x):
    """JSON has no inf or NaN: a reading that is not finite prints null
    (and has already made the run incorrect)."""
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if hasattr(x, "item"):
        return _finite(x.item())
    return x
