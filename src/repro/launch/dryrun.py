import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production mesh and record memory/cost/collective analysis.

MUST be executed as a module (``python -m repro.launch.dryrun``) in a fresh
process — the lines above run before any jax import so the 512
placeholder host devices exist before jax locks the device count.  It is a
CPU-only analysis tool: the platform is pinned to the CPU so that on a
machine with a chip the production mesh is still built from placeholder
host devices.

Usage:
  python -m repro.launch.dryrun                      # all cells, (16,16)
  python -m repro.launch.dryrun --multi-pod          # all cells, (2,16,16)
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --optimizer adamw    # Vanilla-IPA baseline
  python -m repro.launch.dryrun --out results.json

Per cell it prints/records:
  * compiled.memory_analysis()  (bytes/device: args, outputs, temps, peak)
  * compiled.cost_analysis()    (HLO flops / bytes accessed)
  * collective bytes parsed from the optimized HLO (for §Roofline)
"""
import argparse
import json
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool, optimizer: str,
             save_hlo: str = "", fuse_outer: bool = False):
    import jax
    from repro.analysis import hlo_cost
    from repro.configs import (SHAPE_BY_NAME, TrainConfig, get_config,
                               cell_supported)
    from repro.launch import cells
    from repro.launch.mesh import make_production_mesh
    from repro.sharding import rules

    cfg = get_config(arch)
    shape = SHAPE_BY_NAME[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    tcfg = TrainConfig(fuse_outer=True) if fuse_outer else None
    t0 = time.time()
    step, args, shardings, meta = cells.build_cell(
        cfg, shape, mesh, tcfg=tcfg, optimizer=optimizer or None)
    jitted = jax.jit(step, in_shardings=shardings, donate_argnums=(0, 1))
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # loop-aware per-device cost (XLA's cost_analysis counts while bodies
    # once; ours multiplies by known_trip_count — see analysis/hlo_cost.py)
    lac = hlo_cost.analyze(hlo)
    if save_hlo:
        os.makedirs(save_hlo, exist_ok=True)
        mesh_tag = "2x16x16" if multi_pod else "16x16"
        with open(os.path.join(
                save_hlo, f"{arch}_{shape_name}_{mesh_tag}.hlo"), "w") as f:
            f.write(hlo)
    rec = {
        "arch": arch, "shape": shape_name, "kind": meta["kind"],
        "optimizer": meta["optimizer"], "status": "ok",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": 512 if multi_pod else 256,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "device_total_bytes":
                getattr(mem, "argument_size_in_bytes", 0) +
                getattr(mem, "temp_size_in_bytes", 0),
        },
        "cost": {  # loop-aware, per device
            "flops": lac["flops"],
            "bytes_accessed": lac["bytes_accessed"],
            "xla_flops_raw": cost.get("flops"),
        },
        "collectives": lac["collective_bytes"],
    }
    # Grouped-layout audit (train cells only): record the analytic
    # per-device bytes of the stacked low-rank buffers and FAIL the cell
    # if any of them stays fully replicated above the policy cap — the
    # checkable form of "no fully-replicated low-rank buffer".
    report = meta.get("shard_report") or []
    if report:
        rec["per_device_bytes"] = rules.assert_well_sharded(report)
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="")
    p.add_argument("--shape", default="")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--optimizer", default="",
                   help="'' -> lowrank_adam (paper); any registered "
                        "method name (adamw | lowrank_lr | galore | ...) "
                        "lowers its own train cell")
    p.add_argument("--fuse-outer", action="store_true",
                   help="lower the train cells with the outer "
                        "merge+resample folded into the inner step as a "
                        "traced cond (tcfg.fuse_outer)")
    p.add_argument("--out", default="")
    p.add_argument("--save-hlo", default="")
    p.add_argument("--continue-on-error", action="store_true")
    args = p.parse_args(argv)

    from repro.configs import ASSIGNED, SHAPES
    archs = [args.arch] if args.arch else sorted(ASSIGNED)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results = []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                tag = f"{arch} x {shape} [{'2x16x16' if mp else '16x16'}]"
                try:
                    rec = run_cell(arch, shape, mp, args.optimizer,
                                   save_hlo=args.save_hlo,
                                   fuse_outer=args.fuse_outer)
                except Exception as e:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                    if not args.continue_on_error:
                        print(f"[FAIL] {tag}\n{traceback.format_exc()}")
                        if args.out:
                            _dump(results + [rec], args.out)
                        sys.exit(1)
                results.append(rec)
                if rec["status"] == "ok":
                    m = rec["memory"]
                    print(f"[ok] {tag}: mem/device "
                          f"{(m['device_total_bytes'] or 0)/2**30:.2f} GiB, "
                          f"flops {rec['cost']['flops']:.3e}, "
                          f"coll {sum(rec['collectives'].values())/2**30:.2f} GiB "
                          f"(compile {rec['compile_s']}s)", flush=True)
                elif rec["status"] == "skipped":
                    print(f"[skip] {tag}: {rec['reason']}")
                else:
                    print(f"[ERR] {tag}: {rec['error']}")
                if args.out:
                    _dump(results, args.out)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (by assignment), "
          f"{n_err} errors")
    return 0 if n_err == 0 else 1


def _dump(results, path):
    with open(path, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
