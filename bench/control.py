"""Readings that set a cell's limits; not part of a benchmark run.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 [--program 12]

For each seed, in one process: the reference's readings, then

* ``program``: the program's checked steps (the timed path's Trainer, as a
  run builds it) against the reference; for the first ``--program`` seeds;
* ``control``: the reference with every matmul operand rounded to float8
  e4m3, in the program's place (the configuration states bf16 compute);
* ``half_batch``: the reference with half of each batch left out and the
  mean taken over the rest, in the program's place.

A state left unchanged reads 1 on ``change_gap`` by construction and needs
no run.  Prints one JSON line per seed and kind.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    import argparse
    import gc
    import json

    from harness import cell as C
    from harness import compare, program, spec, weights

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, default=0,
                    help="how many of the seeds also run the program")
    ap.add_argument("--kinds", default="control,half_batch")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    C.device_info(cell.chips)
    C._enable_compile_cache()
    cfg = spec.model_config(cell)
    kinds = [k for k in args.kinds.split(",") if k]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        tcfg = spec.train_config(cell, seed & 0x7FFFFFFF)
        lowrank = weights.lowrank_leaves(cfg, tcfg.rank,
                                         tcfg.min_dim_for_lowrank)
        got = {}
        if i < args.program:
            run = program.Run(cell, cfg, tcfg, seed, lowrank)
            got["program"] = run.checked_steps()
            run.close()
            del run
            gc.collect()
        t0 = time.perf_counter()
        ref = C.reference_readings(cell, cfg, tcfg, seed, lowrank)
        ref_s = time.perf_counter() - t0
        for kind in kinds:
            how = {"control": {"quant": True},
                   "half_batch": {"half_batch": True}}[kind]
            got[kind] = C.reference_readings(cell, cfg, tcfg, seed, lowrank,
                                             **how)
        for kind, readings in got.items():
            read = compare.readings(readings, ref)
            print(json.dumps({"seed": seed, "kind": kind, "ref_s": ref_s,
                              "skipped": readings.get("skipped", 0),
                              "losses": readings["losses"],
                              "ref_losses": ref["losses"],
                              **{n: read[n][0] for n in compare.NAMES},
                              "worst": {n: read[n][1]
                                        for n in compare.NAMES}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
