"""Benchmark driver: one module per paper table/figure.

  toy_mse          -> Figures 2-5 (estimator MSE vs samplers/c/samples)
  memory_table     -> Table 2 (peak training memory, every registered
                      method + the vanilla_lr ablation)
  finetune_table   -> Table 1 (LR fine-tuning accuracy across samplers)
  pretrain_curves  -> Figures 7-9 (Stiefel vs Gaussian LowRank-IPA)
  roofline_table   -> EXPERIMENTS.md §Roofline (from dry-run records)

REPRO_BENCH_FAST=0 for full-size runs; default is CPU-budget sizes.
"""
from __future__ import annotations

import sys
import time
import traceback

from . import (finetune_table, memory_table, pretrain_curves, roofline_table,
               toy_mse)

ALL = {
    "toy_mse": toy_mse.main,
    "memory_table": memory_table.main,
    "finetune_table": finetune_table.main,
    "pretrain_curves": pretrain_curves.main,
    "roofline_table": roofline_table.main,
}


def main() -> int:
    names = sys.argv[1:] or list(ALL)
    failed = []
    for name in names:
        print(f"\n===== bench: {name} =====")
        t0 = time.time()
        try:
            ALL[name]()
            print(f"===== {name} done in {time.time()-t0:.0f}s =====")
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"\nFAILED: {failed}")
        return 1
    print("\nall benchmarks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
