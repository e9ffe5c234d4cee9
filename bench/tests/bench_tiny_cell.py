"""A cell's files at a size a CPU test can hold: the same program path,
the same comparison and limits, tiny widths (tests only)."""
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import spec  # noqa: E402

CELLS = ("nemo12b-stage8.pretrain-s4k", "mamba2-780m.pretrain-s2k")


def shrink(cell):
    cfg = json.loads(json.dumps(cell.config))
    ov = cfg["program"]["overrides"]
    ov.update(num_layers=2, d_model=64, vocab_size=500, dtype="float32",
              param_dtype="float32", loss_chunk=32, attn_chunk=32)
    if cfg["program"]["base"] == "mamba2-780m":
        ov.update(ssm_state=16, ssm_head_dim=16, ssd_chunk=16)
    else:
        ov.update(num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128)
    traffic = json.loads(json.dumps(cell.traffic))
    traffic.update(seq=64, batch=4)
    traffic["train"].update(rank=4, min_dim_for_lowrank=16,
                            compute_dtype="float32")
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def tiny(name, root=None):
    return shrink(spec.load_cell(name, *(() if root is None else (root,))))


def run(cell, seed=3, seconds=0.5, trace=False):
    from harness import cell as C

    return C.run_cell(cell, seed, seconds, trace, spec.benchmark(),
                      time.perf_counter(), require_tpu=False)
