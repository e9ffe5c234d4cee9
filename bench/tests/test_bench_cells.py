"""A benchmark run end to end at a CPU-sized copy of each cell: the
program's Trainer through set-up, window and comparison, and a new cell
found by its file alone."""
import json
import shutil

import pytest

from bench_tiny_cell import BENCH, CELLS, run, shrink, tiny

from harness import spec


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run(tiny(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) >= {"train_tok_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_a_new_workload_file_is_found_and_run(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    wl = json.loads((root / "workloads" / f"{CELLS[0]}.json").read_text())
    wl["why"] = "a cell added by a data file alone"
    (root / "workloads" / "nemo12b-stage8.added.json").write_text(
        json.dumps(wl))
    found = spec.load_cell("nemo12b-stage8.added", root)
    assert found.name == "nemo12b-stage8.added"
    res = run(shrink(found))
    assert res["correct"], res["checks"]
