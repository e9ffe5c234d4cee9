"""Find a cell's files by name and turn them into the program's configs.

A cell ``<config>.<traffic>`` is ``workloads/<cell>.json``; it names a
configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<traffic>.json``).  Nothing here knows any cell by name.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load(kind: str, name: str, root: Path = BENCH) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        known = sorted(p.stem for p in (root / kind).glob("*.json"))
        raise SystemExit(f"no {kind[:-1]} named {name!r} "
                         f"(known: {', '.join(known)})")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    @property
    def seq(self) -> int:
        return int(self.traffic["seq"])

    @property
    def batch(self) -> int:
        return int(self.traffic["batch"])

    @property
    def tokens_per_step(self) -> int:
        return self.seq * self.batch

    @property
    def limits(self) -> dict:
        return self.workload["limits"]


def load_cell(name: str, root: Path = BENCH) -> Cell:
    wl = _load("workloads", name, root)
    return Cell(name=name, workload=wl,
                config=_load("configs", wl["config"], root),
                traffic=_load("traffic", wl["traffic"], root))


def benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SystemExit(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def model_config(cell: Cell):
    """The program's ModelConfig for the cell: the named base config with
    the configuration file's overrides."""
    from repro.configs import get_config

    prog = cell.config["program"]
    return get_config(prog["base"]).replace(**prog.get("overrides", {}))


def train_config(cell: Cell, seed: int):
    from repro.configs.base import TrainConfig

    return TrainConfig(optimizer=cell.traffic["method"], seed=seed,
                       **cell.traffic["train"])
