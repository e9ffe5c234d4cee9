"""The comparison fails a broken timed path, and its control.

Each fault is planted underneath a whole run (the harness's look for a
chip skipped) at a CPU-sized copy of each cell, with the cell's own
limits: a step that returns its state unchanged, and half of the batch
left out with the mean taken over the rest.  The control is the
reference with float8 matmuls in the program's place."""
import pytest

from bench_tiny_cell import CELLS, run, tiny

from harness import cell as C
from harness import compare, spec, weights


def _unchanged(monkeypatch):
    from repro.optim import subspace

    def inner_update(grads, trainable, params, state, *, lr, tcfg):
        return params, trainable, state, subspace.clip_by_global_norm(
            grads, tcfg.grad_clip)[1]

    monkeypatch.setattr(subspace, "inner_update", inner_update)


def _half_batch(monkeypatch):
    from repro.train import steps

    ce = steps.chunked_ce

    def half(hidden, unembed, labels, **kw):
        n = hidden.shape[0] // 2
        return ce(hidden[:n], unembed, labels[:n], **kw)

    monkeypatch.setattr(steps, "chunked_ce", half)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run(tiny(name))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny(name)
    cfg = spec.model_config(cell)
    tcfg = spec.train_config(cell, 11)
    lowrank = weights.lowrank_leaves(cfg, tcfg.rank, tcfg.min_dim_for_lowrank)
    ref = C.reference_readings(cell, cfg, tcfg, 11, lowrank)
    ctl = C.reference_readings(cell, cfg, tcfg, 11, lowrank, quant=True)
    ok, checks = compare.verdict(compare.readings(ctl, ref), cell.limits)
    assert not ok, checks
