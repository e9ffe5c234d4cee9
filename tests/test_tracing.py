"""The program names its work: scopes in the compiled training step's op
metadata, and the Trainer's host spans on the profiler's clock.

Run at the benchmark's NeMo cell cut to a CPU size
(``bench/tests/bench_tiny_cell.py``), on the XLA route.  A scope is a
component of an op-name path, bare or wrapped by a transform
(``jvp(repro.loss)``); JAX marks the remat recompute with a
``rematted_computation`` component.
"""
import glob
import os
import re
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench", "tests"))

from bench_tiny_cell import CELLS, tiny  # noqa: E402

from harness import spec  # noqa: E402

from repro.data.synthetic import lm_batch  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

REMAT = "rematted_computation"
STEP_SPAN = "repro.train.step"
SCOPES = ("repro.attention", "repro.loss", "repro.update", "repro.guard",
          "repro.lowrank_forward.xla", "repro.lowrank_backward.xla", REMAT)


def in_scope(path, name):
    """Whether ``name`` is a component of ``path``, bare or inside a
    transform's parentheses."""
    return re.search(r"(?:^|[/(])" + re.escape(name) + r"(?=[/)]|$)",
                     path) is not None


@pytest.fixture(scope="module")
def trainer():
    cell = tiny(CELLS[0])
    cfg = spec.model_config(cell)
    tcfg = spec.train_config(cell, 7)
    kw = dict(batch=cell.batch, seq_len=cell.seq, vocab=cfg.vocab_size)
    return Trainer(cfg, tcfg, lambda step: lm_batch(7, step, **kw))


@pytest.fixture(scope="module")
def op_paths(trainer):
    tr = trainer
    hlo = tr._inner.lower(tr.params, tr.opt_state, tr.health,
                          tr.loader(0)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


@pytest.mark.parametrize("scope", SCOPES)
def test_inner_step_names_its_work(scope, op_paths):
    assert any(in_scope(p, scope) for p in op_paths), scope


def test_recompute_and_backward_are_told_apart(op_paths):
    attn = [p for p in op_paths if in_scope(p, "repro.attention")]
    remat = [p for p in attn if in_scope(p, REMAT)]
    # the forward, the remat recompute of the forward, and the backward
    assert any(p.startswith("jit(guarded)/jvp(") for p in attn)
    assert remat
    assert any("transpose(" in p for p in set(attn) - set(remat))
    assert any(in_scope(p, "repro.update") and
               in_scope(p, "repro.subspace_adam.xla")
               for p in op_paths)


def _host_lines(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(path)
    return [[(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for ev in line.events]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines]


def test_trainer_spans_enclose_each_step(trainer, tmp_path):
    trainer.run(1)                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        trainer.run(2)
    lines = [evs for evs in _host_lines(str(tmp_path))
             if any(n == STEP_SPAN for _, _, n in evs)]
    assert len(lines) == 1
    evs, = lines
    steps = [(s, e) for s, e, n in evs if n == STEP_SPAN]
    assert len(steps) == 2
    for s0, e0 in steps:
        inside = {n for s, e, n in evs if s0 <= s and e <= e0}
        assert {"repro.train.batch", "repro.train.dispatch",
                "repro.train.sync"} <= inside, inside
        assert "repro.train.outer" not in inside     # lazy_k 200
