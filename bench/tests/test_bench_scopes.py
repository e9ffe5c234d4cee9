"""The reader of the program's names (``harness/scopes.py``) on a recorded
trace written as the chip writes it: device op events named by their HLO
text with the op-name path in the event metadata's ``tf_op`` stat, Pallas
calls named after their kernel, and the Trainer's host spans on the
harness's thread."""
import os
import types

import pytest

from bench_tiny_cell import BENCH  # noqa: F401  (puts the harness on the path)

from harness import flops, scopes, trace


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, lines: dict) -> bytes:
    """One XPlane: {line: [(event name, start ns, duration ns, op path)]};
    the op path goes into the event metadata's ``tf_op`` stat."""
    body = _field(2, name) + _field(5, _field(1, 7) + _field(
        2, _field(1, 7) + _field(2, scopes.TF_OP)))
    ids = {}
    for li, (line, events) in enumerate(lines.items()):
        evs = b""
        for ev, start, dur, path in events:
            if (ev, path) not in ids:
                i = ids[ev, path] = len(ids) + 1
                meta = _field(1, i) + _field(2, ev)
                if path:
                    meta += _field(5, _field(1, 7) + _field(5, path))
                body += _field(4, _field(1, i) + _field(2, meta))
            evs += _field(4, _field(1, ids[ev, path])
                          + _field(2, start * 1000) + _field(3, dur * 1000))
        body += _field(3, _field(1, li) + _field(2, line) + evs)
    return _field(1, body)


FWD = "jit(guarded)/jvp()/while/body/closed_call/repro.lowrank_forward.pallas"
REMAT = ("jit(guarded)/transpose(jvp())/while/body/closed_call/checkpoint/"
         "rematted_computation/repro.lowrank_forward.pallas")
ATTN_FWD = "jit(guarded)/jvp()/while/body/closed_call/repro.attention/dot"
ATTN_BWD = ("jit(guarded)/transpose(jvp())/while/body/closed_call/"
            "checkpoint/repro.attention/while/body/dot_general")
BWD_XLA = ("jit(guarded)/transpose(jvp())/while/body/closed_call/"
           "checkpoint/repro.lowrank_backward.xla/dot_general")
LOSS = "jit(guarded)/transpose(jvp(repro.loss))/while/body/exp"
UPDATE = "jit(guarded)/repro.update/repro.subspace_adam.xla/add"
GUARD = "jit(guarded)/repro.guard/select_n"


def _kernel(name: str, n: int) -> str:
    return (f'%{name}.{n} = bf16[256,384]{{1,0}} custom-call(bf16[256,128]'
            f'{{1,0}} %a, bf16[128,384]{{1,0}} %b, bf16[128,8]{{1,0}} %c, '
            f'f32[384,8]{{1,0}} %d), custom_call_target="tpu_custom_call"')


def _ops(kernel: str) -> list:
    """Two steps of one TPU: 1220 ns of ops, a while container over the
    first step's, idle 0-100, 920-1100 and 1500-2000 ns."""
    return [
        (_kernel(kernel, 7), 100, 300, FWD),
        ("%fusion.1 = f32[8]{0} fusion()", 400, 50, ATTN_FWD),
        (_kernel(kernel, 8), 450, 250, REMAT),
        ("%fusion.2 = f32[8]{0} fusion()", 700, 60, ATTN_BWD),
        ("%fusion.3 = f32[8]{0} fusion()", 760, 100, BWD_XLA),
        ("%fusion.4 = f32[8]{0} fusion()", 860, 40, LOSS),
        ("%fusion.5 = f32[8]{0} fusion()", 900, 10, UPDATE),
        ("%fusion.6 = f32[8]{0} fusion()", 910, 5, GUARD),
        ("%copy.1 = f32[8]{0} copy()", 915, 5, None),
        ("%while.3 = (s32[]) while((s32[]) %t)", 100, 815, "jit(guarded)"),
        (_kernel(kernel, 7), 1100, 400, FWD),
    ]


def _host(program: bool) -> list:
    evs = [("bench.trainer_run", 0, 1060, None),
           ("bench.trainer_run", 1060, 940, None),
           ("bench.loader", 0, 100, None)]
    if program:
        evs += [("repro.train.step", 0, 1050, None),
                ("repro.train.batch", 0, 100, None),
                ("repro.train.dispatch", 100, 100, None),
                ("repro.train.sync", 200, 850, None),
                ("repro.train.step", 1060, 640, None),
                ("repro.train.batch", 1060, 20, None),
                ("repro.train.dispatch", 1080, 20, None),
                ("repro.train.sync", 1100, 600, None)]
    return evs


def _write(tmp_path, kernel="lowrank_forward", program=True,
           extra=()) -> str:
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        _plane("/host:CPU", {"python": _host(program)})
        + _plane("/device:TPU:0", {"XLA Ops": _ops(kernel) + list(extra)}))
    return str(tmp_path)


TOTAL = 1220e-9


@pytest.fixture
def recorded(tmp_path):
    return scopes.reduce_dir(_write(tmp_path))


@pytest.mark.parametrize("path,scope,inside", [
    (FWD, "repro.lowrank_forward.pallas", True),
    (REMAT, scopes.REMAT, True),
    (REMAT, "repro.lowrank_forward.pallas", True),
    (LOSS, "repro.loss", True),
    ("jit(guarded)/jvp(repro.loss)/while/body/add", "repro.loss", True),
    (ATTN_BWD, "repro.attention", True),
    (UPDATE, "repro.update", True),
    (UPDATE, "repro.subspace_adam.xla", True),
    (BWD_XLA, "repro.lowrank_backward.pallas", False),
    ("jit(guarded)/repro.attention_mask/add", "repro.attention", False),
    ("jit(guarded)/myrepro.loss/add", "repro.loss", False),
])
def test_scope_is_a_path_component(path, scope, inside):
    assert scopes.in_scope(path, (scope,)) is inside


def test_op_names_read_from_the_event_metadata(tmp_path):
    xplane = trace.find_xplane(_write(tmp_path))
    names = scopes.op_names(xplane)
    assert names[_kernel("lowrank_forward", 8)] == [REMAT]
    assert names["%fusion.3 = f32[8]{0} fusion()"] == [BWD_XLA]
    assert "%copy.1 = f32[8]{0} copy()" not in names
    # ProfileData shows the events but not their metadata's stats
    pd = trace.load(xplane)
    dev, = trace.device_ops(pd).values()
    assert len(dev) == 11


def test_a_text_under_two_paths_is_counted(tmp_path):
    """Two programs can hold an op of the same HLO text under different
    paths: its time goes under the first path the file has, and all of it
    into ``ambiguous_s``."""
    other = "jit(lm_batch)/add"
    sc = scopes.reduce_dir(_write(tmp_path, extra=[
        ("%fusion.1 = f32[8]{0} fusion()", 1600, 30, other)]))
    names = scopes.op_names(trace.find_xplane(str(tmp_path)))
    assert names["%fusion.1 = f32[8]{0} fusion()"] == [ATTN_FWD, other]
    assert sc["ambiguous_s"] == pytest.approx(80e-9)
    assert sc["by_path"][ATTN_FWD] == pytest.approx(80e-9)
    assert other not in sc["by_path"]
    assert sc["op_s"] == pytest.approx(TOTAL + 30e-9)


def test_reduction_by_scope_kernel_and_span(recorded):
    assert recorded["op_s"] == pytest.approx(TOTAL)
    assert recorded["by_path"][FWD] == pytest.approx(700e-9)
    assert recorded["by_path"][""] == pytest.approx(5e-9)
    assert recorded["ambiguous_s"] == 0
    assert recorded["kernels"] == {"lowrank_forward": pytest.approx(950e-9)}
    assert recorded["steps"] == 2
    # idle 0-100 under the first batch, 920-1100 (midpoint 1010) under
    # the first sync, 1500-2000 (midpoint 1750) past the last span
    assert recorded["host_gaps"] == {
        "repro.train.batch": pytest.approx(100e-9),
        "repro.train.sync": pytest.approx(180e-9),
        "none": pytest.approx(500e-9)}


def _ctx(sc, **kw):
    return {"trace": {"scopes": sc}, **kw}


def test_shares_read_by_hand(recorded):
    ctx = _ctx(recorded)
    assert scopes.remat_share(ctx) == pytest.approx(100 * 250 / 1220)
    assert scopes.attention_share(ctx) == pytest.approx(100 * 110 / 1220)
    assert scopes.loss_share(ctx) == pytest.approx(100 * 40 / 1220)
    assert scopes.update_share(ctx) == pytest.approx(100 * 15 / 1220)
    disjoint = (scopes.attention_share(ctx) + scopes.loss_share(ctx)
                + scopes.update_share(ctx))
    assert disjoint < 100


def test_host_wait_by_hand(recorded):
    # 100 ns under a batch span and 180 ns under a sync span, two steps
    assert scopes.host_wait_ms(_ctx(recorded)) == pytest.approx(
        1e3 * 280e-9 / 2)


def _xla_ctx(sc, routes):
    cfg = types.SimpleNamespace(num_layers=2, vocab_size=500)
    lowrank = {"['layers']['mlp']['w_up']": (64, 128, 4),
               "['unembed']": (64, 512, 4)}
    return _ctx(sc, cfg=cfg, lowrank=lowrank, routes=routes, tokens=1000,
                peaks={"bf16_flops": 197e12})


def test_xla_backward_share_of_peak_by_hand(recorded):
    routes = {("lowrank_backward", (256, 64, 128, 4)): "xla",
              ("lowrank_backward", (256, 64, 512, 4)): "pallas",
              ("lowrank_forward", (256, 64, 128, 4)): "xla"}
    work = 2 * (2 * 64 * 128 + 2 * 64 * 4 + 4 * 128 * 4) * 1000
    assert flops.lowrank_matmuls(_xla_ctx(recorded, routes)["cfg"], {
        "['layers']['mlp']['w_up']": (64, 128, 4)}) == [(64, 128, 128, 4, 2)]
    assert scopes.lowrank_bwd_xla_mxu(_xla_ctx(recorded, routes)) == \
        pytest.approx(100 * work / (100e-9 * 197e12))
    # nothing on the XLA route: nothing to read
    routes = {k: "pallas" for k in routes}
    assert scopes.lowrank_bwd_xla_mxu(_xla_ctx(recorded, routes)) is None


def test_nothing_to_read(tmp_path):
    for ctx in ({"trace": None}, {"trace": {"window_s": 1.0}}, {}):
        for read in scopes.METRICS.values():
            assert read(ctx) is None
    # the parent program: no scopes, no program spans, unnamed kernels
    sc = scopes.reduce_dir(_write(tmp_path, "_lambda_", program=False))
    bare = {k: (v if k != "by_path" else {"": sum(v.values())})
            for k, v in sc.items()}
    ctx = _xla_ctx(bare, {("lowrank_backward", (256, 64, 128, 4)): "xla"})
    for name, read in scopes.METRICS.items():
        assert read(ctx) is None, name


def test_program_names_leave_the_accepted_reduction_as_it_was(tmp_path):
    """Named kernels and the Trainer's spans move none of the numbers the
    accepted metrics read from ``trace.reduce``; the program's spans only
    name the idle gaps of the breakdown."""
    old = trace.reduce_dir(_write(tmp_path / "old", "_lambda_", False))
    new = trace.reduce_dir(_write(tmp_path / "new"))
    assert sorted(new) == sorted(old) == [
        "busy_s", "device_ops", "idle_gaps", "kernels", "window_s"]
    for key in ("busy_s", "device_ops", "kernels", "window_s"):
        assert new[key] == old[key], key
    assert [g[1] for g in new["idle_gaps"]] == [g[1] for g in old["idle_gaps"]]
    assert os.path.basename(trace.find_xplane(str(tmp_path / "new"))) \
        == "host.xplane.pb"
