"""The benchmark's yardstick: counts, the trace reduction, the refusals
and the consistency of its data files."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench_tiny_cell import BENCH, CELLS, ROOT, tiny

from harness import cell as C
from harness import compare, flops, peaks, spec, trace, weights


def _lowrank(cell):
    cfg = spec.model_config(cell)
    tcfg = spec.train_config(cell, 0)
    return cfg, weights.lowrank_leaves(cfg, tcfg.rank,
                                       tcfg.min_dim_for_lowrank)


def test_flops_hand_sums_nemo_tiny():
    cfg, lr = _lowrank(tiny(CELLS[0]))
    # d 64, 4 heads / 2 kv heads of 16, ff 128, vocab 500 (512 stored),
    # r 4, 2 layers, seq 64
    per = lambda k, n, r: 4 * k * n + 4 * k * r + 6 * n * r  # noqa: E731
    layer = (per(64, 64, 4) + 2 * per(64, 32, 4) + per(64, 64, 4)
             + 2 * per(64, 128, 4) + per(128, 64, 4))
    attn = 3 * 4 * 16 * 4 * 65 / 2
    want = 2 * (layer + attn) + per(64, 500, 4)
    assert flops.model_flops_per_token(cfg, lr, 64, "lowrank_adam") == want
    fwd = lambda k, n, r: 2 * k * n + 2 * k * r + 2 * n * r  # noqa: E731
    zo_layer = 2 * (fwd(64, 64, 4) * 2 + 2 * fwd(64, 32, 4)
                    + 2 * fwd(64, 128, 4) + fwd(128, 64, 4))
    zo = 2 * (zo_layer + 2 * 4 * 16 * 4 * 65 / 2) + 2 * fwd(64, 500, 4)
    assert flops.model_flops_per_token(cfg, lr, 64, "lowrank_lr") == zo


def test_flops_hand_sums_mamba_tiny():
    cfg, lr = _lowrank(tiny(CELLS[1]))
    # d 64, d_inner 128, 8 heads of 16, state 16, 1 group, chunk 16;
    # in_proj 64 -> 2*128 + 2*16 + 8 = 296, out_proj 128 -> 64
    per = lambda k, n, r: 4 * k * n + 4 * k * r + 6 * n * r  # noqa: E731
    ssd = 2 * 16 * 16 * 1 + 2 * 16 * 8 * 16 + 4 * 16 * 8 * 16
    want = 2 * (per(64, 296, 4) + per(128, 64, 4) + 3 * ssd) \
        + per(64, 500, 4)
    assert flops.model_flops_per_token(cfg, lr, 64, "lowrank_adam") == want


def test_kernel_cost_hand_sums():
    bf, f32 = "bf16", "f32"
    m, k, n, r = 256, 128, 384, 8
    f, b = flops.kernel_cost(
        [(bf, (m, k)), (bf, (k, n)), (bf, (k, r)), (f32, (n, r))],
        [(bf, (m, n)), (bf, (m, r))])
    assert f == 2 * m * k * n + 2 * m * k * r + 2 * m * r * n
    assert b == (m * k + k * n + k * r) * 2 + n * r * 4 + (m * n + m * r) * 2
    f, b = flops.kernel_cost(
        [(bf, (m, n)), (bf, (k, n)), (bf, (k, r)), (f32, (n, r)),
         (bf, (m, r))],
        [(bf, (1, m, k)), (f32, (n, r))])
    assert f == 2 * m * n * k + 4 * m * n * r + 2 * m * r * k
    assert b == (m * n + k * n + k * r + m * r + m * k) * 2 + 2 * n * r * 4
    f, b = flops.kernel_cost([(f32, (3,))] + [(f32, (100, 128))] * 4,
                             [(f32, (100, 128))] * 3)
    assert (f, b) == (0.0, 3 * 4 + 7 * 100 * 128 * 4)


def test_kernel_calls_read_from_hlo_text():
    name = ('%_lambda_.188 = (bf16[8192,4096]{1,0:T(8,128)(2,1)}, '
            'bf16[8192,128]{1,0:T(8,128)(2,1)}) custom-call(bf16[8192,5120]'
            '{1,0:T(8,128)(2,1)S(1)} %bitcast.1105, bf16[5120,4096]{1,0} '
            '%f.117, bf16[5120,128]{1,0} %f.118, bf16[4096,128]{1,0} %f.119),'
            ' custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={bf16[8192,5120]{1,0}}')
    p = trace.parse_op(name)
    assert p["pallas"] and p["kind"] == "_lambda_"
    assert p["operands"] == [("bf16", (8192, 5120)), ("bf16", (5120, 4096)),
                             ("bf16", (5120, 128)), ("bf16", (4096, 128))]
    assert p["results"] == [("bf16", (8192, 4096)), ("bf16", (8192, 128))]
    assert trace._label(p) == "pallas forward M8192 K5120 N4096 r128"
    w = trace.parse_op("%while.282 = (s32[], bf16[2,4096,5120]{2,1,0}) "
                       "while((s32[], bf16[2,4096,5120]) %tuple.4)")
    assert w["kind"] == "while" and not w["pallas"]


def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _plane(name, **lines):
    return types.SimpleNamespace(
        name=name, lines=[types.SimpleNamespace(name=k, events=v)
                          for k, v in lines.items()])


KERNEL = ('%_lambda_.7 = bf16[256,384]{1,0} custom-call(bf16[256,128]{1,0} '
          '%a, bf16[128,384]{1,0} %b, bf16[128,8]{1,0} %c, f32[384,8]{1,0} '
          '%d), custom_call_target="tpu_custom_call"')


def recorded():
    """A two-step window of one TPU: busy 100-500 and 1200-1800 ns."""
    host = _plane("/host:CPU", python=[
        _ev("bench.trainer_run", 0, 1000),
        _ev("bench.loader", 1000, 250),
        _ev("bench.trainer_run", 1000, 1000)])
    dev = _plane("/device:TPU:0", **{"XLA Ops": [
        _ev("fusion.1", 100, 300),
        _ev("fusion.2", 300, 200),
        _ev("%while.3 = (s32[]) while((s32[]) %t)", 100, 400),
        _ev(KERNEL, 1200, 600)]})
    return types.SimpleNamespace(planes=[host, dev])


def test_trace_reduction_on_recorded_trace():
    red = trace.reduce(recorded())
    assert red["window_s"] == pytest.approx(2000e-9)
    assert red["busy_s"] == pytest.approx(1000e-9)
    assert red["idle_gaps"][0][0] == "bench.trainer_run"
    assert red["idle_gaps"][0][1] == pytest.approx(700e-9)
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [700e-9, 200e-9, 100e-9])
    assert red["device_ops"][0] == ["pallas forward M256 K128 N384 r8",
                                    pytest.approx(600e-9)]
    assert not any(k.startswith("while") for k, _ in red["device_ops"])
    k = trace.parse_op(KERNEL)
    f, b = flops.kernel_cost(k["operands"], k["results"])
    assert f == 2 * 256 * 128 * 384 + 2 * 256 * 128 * 8 + 2 * 256 * 8 * 384
    pk = peaks.PEAKS["TPU v5 lite"]
    share = 100 * max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]) / 600e-9
    assert C.read_metric("lowrank_roofline.train", {
        "trace": red, "peaks": pk}) == pytest.approx(share)
    assert C.read_metric("device_idle_share.train",
                         {"trace": red}) == pytest.approx(50.0)
    assert C.read_metric("device_idle_share.train", {"trace": None}) is None


def test_refuses_a_device_kind_missing_from_the_peaks_table(monkeypatch):
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v99")
    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(SystemExit, match="peaks table"):
        C.device_info(1)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")])
    with pytest.raises(C.NoChip):
        C.device_info(4)


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_run_refuses_a_cpu():
    out = _run(ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_run_needs_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, env={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "No module named 'repro'" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_agree():
    bench = spec.benchmark()
    for c in bench["configs"]:
        data = json.load(open(os.path.join(ROOT, c["file"])))
        assert data["name"] == c["name"]
        assert sorted(data["reduced"]) == sorted(c["reduced"])
        assert set(data["reduced"]) <= set(data["published"])
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.workload["config"] == w["config"]
        assert cell.workload["traffic"] == w["traffic"]
        assert cell.chips == w["chips"]
        assert cell.limits and set(cell.limits) <= set(compare.NAMES)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
