"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read and to the ``breakdown`` of the result line.

* Device busy time: the union of the intervals of the ops on each TPU
  device's "XLA Ops" line, averaged over the devices.
* Window: from the start of the first to the end of the last host span
  named ``bench.trainer_run`` (the harness's span around each
  ``Trainer.run`` call).
* Idle gaps: the holes in the union inside the window, each named by the
  innermost harness host span that covers its middle.
* Top device ops: time per op kind and result shape, loop and call
  containers left out (their bodies are counted).
* Kernel calls: every ``tpu_custom_call`` (a Pallas kernel) in the window
  with its operand and result shapes, for the roofline metric.

Op events are named by their HLO text, ``%name = <result> op(<operands>),
...``; shapes are read from it.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.trainer_run"
TOP = 10
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def shapes(text: str) -> list:
    """[(dtype, shape)] of every array type in an HLO text fragment."""
    return [(d, tuple(int(x) for x in dims.split(",") if x))
            for d, dims in _SHAPE.findall(text)]


def parse_op(name: str) -> dict:
    """Kind, result types and (custom calls) operand types of an op event
    named by its HLO text."""
    head, _, rest = name.partition(" = ")
    kind = re.sub(r"\.\d+$", "", head.lstrip("%"))
    m = re.match(r"(\(.*?\)|\S+) [\w\-]+\(", rest)
    out = {"kind": kind, "results": shapes(m.group(1) if m else ""),
           "pallas": 'custom_call_target="tpu_custom_call"' in rest}
    if out["pallas"]:
        args = rest[m.end():rest.find("), custom_call_target")]
        out["operands"] = shapes(args)
    return out


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def host_spans(pd) -> list:
    """[(start_ns, end_ns, name)] of the host lines that carry the
    harness's spans."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events if ev.duration_ns > 0]
            if any(n.startswith("bench.") for _, _, n in evs):
                out += evs
    return out


def device_ops(pd) -> dict:
    """{device plane name: [(start_ns, end_ns, name)]} from each TPU
    device's "XLA Ops" line."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                out[plane.name] = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name) for ev in line.events]
    return out


def _label(p: dict) -> str:
    """Op kind and result shapes; a Pallas low-rank kernel by its role and
    (M, K, N, r)."""
    ops = [s for _, s in p.get("operands", [])]
    if p["pallas"] and len(ops) == 4 and all(len(s) == 2 for s in ops):
        (m, k), (_, n), (_, r) = ops[0], ops[1], ops[2]
        return f"pallas forward M{m} K{k} N{n} r{r}"
    if p["pallas"] and len(ops) == 5 and all(len(s) == 2 for s in ops):
        (m, n), (k, _), (_, r) = ops[0], ops[1], ops[2]
        return f"pallas backward M{m} K{k} N{n} r{r}"
    res = ",".join(f"{d}[{'x'.join(map(str, s))}]" for d, s in p["results"])
    return f"{'pallas' if p['pallas'] else p['kind']} {res}"[:120]


def reduce(pd) -> dict:
    spans = host_spans(pd)
    runs = [s for s in spans if s[2] == WINDOW_SPAN]
    if not runs:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = min(s[0] for s in runs), max(s[1] for s in runs)
    devs = device_ops(pd)
    if not devs:
        raise ValueError("no TPU device ops in the trace")
    busy, by_label, gaps, kernels = [], {}, [], []
    for ops in devs.values():
        merged = _clip(_union([[s, e] for s, e, _ in ops]), lo, hi)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for s, e, name in ops:
            if e <= lo or s >= hi:
                continue
            p = parse_op(name)
            if p["kind"] in CONTAINERS:
                continue
            dur = (e - s) * 1e-9
            label = _label(p)
            by_label[label] = by_label.get(label, 0.0) + dur
            if p["pallas"]:
                kernels.append((p["operands"], p["results"], dur))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        mid = 0.5 * (a + b)
        cover = [s for s in spans if s[0] <= mid <= s[1]]
        name = min(cover, key=lambda s: s[1] - s[0])[2] if cover else "none"
        idle.append([name, (b - a) * 1e-9])
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": sum(busy) / len(busy),
            "device_ops": [[k, v] for k, v in top], "idle_gaps": idle,
            "kernels": kernels}


def reduce_dir(trace_dir: str) -> dict:
    return reduce(load(find_xplane(trace_dir)))
