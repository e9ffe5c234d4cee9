"""Attribute a traced window's device time to the names the program puts
on its work, and the device's idle gaps to the Trainer's host spans.

The program names its work three ways, all on the profiler's clock:

* ``jax.named_scope`` on its blocks (``repro.attention``, ``repro.loss``,
  ``repro.update``, ``repro.guard``) and on every routed low-rank op
  (``repro.<op>.<route>``).  A scope is a component of each device op's
  op-name path, possibly wrapped by JAX's transforms: ``jvp(repro.loss)``
  in the forward, ``transpose(jvp(repro.loss))`` in the backward.  JAX
  marks the remat recompute itself with a ``rematted_computation``
  component.
* ``name=`` on every Pallas call: the custom call is named after the
  kernel (``%lowrank_forward.12 = ... custom-call(...)``).
* ``TraceAnnotation`` host spans in ``Trainer._run``: one
  ``repro.train.step`` per step around ``repro.train.{outer,batch,
  dispatch,sync}``.

On a TPU trace a device op event on the "XLA Ops" line is named by its HLO
text, and its op-name path is the ``tf_op`` stat of the event's metadata,
which ``ProfileData`` does not expose; :func:`op_names` reads it from the
``.xplane.pb`` file.  :func:`reduce` turns a ``ProfileData`` and that map
into the numbers the per-layer readers below need.  Each reader takes the
metric context, finds its input under ``ctx["trace"]["scopes"]``, and
returns None where there is nothing to read (an untraced run, or a program
without these names).
"""
from __future__ import annotations

import re

from . import flops, trace

PROGRAM_SPAN = "repro.train."
STEP_SPAN = "repro.train.step"
REMAT = "rematted_computation"
ATTENTION = ("repro.attention",)
LOSS = ("repro.loss",)
UPDATE = ("repro.update", "repro.guard")
LOWRANK_BWD_XLA = ("repro.lowrank_backward.xla",)
TF_OP = "tf_op"


def in_scope(path: str, names) -> bool:
    """Whether one of ``names`` is a component of ``path``, bare or
    inside a transform's parentheses."""
    return any(re.search(r"(?:^|[/(])" + re.escape(n) + r"(?=[/)]|$)", path)
               for n in names)


# -- the op-name paths, from the xplane file -----------------------------

def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value as a memoryview slice."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf) -> str:
    return bytes(buf).decode()


def op_names(path: str) -> dict:
    """{HLO text: [op-name paths]} of every op on the TPU device planes of
    an ``.xplane.pb`` file, each distinct path once, in the order the file
    has them (two programs may hold ops of the same text under different
    paths).  Reads the XSpace proto: its planes (field 1), each plane's
    name (2), event metadata (4) and stat metadata (5), both maps of id
    (1) to message (2); an event metadata's name (2) and stats (5); a
    stat's metadata id (1) and its string value (5)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 4:
                metas.append(v)
            elif g == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry.get(2, b""))).get(2, b""))
        if not name.startswith("/device:TPU:"):
            continue
        for v in metas:
            hlo, op = "", ""
            for h, w in _fields(dict(_fields(v)).get(2, b"")):
                if h == 2:
                    hlo = _text(w)
                elif h == 5:
                    st = dict(_fields(w))
                    if stat_names.get(st.get(1)) == TF_OP and 5 in st:
                        op = _text(st[5])
            if hlo and op and op not in out.setdefault(hlo, []):
                out[hlo].append(op)
    return out


# -- the reduction ---------------------------------------------------------

def program_spans(pd) -> list:
    """[(start_ns, end_ns, name)] of the Trainer's host spans."""
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_SPAN) and ev.duration_ns > 0]


def _window(pd) -> tuple:
    runs = [s for s in trace.host_spans(pd) if s[2] == trace.WINDOW_SPAN]
    if not runs:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")
    return min(s[0] for s in runs), max(s[1] for s in runs)


def reduce(pd, names: dict) -> dict:
    """Device time by op-name path (``names``: :func:`op_names` of the
    same file; an op whose text has several paths goes under the first,
    and its time is also counted in ``ambiguous_s``) and by Pallas kernel
    name, summed over the TPU devices; idle time by the innermost Trainer
    span that covers each gap's midpoint ("none" outside them), averaged
    over the devices; and the number of steps in the window of the
    harness's ``bench.trainer_run`` spans.  Loop and call containers are
    left out, as in ``trace.reduce``."""
    lo, hi = _window(pd)
    spans = program_spans(pd)
    by_path, kernels, gaps = {}, {}, {}
    op_s = ambiguous_s = 0.0
    devices = 0
    for ops in trace.device_ops(pd).values():
        devices += 1
        for s, e, name in ops:
            if e <= lo or s >= hi:
                continue
            p = trace.parse_op(name)
            if p["kind"] in trace.CONTAINERS:
                continue
            dur = (e - s) * 1e-9
            op_s += dur
            paths = names.get(name) or [""]
            if len(paths) > 1:
                ambiguous_s += dur
            by_path[paths[0]] = by_path.get(paths[0], 0.0) + dur
            if p["pallas"]:
                kernels[p["kind"]] = kernels.get(p["kind"], 0.0) + dur
        merged = trace._clip(trace._union([[s, e] for s, e, _ in ops]),
                             lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            cover = [sp for sp in spans if sp[0] <= mid <= sp[1]]
            span = (min(cover, key=lambda sp: sp[1] - sp[0])[2]
                    if cover else "none")
            gaps[span] = gaps.get(span, 0.0) + (b - a) * 1e-9
    n = max(devices, 1)
    steps = sum(1 for s, e, name in spans
                if name == STEP_SPAN and lo <= 0.5 * (s + e) <= hi)
    return {"op_s": op_s, "by_path": by_path, "ambiguous_s": ambiguous_s,
            "kernels": kernels,
            "host_gaps": {k: v / n for k, v in gaps.items()},
            "steps": steps}


def reduce_dir(trace_dir: str) -> dict:
    path = trace.find_xplane(trace_dir)
    return reduce(trace.load(path), op_names(path))


# -- per-layer readers -----------------------------------------------------

def _scopes(ctx):
    return (ctx.get("trace") or {}).get("scopes")


def share(ctx, names) -> float | None:
    """% of the window's device op time under any of ``names``; None
    where no op carries one."""
    sc = _scopes(ctx)
    if not sc or sc["op_s"] <= 0:
        return None
    under = sum(s for p, s in sc["by_path"].items() if in_scope(p, names))
    return 100.0 * under / sc["op_s"] if under else None


def remat_share(ctx):
    return share(ctx, (REMAT,))


def attention_share(ctx):
    return share(ctx, ATTENTION)


def loss_share(ctx):
    return share(ctx, LOSS)


def update_share(ctx):
    return share(ctx, UPDATE)


def lowrank_bwd_xla_mxu(ctx):
    """% of chips x the bf16 peak reached by the XLA-routed fused
    backward: the FLOPs of every (k, n, r) the route log records as
    ``xla`` for ``lowrank_backward`` (dy W^T, dy B, q V^T, dy^T p:
    2kn + 2kr + 4nr per token) over the window's tokens, divided by the
    device time under ``repro.lowrank_backward.xla``."""
    sc = _scopes(ctx)
    if not sc:
        return None
    xla = {(shapes[1], shapes[2], shapes[3])
           for (op, shapes), rt in ctx["routes"].items()
           if op == "lowrank_backward" and rt == "xla" and len(shapes) == 4}
    work = sum(count * (2 * k * n + 2 * k * r + 4 * n * r)
               for k, n_stored, n, r, count
               in flops.lowrank_matmuls(ctx["cfg"], ctx["lowrank"])
               if (k, n_stored, r) in xla) * ctx["tokens"]
    spent = sum(s for p, s in sc["by_path"].items()
                if in_scope(p, LOWRANK_BWD_XLA))
    if not work or not spent:
        return None
    return 100.0 * work / (spent * ctx["peaks"]["bf16_flops"])


def host_wait_ms(ctx):
    """Device-idle ms per step whose gap lies in a Trainer host span."""
    sc = _scopes(ctx)
    if not sc or not sc["steps"]:
        return None
    wait = sum(s for k, s in sc["host_gaps"].items() if k != "none")
    return 1e3 * wait / sc["steps"]


METRICS = {
    "remat_share.train": remat_share,
    "attention_share.train": attention_share,
    "loss_share.train": loss_share,
    "update_share.train": update_share,
    "lowrank_bwd_xla_mxu.train": lowrank_bwd_xla_mxu,
    "host_wait_ms.train": host_wait_ms,
}
