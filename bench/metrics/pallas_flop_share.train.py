"""Share of the step's low-rank matmul FLOPs, in %, whose op and shape
the dispatch layer's route log records as taking the Pallas kernel (the
forward part by the forward's route, the rest by the backward's)."""

from harness import flops


def read(ctx):
    routes = {}
    for (op, shapes), route in ctx["routes"].items():
        if op in ("lowrank_forward", "lowrank_backward") and len(shapes) == 4:
            _, k, n, r = shapes
            routes.setdefault((op, k, n, r), set()).add(route)
    fwd_only = ctx["cell"].traffic["method"] == "lowrank_lr"
    total = pallas = 0.0
    for k, n_stored, n, r, count in flops.lowrank_matmuls(ctx["cfg"],
                                                          ctx["lowrank"]):
        fwd = count * (2 * k * n + 2 * k * r + 2 * n * r)
        bwd = 0 if fwd_only else count * (2 * k * n + 2 * k * r + 4 * n * r)
        for op, f in (("lowrank_forward", fwd), ("lowrank_backward", bwd)):
            if not f:
                continue
            seen = routes.get((op, k, n_stored, r))
            if not seen:
                return None
            total += f
            if seen == {"pallas"}:
                pallas += f
    return 100.0 * pallas / total if total else None
