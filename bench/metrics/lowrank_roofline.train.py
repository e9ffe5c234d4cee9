"""Share of the roofline reached by the low-rank kernel calls the trace
can attribute (today: the Pallas custom calls), in %: the sum of each
call's least time, the larger of its FLOPs over the bf16 peak and its
compulsory bytes over the HBM peak (``harness.flops.kernel_cost``), over
the sum of their device times.  Nothing to read where no kernel ran."""

from harness import flops


def read(ctx):
    t = ctx["trace"]
    if not t or not t["kernels"]:
        return None
    pk = ctx["peaks"]
    least = spent = 0.0
    for operands, results, dur in t["kernels"]:
        f, b = flops.kernel_cost(operands, results)
        least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
        spent += dur
    return 100.0 * least / spent if spent else None
