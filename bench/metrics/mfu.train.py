"""Model FLOP/s utilization of the traced window, in %: the FLOPs the
method requires per token (``harness.flops``) times tokens per second,
over chips times the device's bf16 peak."""


def read(ctx):
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
