"""Training tokens per second: the tokens of every step completed in the
window, on all chips, over the window's wall time (host clock)."""


def read(ctx):
    return ctx["tokens_per_s"]
