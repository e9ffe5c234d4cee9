"""Programs the Trainer compiled inside the window (growth of the inner
and outer steps' jit caches across it); should read 0."""


def read(ctx):
    return ctx["window_compiles"]
