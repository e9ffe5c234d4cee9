"""Seconds from process start to the first step of the window: weights
made on the device, programs compiled or loaded from the cache, the
checked first steps and the warm-up of the outer step."""


def read(ctx):
    return ctx["setup_s"]
