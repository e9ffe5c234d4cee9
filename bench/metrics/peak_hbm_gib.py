"""Peak device memory of the fullest chip after the window, set-up
included (``peak_bytes_in_use``), in GiB."""


def read(ctx):
    peak = ctx["peak_bytes"]
    return None if peak is None else peak / 2 ** 30
