"""Backend compilations that ran inside the measured window (count)."""


def read(ctx):
    return ctx["window_compiles"]
