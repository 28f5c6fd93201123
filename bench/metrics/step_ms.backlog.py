"""Window over engine steps in it (ms per step), backlog cells."""


def read(ctx):
    steps = ctx["delta"]["steps"]
    return ctx["window_s"] / steps * 1e3 if steps else None
