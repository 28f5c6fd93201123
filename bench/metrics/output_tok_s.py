"""Output tokens emitted by steps that ended inside the window, over the
window's length."""


def read(ctx):
    return ctx["delta"]["tokens"] / ctx["window_s"]
