"""Backend compiles (or persistent-cache loads) inside the window, from
JAX's monitoring events; 0 when set-up warmed every shape."""


def read(ctx):
    return len(ctx["compile_names"])
