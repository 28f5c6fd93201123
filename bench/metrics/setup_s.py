"""Set-up seconds: process start to the window's opening (weights, engine,
compiles, lead-in traffic)."""


def read(ctx):
    return ctx["setup_s"]
