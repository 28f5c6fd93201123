"""Rows decoded per engine step in the window (device and host rows,
EngineStats)."""


def read(ctx):
    d = ctx["delta"]
    rows = d["device_decodes"] + d["offloaded_decodes"]
    return rows / d["steps"] if d["steps"] else None
