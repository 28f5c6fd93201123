"""Share of the window's decoded rows whose attention ran on the host, in %."""


def read(ctx):
    d = ctx["delta"]
    rows = d["device_decodes"] + d["offloaded_decodes"]
    return 100.0 * d["offloaded_decodes"] / rows if rows else None
