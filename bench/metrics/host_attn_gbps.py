"""KV bytes the window's HostAttention.run_layer calls had to read
(bench/costs.py, from row lengths and widths) over those calls' host seconds."""


def read(ctx):
    h = ctx["host_attn"]
    return h["bytes"] / h["seconds"] / 1e9 if h["seconds"] > 0 else None
