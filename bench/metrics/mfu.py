"""Model FLOP/s utilization of the traced window, in %: forward FLOPs of
every prompt and output token processed in it (bench/costs.py) over the
window times the chip's bf16 peak."""

from bench.costs import forward_flops


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks:
        return None
    flops = forward_flops(ctx["work"], ctx["dims"])
    return 100.0 * flops / (tr["window_s"] * peaks["bf16_flops"] * tr["devices"])
