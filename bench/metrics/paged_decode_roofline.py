"""Share of its roofline the Pallas paged-decode kernel reached in the traced
window, in %: the least time the bytes it must move take at the peak HBM
bandwidth (bench/costs.py: q, live KV pages, output) over its device time.
Memory-bound: its FLOPs per byte are about one per query head per KV byte,
far under the chip's ridge point."""

# names the kernel's device operations carry in the trace
KERNEL = ("paged_decode", "_decode_kernel")


def read(ctx):
    tr, peaks = ctx["trace"], ctx["peaks"]
    if not tr or not peaks or not ctx["work"]["kernel_bytes"]:
        return None
    t = sum(v for k, v in tr["ops"].items() if any(s in k for s in KERNEL))
    if t <= 0:
        return None
    return 100.0 * ctx["work"]["kernel_bytes"] / peaks["hbm_bytes_per_s"] / t
