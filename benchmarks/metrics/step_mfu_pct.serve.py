"""The served forward's share of the chip's peak: forward operations of the
real (unpadded) tokens and mask positions of every request answered in the
window (benchmarks/flops.py) / window / peak."""


def read(ctx):
    if "flops_done" not in ctx or ctx["flops_done"] <= 0:
        return None
    return 100.0 * ctx["flops_done"] / ctx["window_s"] / ctx["peak_flops"]
