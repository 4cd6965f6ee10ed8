"""The whole window's share of the chip's peak operation rate, in %.

The operations that the cell's kernels' work needs (``bench/work.py``),
over the traced window times the bfloat16 peak. Unlike a kernel's roofline
share it counts idle time and every other operation, so it still bounds a
change that takes a kernel off the path.
"""


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peaks is None or t.window_s <= 0:
        return None
    flops = sum(f for f, _ in ctx.observed.work.values())
    if flops <= 0:
        return None
    return 100.0 * flops / (t.window_s * ctx.peaks["bf16_flops_per_s"])
