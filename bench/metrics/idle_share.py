"""Share of the traced window in which no operation ran on the device, in %.

1 - (union of the device's operation intervals) / (window), from the
profiler trace, averaged over the chips.
"""


def read(ctx):
    t = ctx.trace
    if t is None or t.devices == 0 or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
