"""Device time per join or per batch outside the Pallas kernels, in ms.

The device's busy time in the traced window (union of its operations)
minus the time of the kernels the cell's work names, over the joins or
batches completed in the window: the support gather, the packet fold and
the bound and mask programs.
"""


def read(ctx):
    t, obs = ctx.trace, ctx.observed
    if t is None or t.devices == 0 or obs.units == 0:
        return None
    kernels = sum(t.kernel_seconds(names) for names in obs.names.values())
    return 1e3 * (t.busy_s - kernels) / obs.units
