"""Device time per join in the row ring's collective-permutes, in ms (mean over chips).

The summed device durations, from the profiler trace, of the operations
whose HLO is a collective-permute (its asynchronous start and done halves
included) and of no other collective, over the joins completed in the
window. The trace's per-op seconds are already the mean over the chips.
"""

import re

PERMUTE = re.compile(
    r"(?:^%|\s)collective-permute(?:-start|-done)?(?:\(|[.\w-]*\s=)"
)


def read(ctx):
    t, obs = ctx.trace, ctx.observed
    if t is None or t.devices == 0 or obs.units == 0:
        return None
    seconds = sum(s for g, s in t.op_seconds.items() if PERMUTE.search(g))
    return 1e3 * seconds / obs.units
