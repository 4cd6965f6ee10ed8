"""Device time per join in collective operations, in ms (mean over chips).

The summed device durations, from the profiler trace, of the operations
whose HLO is a collective (all-gather, all-reduce, reduce-scatter,
collective-permute, all-to-all, their asynchronous start and done halves
included), over the joins completed in the window. The trace's per-op
seconds are already the mean over the chips.
"""

import re

COLLECTIVE = re.compile(
    r"(?:^%|\s)(?:all-gather|all-reduce|reduce-scatter|collective-permute"
    r"|all-to-all)(?:-start|-done)?(?:\(|[.\w-]*\s=)"
)


def is_collective(hlo: str) -> bool:
    """Whether an operation's HLO text is a collective: by its opcode, or
    by an instruction name the compiler derived from one."""
    return COLLECTIVE.search(hlo) is not None


def read(ctx):
    t, obs = ctx.trace, ctx.observed
    if t is None or t.devices == 0 or obs.units == 0:
        return None
    seconds = sum(s for g, s in t.op_seconds.items() if is_collective(g))
    return 1e3 * seconds / obs.units
