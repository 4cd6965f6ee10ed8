"""Live tiles over worklist entries, in %.

The program's own counts on its ``query/worklist`` and ``apss/worklist``
spans (``bench/program_trace.py``): the live tiles compacted from the
block mask over the entries the worklist holds after bucket padding,
summed over the calls that began in the traced window. The kernel and
the fold run over every entry, so 100 % minus this is the share of their
work spent on padding.
"""

from bench.program_trace import WORKLIST_SPANS


def read(ctx):
    pt = getattr(ctx, "program", None)
    if pt is None:
        return None
    entries = pt.stat_sum(WORKLIST_SPANS, "entries")
    if entries <= 0:
        return None
    return 100.0 * pt.stat_sum(WORKLIST_SPANS, "live") / entries
