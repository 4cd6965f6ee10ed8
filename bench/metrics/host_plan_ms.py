"""Host time per join or per batch in the program's planning spans, in ms.

The program's own spans (``bench/program_trace.py``): ``apss/bounds``,
``apss/worklist``, ``apss/support_gather`` and ``apss/upload`` in a
self-join, ``query/worklist`` in a ``query_topk`` call, summed inside the
traced window, over the joins or batches completed in it. ``query/mask``
is left out: it waits on the device.
"""

from bench.program_trace import PLAN_SPANS


def read(ctx):
    pt, obs = getattr(ctx, "program", None), ctx.observed
    if pt is None or obs.units == 0:
        return None
    seconds = pt.span_seconds(PLAN_SPANS)
    return 1e3 * seconds / obs.units if seconds > 0 else None
