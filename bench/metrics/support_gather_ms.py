"""Device time per join of the CSR support gather, in ms.

The operations under the program's ``jax.named_scope("support_gather")``
(the scan that gathers each live tile's column block onto the row block's
support), as a union of intervals inside the traced window
(``bench/program_trace.py``), over the joins completed in it.
"""


def read(ctx):
    pt, obs = getattr(ctx, "program", None), ctx.observed
    if pt is None or obs.units == 0:
        return None
    seconds = pt.scope_seconds("support_gather")
    return 1e3 * seconds / obs.units if seconds > 0 else None
