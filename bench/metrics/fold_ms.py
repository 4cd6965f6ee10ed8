"""Device time per join or per batch of the packet fold, in ms.

The operations under the program's ``jax.named_scope("fold")``
(``fold_packets`` / ``fold_rect_packets``: the scan that merges each
worklist entry's candidate packet into the running top-k), as a union of
intervals inside the traced window (``bench/program_trace.py``), over the
joins or batches completed in it.
"""


def read(ctx):
    pt, obs = getattr(ctx, "program", None), ctx.observed
    if pt is None or obs.units == 0:
        return None
    seconds = pt.scope_seconds("fold")
    return 1e3 * seconds / obs.units if seconds > 0 else None
