"""Mean share of a scoring batch's slots that held a request, in %.

The mean of the server's ``serving.batch_occupancy`` over the window.
"""


def read(ctx):
    fill = ctx.observed.batch_fill
    return None if fill is None else 100.0 * fill
