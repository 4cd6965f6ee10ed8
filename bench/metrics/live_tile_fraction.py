"""Live tiles over all tiles of the block bound mask, in %.

The program's own host counts (``ApssStats.live_tiles / total_tiles`` for
a self-join; the compacted worklist against the query-by-corpus block grid
for retrieval), summed over the window.
"""


def read(ctx):
    obs = ctx.observed
    if obs.total_tiles <= 0:
        return None
    return 100.0 * obs.live_tiles / obs.total_tiles
