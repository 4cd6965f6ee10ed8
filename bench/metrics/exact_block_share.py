"""Share of the window's accumulated tiles that took the exact route, in %.

100 · blocks_exact / (blocks_pruned + blocks_exact), the program's own route
counts summed over the window's joins: a tile (a query block, and on the
2-D sweep a ring step) takes the all-reduce of its whole partial tile where
its Lemma-1 candidates overflow the capacity on a chip, and the candidates'
all-gather and rescore otherwise. Nothing to read where the program counts
no routes.
"""


def read(ctx):
    pruned = getattr(ctx.observed, "blocks_pruned", 0)
    exact = getattr(ctx.observed, "blocks_exact", 0)
    if pruned + exact <= 0:
        return None
    return 100.0 * exact / (pruned + exact)
