"""The fullest chip's shard against the mean shard, in %: 100 · max / mean
of the nonzeros the program put on each chip (its own count, from the
telemetry record of the window's last join)."""


def read(ctx):
    nnz = getattr(ctx.observed, "shard_nnz", ())
    if not nnz or sum(nnz) <= 0:
        return None
    return 100.0 * max(nnz) * len(nnz) / sum(nnz)
