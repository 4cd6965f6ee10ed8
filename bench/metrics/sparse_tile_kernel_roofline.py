"""Roofline share of the Pallas kernel ``_sparse_tile_kernel``, in % (see ``bench/roofline.py``)."""

from bench.roofline import share


def read(ctx):
    return share(ctx, "_sparse_tile_kernel")
