"""Roofline share of the Pallas kernel ``_rect_cand_kernel``, in % (see ``bench/roofline.py``)."""

from bench.roofline import share


def read(ctx):
    return share(ctx, "_rect_cand_kernel")
