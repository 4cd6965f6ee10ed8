"""Quoted replies: a layer over a sparse stand-in corpus that gives it matches.

Posts of a newsgroup quote the post they answer. A Zipf stand-in alone has
almost no pair of rows at a cosine of 0.4 or more, so a self-join on it
would check nothing. :func:`quoted_replies` makes a share of the rows
replies: each reply replaces a share, drawn from U(``quoted``), of its
entries with entries of a parent drawn uniformly from the other rows, the
parent's values copied, and the row made unit-norm again.

A reply keeps its length, so the corpus keeps its exact number of
nonzeros. The quoted entries are a uniform subset of the parent's
(as many as the parent has, at most); the reply's own entries that
remain are those of its original entries not quoted, in their order,
cut to the row's length. Indices stay sorted and unique per row.

The draws come from their own ``SeedSequence`` stream of the run's seed,
so the same seed gives the same corpus.
"""

from __future__ import annotations

import numpy as np

from bench import data

STREAM_REPLIES = 6


def quoted_replies(
    indices: np.ndarray, values: np.ndarray, nnz: np.ndarray, seed: int,
    *, share: float = 0.25, quoted: tuple[float, float] = (0.2, 0.8),
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The padded CSR ``(indices, values, nnz)`` with quoted replies."""
    rng = data.numpy_rng(seed, STREAM_REPLIES)
    n = nnz.shape[0]
    replies = rng.choice(n, size=int(round(share * n)), replace=False)
    parents = (replies + rng.integers(1, n, size=replies.size)) % n
    fractions = rng.uniform(*quoted, size=replies.size)
    out_idx, out_val = indices.copy(), values.astype(np.float64)
    for row, parent, frac in zip(replies, parents, fractions):
        length, p_len = int(nnz[row]), int(nnz[parent])
        q = min(int(round(frac * length)), p_len)
        pick = rng.choice(p_len, size=q, replace=False)
        q_idx = indices[parent, pick]
        own = indices[row, :length]
        keep = ~np.isin(own, q_idx)
        own_idx = own[keep][: length - q]
        own_val = values[row, :length][keep][: length - q]
        dims = np.concatenate([q_idx, own_idx])
        vals = np.concatenate([values[parent, pick], own_val]).astype(np.float64)
        order = np.argsort(dims)
        out_idx[row, :length] = dims[order]
        out_val[row, :length] = vals[order] / np.sqrt(np.sum(vals * vals))
    return out_idx, out_val.astype(np.float32), nnz
