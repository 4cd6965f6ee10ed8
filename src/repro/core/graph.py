"""Similarity-graph construction from APSS matches.

The paper positions APSS output as an undirected similarity graph
``G_S(V, t) = (V, M)`` consumed by transduction / clustering / k-nn algorithms.
These helpers convert the fixed-capacity :class:`Matches` representation into
COO edge lists (host-side numpy — graph consumers are host programs).
"""

from __future__ import annotations

import numpy as np

from repro.core.matches import Matches


def matches_to_coo(
    m: Matches, *, undirected: bool = True
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Convert matches to COO ``(rows, cols, weights)``.

    With ``undirected=True``, keeps each unordered pair once (``i < j``).
    """
    vals = np.asarray(m.values)
    idx = np.asarray(m.indices)
    n, k = idx.shape
    rows = np.repeat(np.arange(n, dtype=np.int32), k)
    cols = idx.reshape(-1)
    w = vals.reshape(-1)
    keep = cols >= 0
    if undirected:
        keep &= rows < cols
    return rows[keep], cols[keep], w[keep].astype(np.float32)


def match_set(m: Matches) -> set[tuple[int, int]]:
    """Unordered match pairs as a python set (test/debug utility)."""
    rows, cols, _ = matches_to_coo(m, undirected=True)
    return {(int(i), int(j)) for i, j in zip(rows, cols)}
