"""Near-duplicate detection — the paper's own
application ("near-duplicate detection by using a high threshold").

Documents (as normalized feature vectors) are self-joined with the APSS
core at a high threshold; of every duplicate cluster only the lowest-index
row survives: a crawl is scrubbed of duplicated content with APSS as a
pipeline stage.
"""

from __future__ import annotations

import numpy as np

from repro.core.apss import apss_blocked, normalize_rows
from repro.core.graph import matches_to_coo


def dedup_corpus(
    D: np.ndarray,
    *,
    threshold: float = 0.95,
    k: int = 64,
    block_rows: int = 512,
) -> tuple[np.ndarray, np.ndarray]:
    """Return (keep_mask, duplicate_of) for rows of D.

    ``duplicate_of[i] = j < i`` for dropped rows, else -1.
    """
    import jax.numpy as jnp

    Dn = normalize_rows(jnp.asarray(D))
    matches = apss_blocked(Dn, threshold, k, block_rows=block_rows)
    rows, cols, _ = matches_to_coo(matches, undirected=True)
    n = D.shape[0]
    keep = np.ones(n, bool)
    dup_of = np.full(n, -1, np.int32)
    # rows < cols by construction: later row is the duplicate.
    order = np.argsort(cols, kind="stable")
    for r, c in zip(rows[order], cols[order]):
        if keep[c] and keep[r]:
            keep[c] = False
            dup_of[c] = r
    return keep, dup_of
