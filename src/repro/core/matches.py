"""Fixed-capacity match extraction for APSS on static-shape accelerators.

The paper emits a variable-length list of ``(i, j, sim)`` matches. On a TPU the
output must be statically shaped, so we represent matches per query row as a
top-``k`` buffer plus an *exact* per-row match count:

- ``values[i, :]``  the ``k`` highest similarities ≥ ``t`` for row ``i``
  (padded with ``-inf``),
- ``indices[i, :]`` their global column ids (padded with ``-1``),
- ``counts[i]``     the exact number of matches ≥ ``t`` (may exceed ``k``; a
  count larger than ``k`` flags truncation — never silent).

This mirrors the paper's all-pairs-0-array design decision: a dense score
accumulator with post-hoc filtering, instead of a hash table.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)

# Precision of every scoring dot: the kernels, the XLA scans, the pruning
# bounds and the oracle. On a TPU, DEFAULT computes an f32 dot in a single
# bf16 pass (about three significant digits), which moves scores across the
# threshold, reorders near-ties in top-k and can push a tile's upper bound
# below a score inside it. HIGHEST keeps f32 accuracy; on the CPU it is
# what DEFAULT already computes.
SCORE_PRECISION = jax.lax.Precision.HIGHEST


class Matches(NamedTuple):
    """Top-k thresholded matches for a block of query rows."""

    values: jax.Array   # (rows, k) f32
    indices: jax.Array  # (rows, k) i32, -1 = empty slot
    counts: jax.Array   # (rows,)   i32, exact #matches ≥ t

    @property
    def capacity(self) -> int:
        return self.values.shape[-1]

    def overflowed(self) -> jax.Array:
        """Rows whose exact count exceeds the top-k capacity."""
        return self.counts > self.capacity


def empty_matches(rows: int, k: int) -> Matches:
    return Matches(
        values=jnp.full((rows, k), NEG_INF, dtype=jnp.float32),
        indices=jnp.full((rows, k), -1, dtype=jnp.int32),
        counts=jnp.zeros((rows,), dtype=jnp.int32),
    )


def extract_matches(
    scores: jax.Array,
    threshold: jax.Array | float,
    k: int,
    *,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    exclude_self: bool = True,
    col_valid: jax.Array | None = None,
) -> Matches:
    """Extract per-row thresholded top-k matches from a dense score tile.

    Args:
      scores: ``(rows, cols)`` dense similarity tile, f32.
      threshold: similarity threshold ``t``.
      k: static match capacity per row.
      row_offset / col_offset: global ids of ``scores[0, 0]`` — used for
        self-pair exclusion and for emitting global column indices.
      exclude_self: mask the ``i == j`` diagonal (APSS self-join semantics).
      col_valid: optional ``(cols,)`` bool mask for padded corpus columns.
    """
    rows, cols = scores.shape
    scores = scores.astype(jnp.float32)
    gcol = jnp.arange(cols, dtype=jnp.int32) + jnp.asarray(col_offset, jnp.int32)
    ok = scores >= jnp.asarray(threshold, jnp.float32)
    if exclude_self:
        grow = jnp.arange(rows, dtype=jnp.int32) + jnp.asarray(row_offset, jnp.int32)
        ok &= grow[:, None] != gcol[None, :]
    if col_valid is not None:
        ok &= col_valid[None, :]

    masked = jnp.where(ok, scores, NEG_INF)
    kk = min(k, cols)
    vals, local_idx = jax.lax.top_k(masked, kk)
    idx = jnp.take(gcol, local_idx, axis=0)
    idx = jnp.where(vals > NEG_INF, idx, -1)
    if kk < k:  # corpus tile narrower than capacity: pad out to k
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    counts = jnp.sum(ok, axis=-1, dtype=jnp.int32)
    return Matches(values=vals, indices=idx, counts=counts)


def merge_matches(a: Matches, b: Matches) -> Matches:
    """Merge two match sets over *disjoint* column ranges for the same rows.

    Counts add; the top-k buffers are re-selected from the union. Used to fold
    ring steps / column blocks into a running result.
    """
    vals = jnp.concatenate([a.values, b.values], axis=-1)
    idx = jnp.concatenate([a.indices, b.indices], axis=-1)
    k = a.capacity
    top_vals, sel = jax.lax.top_k(vals, k)
    top_idx = jnp.take_along_axis(idx, sel, axis=-1)
    top_idx = jnp.where(top_vals > NEG_INF, top_idx, -1)
    return Matches(
        values=top_vals,
        indices=top_idx,
        counts=a.counts + b.counts,
    )


def dedupe_candidates(
    values: jax.Array, indices: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Deduplicate per-row ``(value, index)`` candidate lists by index.

    Duplicates arise in the vertical compressed accumulation when several
    devices propose the same candidate column (each copy carries the identical
    fully-accumulated score). Keeps the first occurrence of every index;
    duplicate slots are invalidated to ``(-inf, -1)``.

    Args:
      values: ``(rows, c)`` scores.
      indices: ``(rows, c)`` int32 column ids, -1 = empty.
    """
    order = jnp.argsort(indices, axis=-1)  # -1 sentinels sort first
    s_idx = jnp.take_along_axis(indices, order, axis=-1)
    s_val = jnp.take_along_axis(values, order, axis=-1)
    prev = jnp.concatenate(
        [jnp.full_like(s_idx[:, :1], -2), s_idx[:, :-1]], axis=-1
    )
    first = (s_idx != prev) & (s_idx >= 0)
    out_val = jnp.where(first, s_val, NEG_INF)
    out_idx = jnp.where(first, s_idx, -1)
    return out_val, out_idx


def matches_from_candidates(
    values: jax.Array,
    indices: jax.Array,
    threshold: jax.Array | float,
    k: int,
    *,
    row_offset: jax.Array | int = 0,
    exclude_self: bool = True,
    dedupe: bool = True,
) -> Matches:
    """Build :class:`Matches` from sparse per-row candidate lists.

    Used by the vertical compressed/recursive accumulators whose final scores
    live in compacted ``(value, index)`` form rather than a dense tile.
    """
    values = values.astype(jnp.float32)
    if dedupe:
        values, indices = dedupe_candidates(values, indices)
    ok = (values >= jnp.asarray(threshold, jnp.float32)) & (indices >= 0)
    if exclude_self:
        rows = values.shape[0]
        grow = jnp.arange(rows, dtype=jnp.int32) + jnp.asarray(row_offset, jnp.int32)
        ok &= indices != grow[:, None]
    masked = jnp.where(ok, values, NEG_INF)
    kk = min(k, values.shape[-1])
    vals, sel = jax.lax.top_k(masked, kk)
    idx = jnp.take_along_axis(jnp.where(ok, indices, -1), sel, axis=-1)
    idx = jnp.where(vals > NEG_INF, idx, -1)
    if kk < k:
        vals = jnp.pad(vals, ((0, 0), (0, k - kk)), constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    counts = jnp.sum(ok, axis=-1, dtype=jnp.int32)
    return Matches(values=vals, indices=idx, counts=counts)


def total_matches(m: Matches) -> jax.Array:
    """Total directed match count (each unordered pair counted twice)."""
    return jnp.sum(m.counts)
