"""The plain reference, its lower-precision control, and the comparison.

The reference computes every score in float64 on the host from the
benchmark's own inputs, and imports nothing of the program:

- :class:`SparseSelfJoin` scores rows of a CSR corpus against all rows
  (self-pairs excluded): a dense product over the dimensions that many rows
  share plus a sparse product over the rest;
- :class:`DenseRetrieval` scores query rows against a dense corpus.

:func:`judge` holds a ``Matches``-shaped answer (per row: top-k values,
their column ids with ``-1`` for an empty slot, and the exact number of
scores at or above the threshold) against those scores. ``tol`` is the
value limit: a score within ``tol`` of the threshold may be counted either
way, and a column within ``2 * tol`` of the reference's k-th best may take
or leave the last places of the top k. Everything else is exact.

The control is the same reference computed the way a cheaper program
would: float32 inputs, products in three bfloat16 passes (the split that
``Precision.HIGH`` makes on a TPU), done on the device by
:func:`control_selfjoin` / :func:`control_retrieval` with
:func:`dot_bf16x3` (the split written out, so it is the same on every
backend and a CPU test computes it too).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sps

# Dimensions held by more rows than this go through the dense product.
_DENSE_COLUMN_ROWS = 64


class SparseSelfJoin:
    """Float64 scores of a padded-CSR corpus against itself."""

    def __init__(self, indices, values, nnz, m: int):
        indices = np.asarray(indices)
        n, cap = indices.shape
        valid = np.arange(cap)[None, :] < np.asarray(nnz)[:, None]
        rows = np.repeat(np.arange(n), cap).reshape(n, cap)[valid]
        cols = indices[valid]
        vals = np.asarray(values, np.float64)[valid]
        x = sps.csr_matrix((vals, (rows, cols)), shape=(n, m))
        x.sum_duplicates()
        heavy = np.bincount(x.indices, minlength=m) > _DENSE_COLUMN_ROWS
        self.head = np.ascontiguousarray(x[:, np.nonzero(heavy)[0]].toarray())
        self.tail = x[:, np.nonzero(~heavy)[0]].tocsr()
        self.tail_t = self.tail.T.tocsr()
        self.n = n

    def scores(self, lo: int, hi: int) -> np.ndarray:
        """``(hi - lo, n)`` scores of rows ``lo:hi``; self-pairs ``-inf``."""
        s = self.head[lo:hi] @ self.head.T
        s += (self.tail[lo:hi] @ self.tail_t).toarray()
        r = np.arange(hi - lo)
        s[r, lo + r] = -np.inf
        return s


class DenseRetrieval:
    """Float64 scores of unit query rows against unit corpus rows."""

    def __init__(self, corpus_unit: np.ndarray):
        self.corpus = corpus_unit

    def scores(self, queries_unit: np.ndarray) -> np.ndarray:
        return queries_unit @ self.corpus.T


@dataclasses.dataclass
class Verdict:
    """What :func:`judge` found over the rows it was given."""

    value_gap: float = 0.0  # widest |answer value - reference score|
    bad_rows: int = 0       # rows whose ids or count break the rules
    rows: int = 0
    matches: int = 0        # reference scores >= threshold + tol

    def add(self, other: "Verdict") -> "Verdict":
        return Verdict(
            max(self.value_gap, other.value_gap),
            self.bad_rows + other.bad_rows,
            self.rows + other.rows,
            self.matches + other.matches,
        )


def judge(scores, values, indices, counts, threshold, k, tol) -> Verdict:
    """:func:`judge_rows` summed over the rows."""
    gap, bad, matches = judge_rows(scores, values, indices, counts, threshold, k, tol)
    return Verdict(
        value_gap=float(gap.max(initial=0.0)),
        bad_rows=int(bad.sum()),
        rows=int(gap.size),
        matches=int(matches.sum()),
    )


def judge_rows(scores, values, indices, counts, threshold, k, tol):
    """Hold one block of answers against the reference ``scores``.

    ``scores (r, c)`` float64, ``-inf`` where a column may never match;
    ``values (r, k)``, ``indices (r, k)``, ``counts (r,)`` the answers of the
    same rows. A row is bad when its count lies outside the counts the
    reference allows within ``tol`` of the threshold; when it does not hold
    ``min(count, k)`` distinct valid columns; when a column it holds scores
    below ``threshold - tol`` or more than ``2 * tol`` below the reference's
    k-th best; or when it leaves out a column that scores above both
    ``threshold + tol`` and the reference's (k+1)-th best by ``2 * tol``.

    Returns per row the widest value gap, whether the row is bad, and the
    reference's number of scores at or above ``threshold + tol``.
    """
    scores = np.asarray(scores, np.float64)
    values = np.asarray(values, np.float64)
    indices = np.asarray(indices, np.int64)
    counts = np.asarray(counts, np.int64)
    r, c = scores.shape
    t = float(threshold)

    member = indices >= 0
    in_range = member & (indices < c)
    safe = np.where(in_range, indices, 0)
    s_member = np.where(in_range, np.take_along_axis(scores, safe, axis=1), -np.inf)
    with np.errstate(invalid="ignore"):
        gap = np.abs(np.where(member, values, 0.0) - np.where(member, s_member, 0.0))
    finite_gap = np.where(np.isfinite(gap), gap, 0.0)

    count_lo = (scores >= t + tol).sum(axis=1)
    count_hi = (scores >= t - tol).sum(axis=1)
    kk = min(k + 1, c)
    top_idx = np.argpartition(-scores, kk - 1, axis=1)[:, :kk]
    top_val = np.take_along_axis(scores, top_idx, axis=1)
    order = np.argsort(-top_val, axis=1, kind="stable")
    top_idx = np.take_along_axis(top_idx, order, axis=1)
    top_val = np.take_along_axis(top_val, order, axis=1)
    kth = top_val[:, k - 1] if c >= k else np.full(r, -np.inf)
    next_best = top_val[:, k] if c > k else np.full(r, -np.inf)

    bad = (counts < count_lo) | (counts > count_hi)
    bad |= member.sum(axis=1) != np.minimum(counts, k)
    bad |= (member & ~in_range).any(axis=1)
    bad |= (member & ~np.isfinite(gap)).any(axis=1)
    floor = np.maximum(t - tol, kth - 2 * tol)
    bad |= (member & ~(s_member >= floor[:, None])).any(axis=1)
    ids = np.sort(np.where(member, indices, -1), axis=1)
    bad |= ((ids[:, 1:] == ids[:, :-1]) & (ids[:, 1:] >= 0)).any(axis=1)
    must = np.maximum(t + tol, next_best + 2 * tol)
    required = top_val[:, :k] > must[:, None]
    present = (top_idx[:, :k, None] == indices[:, None, :]).any(axis=2)
    bad |= (required & ~present).any(axis=1)

    return finite_gap.max(axis=1, initial=0.0), bad, count_lo


# ---------------------------------------------------------------------------
# The control: the reference in three bfloat16 passes, on the device
# ---------------------------------------------------------------------------


def dot_bf16x3(a, b):
    """``a @ b.T`` of float32 operands from three bfloat16 products.

    Each operand is split into a bfloat16 head and a bfloat16 tail, both
    rounded with ``reduce_precision`` (which the compiler may not drop as
    excess precision), and ``head·head + head·tail + tail·head`` is summed
    in float32: the split ``Precision.HIGH`` makes on a TPU, the same on
    every backend.
    """

    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    def mm(x, y):
        return jnp.einsum("rd,cd->rc", x, y, preferred_element_type=jnp.float32)

    ah, al = split(a)
    bh, bl = split(b)
    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def _topk(scores, threshold, k):
    ok = scores >= threshold
    masked = jnp.where(ok, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, k)
    idx = jnp.where(vals > -jnp.inf, idx, -1)
    return vals, idx, jnp.sum(ok, axis=1, dtype=jnp.int32)


def control_selfjoin(indices, values, nnz, m, threshold, k, block=512):
    """The self-join's answer from :func:`dot_bf16x3` over the dense corpus."""
    indices = jnp.asarray(indices)
    values = jnp.asarray(values)
    n, cap = indices.shape
    block = min(block, n)
    valid = jnp.arange(cap)[None, :] < jnp.asarray(nnz)[:, None]
    dense = (
        jnp.zeros((n, m), jnp.float32)
        .at[jnp.arange(n)[:, None], indices]
        .add(jnp.where(valid, values, 0.0))
    )

    @jax.jit
    def rows(dense, lo):
        blk = jax.lax.dynamic_slice_in_dim(dense, lo, block)
        s = dot_bf16x3(blk, dense)
        gid = lo + jnp.arange(block)
        s = jnp.where(gid[:, None] == jnp.arange(n)[None, :], -jnp.inf, s)
        return _topk(s, threshold, k)

    parts = [
        jax.tree.map(np.asarray, rows(dense, min(lo, n - block)))
        for lo in range(0, n, block)
    ]
    out = [np.zeros((n,) + p.shape[1:], p.dtype) for p in parts[0]]
    for lo, p in zip(range(0, n, block), parts):
        lo_eff = min(lo, n - block)
        for o, a in zip(out, p):
            o[lo_eff:lo_eff + block] = a
    return tuple(out)


def control_retrieval(queries, corpus, threshold, k):
    """Retrieval answers from :func:`dot_bf16x3` of unit float32 rows."""

    @jax.jit
    def run(q, c):
        def unit(x):
            return x / jnp.sqrt(jnp.sum(x * x, axis=1, keepdims=True))

        return _topk(dot_bf16x3(unit(q), unit(c)), threshold, k)

    return tuple(np.asarray(a) for a in run(jnp.asarray(queries), jnp.asarray(corpus)))
