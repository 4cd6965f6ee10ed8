"""The CSR scoring primitives against float64 dense oracles.

``gather_dot`` scores a dense query block against a CSR corpus block (the
op the vertical self-join spends most of its join in), ``densify_rows``
scatters one row block to dense, and ``dedupe_rows`` combines duplicate
coordinates. The corpora here carry what the generators never produce:
empty rows, padding slots, duplicate (and cancelling) coordinates, and a
``cap`` wider than any row.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse import (
    SparseCorpus,
    dedupe_rows,
    densify_rows,
    gather_dot,
)


def _csr(n, m, cap, *, duplicates, seed=0):
    """Random padded CSR: rows of 0..cap entries (every 5th row empty),
    signed values, padding slots ``(0, 0.0)``; with ``duplicates`` the
    dimensions are drawn with replacement."""
    rng = np.random.default_rng(seed)
    idx = np.zeros((n, cap), np.int32)
    val = np.zeros((n, cap), np.float32)
    nnz = np.zeros(n, np.int32)
    for r in range(n):
        k = 0 if r % 5 == 4 else int(rng.integers(1, cap + 1))
        if not duplicates:
            k = min(k, m)
        dims = (
            rng.integers(0, m, size=k)
            if duplicates
            else rng.choice(m, size=k, replace=False)
        )
        idx[r, :k] = dims
        val[r, :k] = rng.standard_normal(k)
        nnz[r] = k
    return idx, val, nnz


def _dense64(idx, val, m):
    """Float64 scatter-add of a CSR block: duplicate coordinates sum."""
    out = np.zeros((idx.shape[0], m), np.float64)
    rows = np.repeat(np.arange(idx.shape[0]), idx.shape[1])
    np.add.at(out, (rows, idx.reshape(-1)), val.reshape(-1).astype(np.float64))
    return out


@pytest.mark.parametrize("duplicates", [False, True])
@pytest.mark.parametrize(
    "rows,cols,cap,m,chunk",
    [
        (8, 16, 5, 40, 32),    # cap below one chunk: padded to it
        (8, 16, 32, 40, 32),   # cap exactly one chunk
        (4, 12, 33, 64, 32),   # one slot past a chunk
        (16, 8, 7, 20, 4),     # several chunks, ragged last
        (1, 1, 1, 3, 32),      # a single slot
        (8, 8, 64, 16, 16),    # cap wider than m
    ],
)
def test_gather_dot_matches_float64_dense_product(
    rows, cols, cap, m, chunk, duplicates
):
    idx, val, _ = _csr(cols, m, cap, duplicates=duplicates, seed=cap)
    rng = np.random.default_rng(rows)
    qd = rng.standard_normal((rows, m)).astype(np.float32)
    qd[rows // 2] = 0.0  # an empty query row scores 0 everywhere
    got = gather_dot(
        jnp.asarray(qd), jnp.asarray(idx), jnp.asarray(val), chunk=chunk
    )
    want = qd.astype(np.float64) @ _dense64(idx, val, m).T
    assert got.shape == (rows, cols) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    # empty corpus rows (nnz 0, all padding) and the empty query row
    empty = np.flatnonzero(np.arange(cols) % 5 == 4)
    assert (np.asarray(got)[:, empty] == 0).all()
    assert (np.asarray(got)[rows // 2] == 0).all()


@pytest.mark.parametrize(
    "n,rows,start",
    [(16, 16, 0), (16, 8, 8), (16, 4, 12), (24, 8, 16), (9, 3, 3), (9, 1, 8)],
)
def test_densify_rows_is_the_block_of_the_dense_corpus(n, rows, start):
    m = 24
    idx, val, nnz = _csr(n, m, 6, duplicates=True, seed=n)
    # widen cap past every row: the extra slots are inert padding
    idx = np.pad(idx, ((0, 0), (0, 5)))
    val = np.pad(val, ((0, 0), (0, 5)))
    sp = SparseCorpus(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(nnz), m)
    want = _dense64(idx, val, m)[start:start + rows]
    eager = densify_rows(sp, start, rows)
    traced = jax.jit(
        lambda s, st: densify_rows(s, st, rows)
    )(sp, jnp.int32(start))
    for got in (eager, traced):
        assert got.shape == (rows, m)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


def _dedupe_case(name):
    if name == "distinct":
        return [[3, 1, 4, 0]], [[0.5, -1.0, 2.0, 0.0]]
    if name == "one_dimension":
        return [[2, 2, 2, 2]], [[0.25, 0.5, 1.0, -0.75]]
    if name == "cancelling":
        return [[5, 1, 5, 1]], [[0.3, 0.2, -0.3, 0.4]]
    if name == "dimension_zero_and_padding":
        return [[0, 7, 0, 0]], [[0.6, 0.1, 0.3, 0.0]]
    idx, val, _ = _csr(12, 6, 9, duplicates=True, seed=3)
    return idx, val


@pytest.mark.parametrize(
    "case",
    ["distinct", "one_dimension", "cancelling", "dimension_zero_and_padding",
     "random"],
)
def test_dedupe_rows_sums_each_dimension_into_one_slot(case):
    idx, val = (np.asarray(a) for a in _dedupe_case(case))
    idx = idx.astype(np.int32)
    val = val.astype(np.float32)
    di, dv = (np.asarray(a) for a in dedupe_rows(jnp.asarray(idx), jnp.asarray(val)))
    assert di.shape == idx.shape and dv.shape == val.shape
    m = int(idx.max()) + 1
    # the same vector, to f32 rounding of the run sums
    np.testing.assert_allclose(
        _dense64(di, dv, m), _dense64(idx, val, m), rtol=1e-6, atol=1e-6
    )
    for r in range(idx.shape[0]):
        # each dimension the row names owns exactly one slot; every other
        # slot is the inert (0, 0.0) padding
        for d in np.unique(idx[r]):
            if d == 0:
                continue
            assert (di[r] == d).sum() == 1, (r, d)
        assert (dv[r][di[r] == 0] != 0).sum() <= 1
    if case == "one_dimension":
        np.testing.assert_allclose(dv[0][di[0] == 2], [1.0])
    if case == "cancelling":
        np.testing.assert_allclose(dv[0][di[0] == 5], [0.0], atol=1e-7)
