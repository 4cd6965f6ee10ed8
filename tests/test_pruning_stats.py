"""Block pruning summaries and the live-tile mask, checked one piece at a
time: the CSR summaries against the dense ones, ``row_nnz`` and the
minsize summaries with ``eps``, and ``live_tile_mask`` under every
combination of its switches, at block sizes that do and do not divide n.
Every pruned tile's oracle maximum must lie below the threshold.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apss import pad_rows
from repro.core.pruning import (
    block_maxweight_bounds,
    block_minsize_bounds,
    dense_block_stats,
    live_tile_mask,
    row_nnz,
    sparse_block_maxweight,
    sparse_block_stats,
)
from repro.core.sparse import SparseCorpus, from_dense, pad_rows_sparse, to_dense
from repro.data.synthetic import synthetic_corpus


@pytest.fixture(scope="module")
def zipf50():
    """50 Zipfian rows: 50 is divisible by some block sizes and not others."""
    return synthetic_corpus(50, 96, 6.0, seed=4)


@pytest.mark.parametrize("block", [5, 8, 10, 16, 64])
def test_sparse_block_stats_equal_dense_block_stats(zipf50, block):
    Dp, _ = pad_rows(jnp.asarray(zipf50), block)
    spp, _ = pad_rows_sparse(from_dense(zipf50), block)
    dense = dense_block_stats(Dp, block)
    sparse = sparse_block_stats(spp, block)
    nb = -(-50 // block)
    assert dense.maxw.shape == sparse.maxw.shape == (nb, 96)
    # the CSR side takes each weight through dedupe_rows' run sums, which
    # can round it by an ulp; the support (which weights are 0) is exact
    for a, b in ((sparse.maxw, dense.maxw), (sparse.mw, dense.mw)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0, atol=2.5e-7
        )
    np.testing.assert_array_equal(
        np.asarray(sparse.maxw) > 0, np.asarray(dense.maxw) > 0
    )
    np.testing.assert_array_equal(
        np.asarray(sparse.max_nnz), np.asarray(dense.max_nnz)
    )


@pytest.mark.parametrize("block", [2, 4, 8])
def test_sparse_block_maxweight_sees_duplicates_as_one_component(block):
    """Duplicate slots (some cancelling) bound by ``|Σ slots|``, which is
    what the dense bound sees after the scatter-add."""
    rng = np.random.default_rng(block)
    n, m, cap = 16, 12, 6
    idx = rng.integers(0, 4, size=(n, cap)).astype(np.int32)  # many repeats
    val = rng.standard_normal((n, cap)).astype(np.float32)
    idx[0, :2], val[0, :2] = 3, [0.5, -0.5]  # cancels to 0
    idx[1, :3], val[1, :3] = 1, [0.2, 0.2, 0.2]  # 0.6 > any single slot
    sp = SparseCorpus(
        jnp.asarray(idx), jnp.asarray(val), jnp.full((n,), cap, jnp.int32), m
    )
    got = np.asarray(sparse_block_maxweight(sp, block))
    want = np.asarray(block_maxweight_bounds(to_dense(sp), block))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("eps", [0.0, 0.05, 0.3])
def test_row_nnz_counts_components_above_eps(zipf50, eps):
    D = zipf50.copy()
    D[3] = 0.0  # an empty row
    D[7, :4] = -0.4  # negative weights count by magnitude
    got = np.asarray(row_nnz(jnp.asarray(D), eps))
    np.testing.assert_array_equal(got, (np.abs(D) > eps).sum(axis=1))
    assert got[3] == 0 and got.dtype == np.int32


@pytest.mark.parametrize("eps", [0.0, 0.05, 0.3])
def test_block_minsize_bounds_with_eps(zipf50, eps):
    block = 10
    mw, max_nnz = block_minsize_bounds(jnp.asarray(zipf50), block, eps)
    blocks = np.abs(zipf50).reshape(5, block, 96)
    np.testing.assert_array_equal(np.asarray(mw), blocks.max(axis=(1, 2)))
    np.testing.assert_array_equal(
        np.asarray(max_nnz), (blocks > eps).sum(axis=2).max(axis=1)
    )
    # dense_block_stats passes eps through to the same summary
    stats = dense_block_stats(jnp.asarray(zipf50), block, eps)
    np.testing.assert_array_equal(np.asarray(stats.max_nnz), np.asarray(max_nnz))


@pytest.fixture(scope="module")
def flat_and_short():
    """48 unit rows in two disjoint bands of 32 dimensions (rows 0-23, 24-47),
    so cross-band tiles are provably dead. In each band the first 12 rows
    are flat (0.25 on 16 dimensions) and the last 12 short (0.5 on 4): a
    flat row meets a short one at most 4 · 0.25 · 0.5 = 0.5, which the
    minsize bound ``0.25 · √4`` proves at block 12 while the maxweight
    bound does not."""
    rng = np.random.default_rng(6)
    D = np.zeros((48, 64), np.float32)
    for r in range(48):
        band = 32 * (r // 24)
        flat = r % 24 < 12
        dims = band + rng.choice(32, size=16 if flat else 4, replace=False)
        D[r, dims] = 0.25 if flat else 0.5
    return D


@pytest.mark.parametrize("block", [10, 12])
@pytest.mark.parametrize(
    "use_minsize,normalized",
    [(False, False), (False, True), (True, False), (True, True)],
)
@pytest.mark.parametrize("t", [0.1, 0.3, 0.5, 0.7, 0.9])
def test_live_tile_mask_prunes_only_dead_tiles(
    flat_and_short, block, use_minsize, normalized, t
):
    Dp, _ = pad_rows(jnp.asarray(flat_and_short), block)
    nb = Dp.shape[0] // block
    stats = dense_block_stats(Dp, block)
    live, ub = live_tile_mask(
        stats, stats, t,
        use_minsize=use_minsize, normalized=normalized, return_ub=True,
    )
    live, ub = np.asarray(live), np.asarray(ub)
    assert live.shape == ub.shape == (nb, nb)
    np.testing.assert_array_equal(
        live,
        np.asarray(live_tile_mask(
            stats, stats, t, use_minsize=use_minsize, normalized=normalized
        )),
    )
    # the oracle: the largest similarity inside each tile
    D64 = np.asarray(Dp, np.float64)
    S = D64 @ D64.T
    tile_max = S.reshape(nb, block, nb, block).max(axis=(1, 3))
    assert (ub >= tile_max - 1e-6).all()  # ub bounds every pair in its tile
    assert (tile_max[~live] < t).all()  # every pruned tile holds no match
    np.testing.assert_array_equal(live, ub >= t)
    # cross-band tiles share no dimension: ub is 0 and they are dead
    assert not live.all()
    # the minsize bound applies only to a normalized column side
    maxweight_only = np.asarray(
        live_tile_mask(stats, stats, t, use_minsize=False)
    )
    if use_minsize and normalized:
        assert not (live & ~maxweight_only).any()
        if block == 12 and t > 0.5:  # flat × short tiles
            assert (maxweight_only & ~live).any()
    else:
        np.testing.assert_array_equal(live, maxweight_only)
