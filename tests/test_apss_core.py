"""Unit tests: single-device APSS core (oracle, blocked, matches, pruning)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apss import (
    apss_blocked,
    apss_reference,
    normalize_rows,
    pad_rows,
    similarity_topk,
)
from repro.core.graph import match_set, matches_to_coo
from repro.core.matches import (
    dedupe_candidates,
    empty_matches,
    extract_matches,
    matches_from_candidates,
    merge_matches,
)
from repro.core.pruning import (
    block_prune_mask,
    block_upper_bounds,
    block_maxweight_bounds,
    local_threshold,
    prune_stats,
)

T, K = 0.35, 16


def test_reference_matches_bruteforce(corpus):
    ref = apss_reference(jnp.asarray(corpus), T, K)
    S = corpus @ corpus.T
    np.fill_diagonal(S, 0.0)
    want = int((S >= T).sum())
    assert int(ref.counts.sum()) == want
    # every reported value really is ≥ T and equals the true similarity
    rows, cols, w = matches_to_coo(ref, undirected=False)
    np.testing.assert_allclose(w, S[rows, cols], rtol=1e-5)
    assert (w >= T).all()


@pytest.mark.parametrize("block_rows", [16, 32, 128, 24, 48, 100])
def test_blocked_equals_reference(corpus, block_rows):
    ref = apss_reference(jnp.asarray(corpus), T, K)
    blk = apss_blocked(jnp.asarray(corpus), T, K, block_rows=block_rows)
    assert match_set(blk) == match_set(ref)
    np.testing.assert_array_equal(blk.counts, ref.counts)


def test_blocked_with_prune_stats(corpus):
    m, stats = apss_blocked(
        jnp.asarray(corpus), T, K, block_rows=32, with_prune_stats=True
    )
    ref = apss_reference(jnp.asarray(corpus), T, K)
    assert match_set(m) == match_set(ref)
    assert 0.0 < float(stats.live_fraction) <= 1.0


def test_similarity_topk_join():
    rng = np.random.default_rng(1)
    Q = rng.standard_normal((37, 24)).astype(np.float32)
    C = rng.standard_normal((53, 24)).astype(np.float32)
    Q = np.asarray(normalize_rows(jnp.asarray(Q)))
    C = np.asarray(normalize_rows(jnp.asarray(C)))
    got = similarity_topk(jnp.asarray(Q), jnp.asarray(C), 0.2, k=8, block_rows=16)
    S = Q @ C.T
    np.testing.assert_array_equal(
        np.asarray(got.counts), (S >= 0.2).sum(1).astype(np.int32)
    )
    # top-1 is the argmax wherever some match exists
    has = np.asarray(got.counts) > 0
    np.testing.assert_array_equal(
        np.asarray(got.indices[:, 0])[has], S.argmax(1)[has]
    )


def test_extract_matches_excludes_self():
    S = jnp.eye(8, dtype=jnp.float32)  # only self-similarities
    m = extract_matches(S, 0.5, 4)
    assert int(m.counts.sum()) == 0
    assert (np.asarray(m.indices) == -1).all()


def test_extract_matches_capacity_overflow_visible():
    S = jnp.ones((4, 10), jnp.float32)
    m = extract_matches(S, 0.5, 3, exclude_self=False)
    assert (np.asarray(m.counts) == 10).all()
    assert bool(m.overflowed().all())


@pytest.mark.parametrize("shape,multiple", [
    ((10, 3), 4), ((8, 3), 4), ((5,), 8), ((3, 2, 2), 1),
])
def test_pad_rows_zero_pads_axis_0_to_a_multiple(shape, multiple):
    x = jnp.arange(1, 1 + np.prod(shape), dtype=jnp.float32).reshape(shape)
    got, n = pad_rows(x, multiple)
    assert n == shape[0]
    assert got.shape[0] % multiple == 0 and got.shape[0] - n < multiple
    assert got.shape[1:] == shape[1:]
    np.testing.assert_array_equal(got[:n], x)
    assert (np.asarray(got[n:]) == 0).all()


@pytest.mark.parametrize("rows,k", [(4, 3), (2, 8)])
def test_empty_matches_is_the_merge_identity(rows, k):
    e = empty_matches(rows, k)
    assert e.values.shape == e.indices.shape == (rows, k)
    assert (np.asarray(e.values) == -np.inf).all()
    assert (np.asarray(e.indices) == -1).all() and int(e.counts.sum()) == 0
    rng = np.random.default_rng(rows)
    S = jnp.asarray(rng.random((rows, 12)), jnp.float32)
    m = extract_matches(S, 0.4, k, col_offset=5, exclude_self=False)
    for merged in (merge_matches(e, m), merge_matches(m, e)):
        np.testing.assert_array_equal(merged.values, m.values)
        np.testing.assert_array_equal(merged.indices, m.indices)
        np.testing.assert_array_equal(merged.counts, m.counts)


def test_merge_matches_disjoint_columns():
    S = jnp.asarray(np.random.default_rng(2).random((6, 40)), jnp.float32)
    full = extract_matches(S, 0.5, 8, exclude_self=False)
    a = extract_matches(S[:, :20], 0.5, 8, exclude_self=False)
    b = extract_matches(S[:, 20:], 0.5, 8, col_offset=20, exclude_self=False)
    merged = merge_matches(a, b)
    np.testing.assert_array_equal(merged.counts, full.counts)
    np.testing.assert_allclose(merged.values, full.values, rtol=1e-6)


def test_dedupe_candidates():
    vals = jnp.asarray([[1.0, 2.0, 1.0, 3.0]], jnp.float32)
    idx = jnp.asarray([[5, 7, 5, -1]], jnp.int32)
    v, i = dedupe_candidates(vals, idx)
    live = np.asarray(i[0])
    assert sorted(x for x in live if x >= 0) == [5, 7]
    assert (np.asarray(v[0])[live == 5] == 1.0).all()


def test_matches_from_candidates_threshold_and_self():
    vals = jnp.asarray([[0.9, 0.2, 0.7]], jnp.float32)
    idx = jnp.asarray([[0, 1, 2]], jnp.int32)
    m = matches_from_candidates(vals, idx, 0.5, 4, row_offset=0)
    # index 0 == row 0 → self-excluded; 0.2 below threshold
    assert int(m.counts[0]) == 1
    assert int(m.indices[0, 0]) == 2


def test_block_bounds_are_upper_bounds(corpus):
    b = 16
    maxw = block_maxweight_bounds(jnp.asarray(corpus), b)
    ub = np.asarray(block_upper_bounds(maxw, maxw))
    S = corpus @ corpus.T
    nb = corpus.shape[0] // b
    for i in range(nb):
        for j in range(nb):
            true_max = S[i * b:(i + 1) * b, j * b:(j + 1) * b].max()
            assert ub[i, j] >= true_max - 1e-5


def test_prune_mask_never_kills_matches(corpus):
    b = 16
    mask = np.asarray(
        block_prune_mask(jnp.asarray(corpus), jnp.asarray(corpus), T, b)
    )
    S = corpus @ corpus.T
    np.fill_diagonal(S, 0.0)
    nb = corpus.shape[0] // b
    for i in range(nb):
        for j in range(nb):
            if not mask[i, j]:
                blockmax = S[i * b:(i + 1) * b, j * b:(j + 1) * b].max()
                assert blockmax < T


def test_local_threshold_lemma1_form():
    assert float(local_threshold(0.8, 4)) == pytest.approx(0.2)


def test_prune_stats_counts():
    mask = jnp.asarray([[True, False], [True, True]])
    s = prune_stats(mask)
    assert int(s.live_blocks) == 3 and int(s.total_blocks) == 4


def test_apss_blocked_kernel_path(corpus):
    """Pallas-kernel-backed self-join == oracle (interpret mode)."""
    ref = apss_reference(jnp.asarray(corpus), T, K)
    got = apss_blocked(jnp.asarray(corpus), T, K, block_rows=128, use_kernel=True)
    assert match_set(got) == match_set(ref)
    np.testing.assert_array_equal(got.counts, ref.counts)
