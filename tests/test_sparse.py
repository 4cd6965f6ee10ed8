"""Sparse APSS subsystem: representation roundtrips, inverted-index pruning
soundness, and sparse↔dense exactness of every scoring path (single-device
XLA + Pallas worklist kernel + all distributed sparse variants) across
densities, empty rows, duplicate coordinates, and non-tile-multiple shapes.

The contract under test (DESIGN.md §5): every sparse path produces the
identical ``match_set`` and exact ``counts`` as ``apss_reference`` on the
densified corpus.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apss import (
    apss_blocked,
    apss_reference,
    normalize_rows,
    similarity_topk,
)
from repro.core.graph import match_set
from repro.core.pruning import (
    sparse_block_prune_mask,
    sparse_block_support,
    sparse_candidate_mask,
)
from repro.core.sparse import (
    SparseCorpus,
    density,
    from_dense,
    normalize_sparse,
    deal_dims,
    pad_rows_sparse,
    shard_dims,
    sparse_similarity_topk,
    to_dense,
)
from repro.data.sparse import sparse_clustered_corpus, sparse_zipfian_corpus
from repro.kernels.apss_block import sparse as sparse_kernels
from repro.kernels.apss_block.sparse import apss_sparse_compacted

T, K = 0.3, 16


def _dense_corpus(n, m, dens, seed=0, empty_rows=()):
    rng = np.random.default_rng(seed)
    D = np.abs(rng.standard_normal((n, m))).astype(np.float32)
    D *= rng.random((n, m)) < dens
    for r in empty_rows:
        D[r] = 0
    return np.asarray(normalize_rows(jnp.asarray(D)))


def _check(got, ref):
    assert match_set(got) == match_set(ref)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(ref.counts))


# -- representation -----------------------------------------------------------


def test_from_dense_to_dense_roundtrip():
    D = _dense_corpus(40, 64, 0.2, seed=1, empty_rows=(3, 39))
    sp = from_dense(D)
    np.testing.assert_allclose(np.asarray(to_dense(sp)), D, rtol=1e-6)
    assert sp.shape == D.shape
    assert np.asarray(sp.nnz).sum() == (D != 0).sum()


def test_duplicate_coordinates_sum_in_to_dense():
    # COO convention: duplicate (row, dim) slots sum.
    sp = SparseCorpus(
        jnp.asarray([[2, 2, 5], [0, 0, 0]], jnp.int32),
        jnp.asarray([[1.0, 2.0, 4.0], [3.0, 0.0, 0.0]], jnp.float32),
        jnp.asarray([3, 1], jnp.int32),
        m=8,
    )
    d = np.asarray(to_dense(sp))
    assert d[0, 2] == 3.0 and d[0, 5] == 4.0 and d[1, 0] == 3.0


def test_generators_are_normalized_and_never_dense():
    for sp in (
        sparse_zipfian_corpus(50, 4096, 6, seed=2),
        sparse_clustered_corpus(50, 4096, 6, n_clusters=16, seed=2),
    ):
        # O(n · cap) memory: capacity tracks realized nnz, not m.
        assert sp.cap < 64 < sp.m
        nrm = np.linalg.norm(np.asarray(to_dense(sp)), axis=1)
        np.testing.assert_allclose(nrm, 1.0, rtol=1e-5)
        assert 0 < density(sp) < 0.02


def test_normalize_sparse_matches_dense_normalize():
    D = _dense_corpus(16, 32, 0.3, seed=4) * 5.0
    sp = normalize_sparse(from_dense(D))
    np.testing.assert_allclose(
        np.asarray(to_dense(sp)),
        np.asarray(normalize_rows(jnp.asarray(D))),
        rtol=1e-5,
    )


def test_shard_dims_partition_is_lossless():
    D = _dense_corpus(24, 48, 0.3, seed=5)
    sp = from_dense(D)
    idx_s, val_s, nnz_s, m_loc = shard_dims(sp, 4)
    assert m_loc == 12
    owner, local = deal_dims(sp, 4)
    back = np.zeros_like(D)
    for d in range(4):
        loc = SparseCorpus(
            jnp.asarray(idx_s[d]), jnp.asarray(val_s[d]),
            jnp.asarray(nnz_s[d]), m_loc,
        )
        mine = np.nonzero(owner == d)[0]
        back[:, mine] += np.asarray(to_dense(loc))[:, local[mine]]
    np.testing.assert_allclose(back, D, rtol=1e-6)


@pytest.mark.parametrize("m", [1000, 1001, 1003])
def test_deal_dims_covers_once_and_balances_zipf(m):
    """Every dimension lies on exactly one shard at one local id below
    ⌈m/p⌉, and dealing by posting-list length spreads a Zipf corpus's
    nonzeros over the shards within 5 %, where contiguous ranges would
    put most of them on the shard holding the head."""
    p = 4
    sp = sparse_zipfian_corpus(400, m, 40, seed=3)
    owner, local = deal_dims(sp, p)
    m_loc = -(-m // p)
    pairs = owner * m_loc + local
    assert owner.min() >= 0 and owner.max() < p and local.max() < m_loc
    assert np.unique(pairs).size == m  # one (shard, local id) per dimension
    idx_s, val_s, nnz_s, got_m_loc = shard_dims(sp, p)
    assert got_m_loc == m_loc
    per_shard = nnz_s.sum(axis=1)
    assert per_shard.sum() == int(np.asarray(sp.nnz).sum())
    assert per_shard.max() / per_shard.mean() <= 1.05
    valid = np.arange(idx_s.shape[-1]) < nnz_s[..., None]
    assert idx_s[valid].max() < m_loc


# -- inverted-index candidate generation + sparse bounds ----------------------


def test_sparse_candidate_mask_is_support_intersection():
    sp = sparse_clustered_corpus(64, 256, 6, n_clusters=8, seed=3)
    spp, _ = pad_rows_sparse(sp, 8)
    sup = sparse_block_support(spp, 8)
    cand = np.asarray(sparse_candidate_mask(sup, sup))
    want = (np.asarray(sup).astype(np.int32) @ np.asarray(sup).T) > 0
    np.testing.assert_array_equal(cand, want)
    # 8 disjoint dimension bands over 8 row blocks ⇒ only diagonal tiles.
    assert cand.sum() < cand.size


def test_sparse_prune_mask_sound_and_matches_dense_bound():
    D = _dense_corpus(64, 96, 0.15, seed=6)
    sp = from_dense(D)
    b = 8
    mask = np.asarray(sparse_block_prune_mask(sp, sp, T, b))
    S = D @ D.T
    for i in range(64 // b):
        for j in range(64 // b):
            if not mask[i, j]:
                blk = S[i * b:(i + 1) * b, j * b:(j + 1) * b].copy()
                if i == j:
                    np.fill_diagonal(blk, 0.0)
                assert blk.max() < T  # pruned ⇒ provably matchless


# -- single-device exactness --------------------------------------------------


@pytest.mark.parametrize("dens", [0.001, 0.01, 0.1])
def test_blocked_sparse_exact_across_densities(dens):
    sp = sparse_zipfian_corpus(96, 2048, max(2, dens * 2048), seed=7)
    ref = apss_reference(to_dense(sp), T, K)
    _check(sparse_similarity_topk(sp, sp, T, K, block_rows=32, exclude_self=True), ref)
    _check(apss_blocked(sp, T, K, block_rows=32), ref)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sparse_compacted_exact(use_kernel):
    sp = sparse_clustered_corpus(96, 512, 8, n_clusters=8, seed=8)
    ref = apss_reference(to_dense(sp), 0.4, K)
    got = apss_sparse_compacted(
        sp, 0.4, K, block_m=16, lane_pad=8, use_kernel=use_kernel
    )
    _check(got, ref)


@pytest.mark.parametrize("n", [33, 96, 100])  # non-tile-multiple shapes
def test_sparse_compacted_ragged_shapes(n):
    D = _dense_corpus(n, 80, 0.15, seed=n, empty_rows=(0, n - 1))
    sp = from_dense(D)
    ref = apss_reference(jnp.asarray(D), T, K)
    _check(apss_sparse_compacted(sp, T, K, block_m=16, lane_pad=8), ref)
    _check(sparse_similarity_topk(sp, sp, T, K, block_rows=16, exclude_self=True), ref)


# -- support gather: dimension→slot table against the binary search ----------


def _lookup_case(name):
    """``bdims (nb, S)`` (sorted supports padded with the sentinel ``m``) and
    one CSR block ``(idx, val)`` for an equivalence case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    m, nb, bn, cap, S = 600, 3, 16, 12, 128
    sizes = [100, 60, 128]
    if name == "empty_support":
        sizes[1] = 0
    if name == "S_384":
        S, sizes = 384, [300, 130, 384]
    bdims = np.full((nb, S), m, np.int32)
    for b, k in enumerate(sizes):
        bdims[b, :k] = np.sort(rng.choice(m, k, replace=False))
    # draw from every block's support plus some dims outside all of them
    pool = np.unique(bdims[bdims < m])
    outside = np.setdiff1d(np.arange(m), pool)
    idx = rng.choice(pool, (bn, cap))
    val = rng.random((bn, cap)).astype(np.float32) + 0.05
    nnz = rng.integers(0, cap + 1, bn)
    if name == "duplicates":
        idx[:, 1] = idx[:, 0]
        idx[:, 2] = idx[:, 0]
    if name == "outside_support":
        miss = rng.random((bn, cap)) < 0.4
        idx = np.where(miss, rng.choice(outside, (bn, cap)), idx)
    if name == "csr_padding":
        nnz[::3] = 0  # empty rows: all padding
    else:
        nnz[:] = cap
    valid = np.arange(cap)[None, :] < nnz[:, None]
    idx, val = np.where(valid, idx, 0), np.where(valid, val, 0.0)
    if name == "sentinel":
        # every block pads with the sentinel m (the table's last column
        # holds a padded slot), and m - 1, the last real dimension, is read
        idx[:, -1] = m - 1
        bdims[:, -1] = m
    return m, bdims, idx.astype(np.int32), val.astype(np.float32)


def _search_gather(bd, idx, val):
    """NumPy oracle: the binary-search gather the slot table replaced.
    Each index is searched in the sorted support ``bd (S,)``; a miss adds
    0.0 to its clamped slot."""
    S = bd.shape[0]
    pos = np.minimum(np.searchsorted(bd, idx), S - 1)
    contrib = np.where(bd[pos] == idx, val, 0.0).astype(np.float32)
    rows = np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape)
    out = np.zeros((idx.shape[0], S), np.float32)
    np.add.at(out, (rows, pos), contrib)
    return out


@pytest.mark.parametrize(
    "case",
    ["duplicates", "outside_support", "csr_padding", "sentinel",
     "empty_support", "S_384"],
)
def test_slot_table_gather_equals_binary_search_gather(case):
    m, bdims, idx, val = _lookup_case(case)
    nb, S = bdims.shape
    slot = np.asarray(sparse_kernels._slot_table(jnp.asarray(bdims), m))
    assert slot.shape == (nb, m + 1)
    for b in range(nb):
        real = bdims[b][bdims[b] < m]
        np.testing.assert_array_equal(slot[b, real], np.arange(len(real)))
        assert (np.delete(slot[b, :m], real) == S).all()
        table = sparse_kernels._gather_block(
            jnp.asarray(slot[b]), jnp.asarray(idx), jnp.asarray(val), S
        )
        assert table.shape == (idx.shape[0], S)
        search = _search_gather(bdims[b], idx, val)
        assert np.array_equal(np.asarray(table), search)
        if len(real):  # the case gathers something onto every non-empty block
            assert search.any()


# -- support gather: the device build of bdims / bx against the host loop ----


def _host_support_gather(sp, block_m, pad_to):
    """NumPy oracle: the host loop the device build replaced. Per row block,
    ``np.unique`` of the valid indices, then ``np.add.at`` of each row onto
    them (a miss adds 0.0 to its clamped slot)."""
    idx, val, nnz = (np.asarray(a) for a in (sp.indices, sp.values, sp.nnz))
    n, cap = idx.shape
    nb = n // block_m
    valid = np.arange(cap)[None, :] < nnz[:, None]
    blocks = [slice(b * block_m, (b + 1) * block_m) for b in range(nb)]
    uniq = [np.unique(idx[sl][valid[sl]]) for sl in blocks]
    S = max(1, max((len(u) for u in uniq), default=1))
    S = -(-S // pad_to) * pad_to
    bdims = np.full((nb, S), sp.m, np.int32)
    bx = np.zeros((nb, block_m, S), np.float32)
    rows = np.arange(block_m)[:, None]
    for b, (u, sl) in enumerate(zip(uniq, blocks)):
        if len(u) == 0:
            continue
        bdims[b, : len(u)] = u
        pos = np.minimum(np.searchsorted(u, idx[sl]), len(u) - 1)
        hit = (u[pos] == idx[sl]) & valid[sl]
        np.add.at(bx[b], (rows, pos), np.where(hit, val[sl], 0.0))
    return bdims, bx


def _support_case(name):
    """``(corpus padded to the block, block_m, pad_to, duplicates)``."""
    block_m, pad_to = {
        "b8_p8": (8, 8), "b16_p128_duplicates": (16, 128),
        "b128_p128_row_padding": (128, 128), "b16_p8_empty_block": (16, 8),
        "b8_p8_whole_vocabulary": (8, 8), "b16_p8_stale_padding": (16, 8),
        "b8_p128_all_empty": (8, 128),
    }[name]
    dups = name.endswith("duplicates")
    sp = random_csr(
        sum(map(ord, name)), 300 if "row_padding" in name else 64, 200, 12,
        dup_prob=0.0,
    )
    idx, val, nnz = (np.array(a) for a in (sp.indices, sp.values, sp.nnz))
    m = sp.m
    if dups:  # three entries of one coordinate in every row, which sum
        idx[:, 1] = idx[:, 2] = idx[:, 0]
    if name == "b16_p8_empty_block":
        nnz[16:32] = 0
        idx[16:32], val[16:32] = 0, 0.0
    if name == "b8_p8_whole_vocabulary":
        # block 0's 8 rows × 10 entries name every one of m = 40 dims
        m = 40
        idx = idx % m
        idx[:8, :10] = np.arange(80).reshape(8, 10) % m
        nnz[:8] = np.maximum(nnz[:8], 10)
    if name == "b16_p8_stale_padding":
        # slots past nnz hold stale dims and values: only nnz decides
        rng = np.random.default_rng(1)
        pad = np.arange(idx.shape[1])[None, :] >= nnz[:, None]
        idx = np.where(pad, rng.integers(0, m, idx.shape), idx)
        val = np.where(pad, 1.0, val)
    if name == "b8_p128_all_empty":
        nnz[:] = 0
        idx[:], val[:] = 0, 0.0
    sp = SparseCorpus(
        jnp.asarray(idx, jnp.int32), jnp.asarray(val, jnp.float32),
        jnp.asarray(nnz, jnp.int32), m,
    )
    return pad_rows_sparse(sp, block_m)[0], block_m, pad_to, dups


@pytest.mark.parametrize(
    "case",
    ["b8_p8", "b16_p128_duplicates", "b128_p128_row_padding",
     "b16_p8_empty_block", "b8_p8_whole_vocabulary", "b16_p8_stale_padding",
     "b8_p128_all_empty"],
)
def test_device_support_gather_equals_host_loop(case):
    spp, block_m, pad_to, dups = _support_case(case)
    bdims, bx = sparse_kernels.block_support_gather(spp, block_m, pad_to=pad_to)
    assert isinstance(bdims, jax.Array) and isinstance(bx, jax.Array)
    want_dims, want_x = _host_support_gather(spp, block_m, pad_to)
    assert bdims.shape == want_dims.shape and bx.shape == want_x.shape
    np.testing.assert_array_equal(np.asarray(bdims), want_dims)
    if dups:  # the sums may round in another order: one ulp
        np.testing.assert_array_max_ulp(np.asarray(bx), want_x, maxulp=1)
    else:
        np.testing.assert_array_equal(np.asarray(bx), want_x)
    if case == "b8_p8_whole_vocabulary":
        np.testing.assert_array_equal(np.asarray(bdims)[0], np.arange(40))
    if case == "b16_p8_empty_block":
        assert (np.asarray(bdims)[1] == spp.m).all() and not np.asarray(bx)[1].any()


def test_benchmark_spy_reads_block_rows_from_the_support_gather():
    # bench/kinds/selfjoin.py counts the kernel's work with the block rows
    # it keeps from this call; routing the join around the name loses them
    from bench.spy import CallSpy, support_block

    sp = sparse_clustered_corpus(96, 512, 8, n_clusters=8, seed=8)
    with CallSpy(
        sparse_kernels, "block_support_gather", keep=support_block
    ) as spy:
        apss_sparse_compacted(sp, 0.4, K, block_m=16, lane_pad=8, use_kernel=False)
    assert spy.kept == [16]


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sparse_compacted_slot_table_exact(use_kernel):
    sp = random_csr(5, 70, 300, 10, dup_prob=1.0)
    ref = apss_reference(to_dense(sp), 0.2, K)
    got = apss_sparse_compacted(
        sp, 0.2, K, block_m=16, lane_pad=128, use_kernel=use_kernel
    )
    _check(got, ref)


def test_sparse_compacted_all_pruned_returns_empty():
    sp = from_dense(_dense_corpus(32, 64, 0.1, seed=9))
    got = apss_sparse_compacted(sp, 1.5, K, block_m=16, lane_pad=8)
    assert int(np.asarray(got.counts).sum()) == 0
    assert (np.asarray(got.indices) == -1).all()


def test_sparse_rectangular_join_with_offsets():
    Q = from_dense(_dense_corpus(24, 64, 0.2, seed=10))
    C = from_dense(_dense_corpus(40, 64, 0.2, seed=11))
    S = np.asarray(to_dense(Q)) @ np.asarray(to_dense(C)).T
    got = similarity_topk(Q, C, T, K, block_rows=16, col_offset=100)
    counts = (S >= T).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(got.counts), counts)
    idx = np.asarray(got.indices)
    for r in range(24):
        want = {int(c) + 100 for c in np.nonzero(S[r] >= T)[0]}
        assert set(idx[r][idx[r] >= 0]) == want


def test_blocked_sparse_prune_stats():
    sp = sparse_clustered_corpus(64, 512, 8, n_clusters=8, seed=12)
    m, stats = apss_blocked(sp, 0.4, K, block_rows=16, with_prune_stats=True)
    _check(m, apss_reference(to_dense(sp), 0.4, K))
    assert 0 < int(stats.live_blocks) <= int(stats.total_blocks)


def test_sparse_use_kernel_rejects_rectangular():
    Q = from_dense(_dense_corpus(16, 32, 0.3, seed=13))
    with pytest.raises(ValueError):
        similarity_topk(Q, Q, T, K, use_kernel=True)


# -- distributed sparse variants ----------------------------------------------


@pytest.fixture(scope="module")
def sparse128():
    D = _dense_corpus(128, 96, 0.15, seed=14, empty_rows=(17,))
    return from_dense(D), apss_reference(jnp.asarray(D), T, K)


@pytest.mark.parametrize("schedule", ["allgather", "ring", "halfring"])
def test_sparse_horizontal_exact(mesh8, sparse128, schedule):
    from repro.core.distributed import apss_horizontal

    sp, ref = sparse128
    got = apss_horizontal(sp, T, K, mesh8, "data", schedule=schedule, block_rows=16)
    _check(got, ref)


def test_sparse_halfring_ring_parity(mesh8, sparse128):
    """The CSR triple travels the halfring caravan exactly like dense
    blocks: match-for-match parity with the sparse ring (ROADMAP item)."""
    from repro.core.distributed import apss_horizontal

    sp, _ = sparse128
    ring = apss_horizontal(sp, T, K, mesh8, "data", schedule="ring", block_rows=16)
    half = apss_horizontal(
        sp, T, K, mesh8, "data", schedule="halfring", block_rows=16
    )
    assert match_set(half) == match_set(ring)
    np.testing.assert_array_equal(
        np.asarray(half.counts), np.asarray(ring.counts)
    )
    np.testing.assert_allclose(
        np.sort(np.asarray(half.values), axis=-1),
        np.sort(np.asarray(ring.values), axis=-1),
        atol=1e-6,
    )


def test_sparse_halfring_odd_device_count(sparse128):
    """Odd p exercises the final-offset backward orientation (the even-p
    schedule skips it)."""
    import jax

    from repro.core.distributed import apss_horizontal
    from repro.core.sparse import pad_rows_sparse

    sp, ref = sparse128
    devs = jax.devices()[:5]
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    spp, n = pad_rows_sparse(sp, 5)  # 128 → 130 rows over 5 devices
    got = apss_horizontal(
        spp, T, K, mesh, "data", schedule="halfring", block_rows=13
    )
    got = jax.tree.map(lambda x: x[:n], got)
    _check(got, ref)


@pytest.mark.parametrize(
    "accumulation", ["allreduce", "scatter", "compressed", "recursive"]
)
def test_sparse_vertical_exact(mesh8_model, sparse128, accumulation):
    from repro.core.distributed import apss_vertical

    sp, ref = sparse128
    got = apss_vertical(
        sp, T, K, mesh8_model, "model", accumulation=accumulation, block_rows=16
    )
    _check(got, ref)


def test_sparse_2d_exact(mesh4x2, sparse128):
    """The last cell of the variant matrix: sparse ring ∘ posting-list-
    sharded accumulation (full coverage in tests/test_sparse_2d.py)."""
    from repro.core.distributed import apss_2d

    sp, ref = sparse128
    got = apss_2d(
        sp, T, K, mesh4x2, accumulation="compressed", block_rows=16,
        candidate_capacity=128,
    )
    _check(got, ref)


def test_sparse_hierarchical_exact(sparse128):
    """ROADMAP item closed: the CSR triple rides the nested pod ring."""
    from repro.compat import make_mesh
    from repro.core.distributed import apss_horizontal_hierarchical

    sp, ref = sparse128
    mesh = make_mesh((2, 4), ("pod", "data"))
    got = apss_horizontal_hierarchical(
        sp, T, K, mesh, ("pod", "data"), block_rows=16
    )
    _check(got, ref)


def test_sparse_hierarchical_3level_exact(sparse128):
    from repro.compat import make_mesh
    from repro.core.distributed import apss_horizontal_hierarchical

    sp, ref = sparse128
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    got = apss_horizontal_hierarchical(
        sp, T, K, mesh, ("pod", "data", "model"), block_rows=16
    )
    _check(got, ref)


def test_sparse_hierarchical_ring_parity(mesh8, sparse128):
    """The nested pod ring visits the same (local, visiting) block pairs as
    the flat sparse ring, just in hierarchical hop order: match-for-match
    parity, row-aligned (both shard rows in flat row-major rank order)."""
    from repro.compat import make_mesh
    from repro.core.distributed import apss_horizontal, apss_horizontal_hierarchical

    sp, _ = sparse128
    ring = apss_horizontal(sp, T, K, mesh8, "data", schedule="ring", block_rows=16)
    mesh = make_mesh((2, 4), ("pod", "data"))
    hier = apss_horizontal_hierarchical(
        sp, T, K, mesh, ("pod", "data"), block_rows=16
    )
    assert match_set(hier) == match_set(ring)
    np.testing.assert_array_equal(
        np.asarray(hier.counts), np.asarray(ring.counts)
    )
    np.testing.assert_allclose(
        np.sort(np.asarray(hier.values), axis=-1),
        np.sort(np.asarray(ring.values), axis=-1),
        atol=1e-6,
    )


# -- adversarial CSR structure (shared with tests/test_sparse_properties.py) --


def random_csr(seed, n, m, cap, *, dup_prob=0.3, empty_prob=0.2):
    """Raw CSR with adversarial structure: duplicate coordinates (which by
    convention sum) and empty rows, not necessarily normalized."""
    rng = np.random.default_rng(seed)
    nnz = rng.integers(0, cap + 1, size=n).astype(np.int32)
    nnz[rng.random(n) < empty_prob] = 0
    idx = rng.integers(0, m, size=(n, cap)).astype(np.int32)
    if rng.random() < dup_prob and cap > 1:
        idx[:, 1] = idx[:, 0]  # force duplicates in every non-trivial row
    val = (rng.random((n, cap)).astype(np.float32) * 0.8).astype(np.float32)
    mask = np.arange(cap)[None, :] < nnz[:, None]
    return SparseCorpus(
        jnp.asarray(np.where(mask, idx, 0)),
        jnp.asarray(np.where(mask, val, 0.0)),
        jnp.asarray(nnz),
        m,
    )


def test_duplicate_concentration_is_not_pruned():
    """Regression: per-slot maxweight under-bounds duplicates. Row 0 stores
    dim 3 as two 0.5 slots (effective weight 1.0); a per-slot max of 0.5
    would prune the cross-block tile at t=0.8 and silently drop the match."""
    idx = np.zeros((32, 2), np.int32)
    val = np.zeros((32, 2), np.float32)
    idx[0] = [3, 3]; val[0] = [0.5, 0.5]   # block 0: effective (3, 1.0)
    idx[16] = [3, 0]; val[16] = [1.0, 0.0]  # block 1
    sp = SparseCorpus(
        jnp.asarray(idx), jnp.asarray(val),
        jnp.asarray([2] + [0] * 15 + [1] + [0] * 15, dtype=jnp.int32), m=8,
    )
    t = 0.8
    mask = np.asarray(sparse_block_prune_mask(sp, sp, t, 16))
    assert mask[0, 1] and mask[1, 0]  # the tile with the true match is live
    ref = apss_reference(to_dense(sp), t, 4)
    assert int(np.asarray(ref.counts).sum()) == 2
    _check(apss_sparse_compacted(sp, t, 4, block_m=16, lane_pad=8), ref)
    _check(
        apss_sparse_compacted(sp, t, 4, block_m=16, lane_pad=8, use_kernel=True),
        ref,
    )


def test_negative_threshold_keeps_zero_similarity_pairs():
    """Regression: at t ≤ 0 zero-similarity pairs (disjoint support) match;
    an explicit support-intersection conjunct in the prune mask would
    unsoundly drop them."""
    D = np.zeros((32, 16), np.float32)
    D[:16, 0] = 1.0   # block 0 uses dim 0 only
    D[16:, 8] = 1.0   # block 1 uses dim 8 only — zero sim across blocks
    sp = from_dense(D)
    t = -0.5
    mask = np.asarray(sparse_block_prune_mask(sp, sp, t, 16))
    assert mask.all()
    ref = apss_reference(jnp.asarray(D), t, 32)
    _check(apss_sparse_compacted(sp, t, 32, block_m=16, lane_pad=8), ref)
    _check(sparse_similarity_topk(sp, sp, t, 32, block_rows=16, exclude_self=True), ref)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_adversarial_csr_join_equals_dense_reference(seed):
    """Non-hypothesis twin of the property test: duplicates + empty rows +
    ragged n, fixed seeds (runs even where hypothesis is absent)."""
    sp = random_csr(seed, 20 + 7 * seed, 40, 6)
    ref = apss_reference(to_dense(sp), 0.3, 32)
    _check(
        sparse_similarity_topk(sp, sp, 0.3, 32, block_rows=16, exclude_self=True),
        ref,
    )
    _check(apss_sparse_compacted(sp, 0.3, 32, block_m=16, lane_pad=8), ref)


# -- the sparse vertical path at awkward shapes -------------------------------


def _quoted_zipf(n, m, avg_nnz, seed):
    """A Zipf corpus in which a quarter of the rows quote a share of a
    random parent's entries, so that the join has matches; unit rows."""
    D = np.array(to_dense(sparse_zipfian_corpus(n, m, avg_nnz, seed=seed)))
    rng = np.random.default_rng(seed)
    for r in rng.choice(n, n // 4, replace=False):
        parent = (r + rng.integers(1, n)) % n
        cols = np.nonzero(D[parent])[0]
        quoted = max(1, int(rng.uniform(0.2, 0.8) * cols.size))
        take = rng.choice(cols, quoted, replace=False)
        D[r, take] = D[parent, take]
    return D / np.linalg.norm(D, axis=1, keepdims=True)


TQ = 0.5  # a threshold at which no row of quoted200 has more than K matches


@pytest.fixture(scope="module")
def quoted200():
    """200 rows (not a multiple of the 64-row block), m = 1001 (odd)."""
    D = _quoted_zipf(200, 1001, 10, seed=21)
    return from_dense(D), apss_reference(jnp.asarray(D), TQ, K)


@pytest.mark.parametrize("capacity", [256, 2])
@pytest.mark.parametrize(
    "accumulation", ["compressed", "recursive", "scatter", "allreduce"]
)
def test_sparse_vertical_matches_oracle_at_awkward_shapes(
    quoted200, accumulation, capacity
):
    """``apss(SparseCorpus, distribution="vertical")`` on 4 devices agrees
    with the dense oracle: match sets, ids and counts exactly, values to
    float32 rounding. A capacity of 256 holds every column of the padded
    join, so the pruned accumulations prune every block; with 2 the Lemma-1
    candidates overflow and they score those blocks exactly instead."""
    import jax

    from repro.core.distributed import apss

    sp, ref = quoted200
    assert int(np.asarray(ref.counts).max()) <= K  # no row truncated at k
    assert (np.asarray(ref.counts) > 0).mean() > 0.2
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("model",))
    got, stats = apss(
        sp, TQ, K, mesh, distribution="vertical", accumulation=accumulation,
        block_rows=64, candidate_capacity=capacity, return_stats=True,
    )
    _check(got, ref)
    np.testing.assert_array_equal(
        np.sort(np.asarray(got.indices), axis=1),
        np.sort(np.asarray(ref.indices), axis=1),
    )
    np.testing.assert_allclose(
        np.sort(np.asarray(got.values), axis=1),
        np.sort(np.asarray(ref.values), axis=1),
        rtol=1e-6, atol=1e-6,
    )
    blocks = 256 // 64
    assert int(stats.blocks_pruned) + int(stats.blocks_exact) == blocks
    if accumulation in ("scatter", "allreduce"):
        assert int(stats.blocks_exact) == blocks
    elif capacity == 2:
        assert int(stats.overflow_rows) > 0 and int(stats.blocks_exact) > 0
    else:
        assert int(stats.overflow_rows) == 0 and int(stats.blocks_pruned) == blocks


def test_sparse_vertical_padding_rows_never_match(quoted200):
    """At a negative threshold every real pair matches; the 56 empty rows
    that pad 200 rows to the block multiple must neither appear nor count."""
    import jax

    from repro.core.distributed import apss

    sp, _ = quoted200
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("model",))
    got = apss(sp, -0.5, 8, mesh, distribution="vertical", block_rows=64)
    assert got.counts.shape == (200,)
    np.testing.assert_array_equal(np.asarray(got.counts), 199)
    idx = np.asarray(got.indices)
    assert idx.min() >= 0 and idx.max() < 200


@pytest.fixture(scope="module")
def one_long_row():
    """1,200 short Zipf rows and one of 200 nonzeros: the shards' width is
    set by the short rows, and the long row's excess entries spill."""
    D = np.array(to_dense(sparse_zipfian_corpus(1201, 2003, 8, seed=23)))
    rng = np.random.default_rng(23)
    D[600] = 0.0
    D[600, rng.choice(2003, 200, replace=False)] = rng.random(200) + 0.05
    # row 601 quotes the long row's last 100 dimensions, which hold its
    # spilled entries: a match of about 0.71 that the spill alone carries
    D[601] = 0.0
    D[601, np.nonzero(D[600])[0][-100:]] = D[600, np.nonzero(D[600])[0][-100:]]
    D /= np.linalg.norm(D, axis=1, keepdims=True)
    return from_dense(D), apss_reference(jnp.asarray(D), TQ, K)


@pytest.mark.parametrize("accumulation", ["compressed", "allreduce"])
def test_sparse_vertical_spills_the_longest_row_exactly(one_long_row, accumulation):
    """The partial tile over the cut rows plus the spill list equals the
    oracle's scores, the long row's own matches included."""
    import jax

    from repro.core import distributed as dist

    sp, ref = one_long_row
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("model",))
    idx_s, _, (rows, _, vals), _, _ = dist._vertical_sparse_split(
        sp, 256, mesh, "model"
    )
    assert idx_s.shape[-1] == 32  # the width the short rows need
    rows, vals = np.asarray(rows), np.asarray(vals)
    assert set(rows[vals != 0].tolist()) == {600}  # only the long row spills
    got = dist.apss(
        sp, TQ, K, mesh, distribution="vertical", accumulation=accumulation,
        block_rows=256,
    )
    _check(got, ref)
    assert int(np.asarray(ref.counts)[600]) > 0  # the long row has a match
    assert 601 in np.asarray(got.indices)[600].tolist()


def _host_vertical_split(sp, p, block_rows):
    """NumPy oracle of the sparse vertical split: ``shard_dims``' packing
    cut to the width ``_cut_width`` gives, the entries past it listed per
    shard in row-major order, rows padded to the block."""
    from repro.core import distributed as dist

    idx_s, val_s, nnz_s, m_loc = shard_dims(sp, p)
    width, E = dist._cut_width(nnz_s.T)
    grow = ((0, 0), (0, 0), (0, max(width - idx_s.shape[-1], 0)))
    idx_s, val_s = np.pad(idx_s, grow), np.pad(val_s, grow)
    slots = np.arange(idx_s.shape[-1])
    past = (slots >= width) & (slots < nnz_s[..., None])
    spill = [np.zeros((p, E), t) for t in (np.int32, np.int32, np.float32)]
    for d in range(p):
        r, c = np.nonzero(past[d])
        spill[0][d, : r.size] = r
        spill[1][d, : r.size] = idx_s[d, r, c]
        spill[2][d, : r.size] = val_s[d, r, c]
    rows = ((0, 0), (0, (-sp.n) % block_rows), (0, 0))
    cut = [np.pad(a[..., :width], rows) for a in (idx_s, val_s)]
    return cut, spill, nnz_s.sum(axis=1), m_loc


@pytest.mark.parametrize("corpus", ["quoted200", "one_long_row", "adversarial"])
def test_sparse_vertical_split_on_the_mesh_equals_host_packing(corpus, request):
    """The split dealt, counted and packed on the devices equals the host
    packing cut to the same width, bit for bit: stacks, spill lists, shard
    counts. ``adversarial`` has duplicate coordinates, empty rows and rows
    narrower than the width, which the pack widens."""
    import jax

    from repro.core import distributed as dist

    if corpus == "adversarial":
        sp = random_csr(5, 90, 37, 6, dup_prob=1.0)
    else:
        sp, _ = request.getfixturevalue(corpus)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("model",))
    idx_s, val_s, spill, shard_nnz, m_loc = dist._vertical_sparse_split(
        sp, 64, mesh, "model"
    )
    (want_idx, want_val), want_spill, want_nnz, want_m_loc = (
        _host_vertical_split(sp, 4, 64)
    )
    assert m_loc == want_m_loc and idx_s.shape[1] % 64 == 0
    np.testing.assert_array_equal(shard_nnz, want_nnz)
    np.testing.assert_array_equal(np.asarray(idx_s), want_idx)
    np.testing.assert_array_equal(np.asarray(val_s), want_val)
    for got, want in zip(spill, want_spill):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert idx_s.sharding.spec[0] == "model"  # each chip holds its own shard
