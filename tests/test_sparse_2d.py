"""Sparse 2-D checkerboard (ISSUE 5 tentpole): the sparse horizontal ring
composed with posting-list-sharded vertical accumulation in ``apss_2d``.

The contract: on any mesh shape and density, ``apss_2d`` on a
:class:`SparseCorpus` returns exactly the dense-oracle matches (the same
exactness bar every other sparse path meets), the per-cell pruning bounds
stay sound under the dimension split (Lemma 1 + the cell-norm ≤ 1
argument, DESIGN.md §5), and the planner both enumerates and correctly
dispatches the new family.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core.apss import apss_reference, normalize_rows
from repro.core.distributed import apss, apss_2d, apss_horizontal
from repro.core.graph import match_set
from repro.core.pruning import checkerboard_live_mask
from repro.core.sparse import (
    deal_dims,
    dim_slices,
    from_dense,
    shard_dims,
    to_dense,
)
from repro.data.sparse import sparse_clustered_corpus
from test_sparse import one_long_row, quoted200, random_csr  # noqa: F401

T, K = 0.3, 16


def _dense_corpus(n, m, dens, seed=0):
    rng = np.random.default_rng(seed)
    D = np.abs(rng.standard_normal((n, m))).astype(np.float32)
    D *= rng.random((n, m)) < dens
    return np.asarray(normalize_rows(jnp.asarray(D)))


def _check(got, ref):
    assert match_set(got) == match_set(ref)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(ref.counts))


# -- exactness across densities × mesh shapes ---------------------------------


@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2), (2, 2)])
@pytest.mark.parametrize("dens", [1e-3, 1e-2, 0.1])
def test_sparse_2d_exact_vs_dense_oracle(dens, mesh_shape):
    """Every (density × checkerboard shape) cell matches the oracle for both
    accumulations — including the near-empty 1e-3 regime where most rows
    have a single component."""
    D = _dense_corpus(128, 1024, dens, seed=3)
    sp = from_dense(D)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    ref = apss_reference(jnp.asarray(D), T, K)
    for acc in ("allreduce", "compressed"):
        got = apss_2d(
            sp, T, K, mesh, accumulation=acc, block_rows=16,
            candidate_capacity=128,
        )
        _check(got, ref)


def test_sparse_2d_parity_with_sparse_ring(mesh8):
    """The composed checkerboard and the 1-D sparse ring are two routes to
    the same exact answer — parity pins the composition against the
    already-trusted sparse horizontal path."""
    sp = from_dense(_dense_corpus(128, 1024, 0.01, seed=4))
    ring = apss_horizontal(
        sp, T, K, mesh8, "data", schedule="ring", block_rows=16
    )
    mesh = make_mesh((4, 2), ("data", "model"))
    twod = apss_2d(
        sp, T, K, mesh, accumulation="compressed", block_rows=16,
        candidate_capacity=128,
    )
    _check(twod, ring)


def test_sparse_2d_overflow_reported():
    """Tiny candidate capacity must trip the overflow counter — capacity
    truncation is visible in the composed schedule too, never silent."""
    sp = from_dense(_dense_corpus(128, 96, 0.15, seed=5))
    mesh = make_mesh((4, 2), ("data", "model"))
    _, stats = apss_2d(
        sp, 0.05, K, mesh, accumulation="compressed", block_rows=16,
        candidate_capacity=4, return_stats=True,
    )
    assert int(stats.overflow_rows) > 0


def test_sparse_2d_divisibility_errors():
    """No shape constraint is left to fail: any m splits (the dealt
    dimension split pads each shard to ⌈m/r⌉ local ids), and any n too
    (rows are padded with empty rows to a multiple of q · block), so the
    joins over an odd m and over n = 66 on 4 row shards stay exact."""
    mesh = make_mesh((4, 2), ("data", "model"))
    D = _dense_corpus(64, 99, 0.2, seed=6)
    got = apss_2d(from_dense(D), T, K, mesh, block_rows=16)
    _check(got, apss_reference(jnp.asarray(D), T, K))
    D = _dense_corpus(66, 96, 0.2, seed=6)
    got = apss_2d(from_dense(D), T, K, mesh)
    assert got.counts.shape == (66,)
    _check(got, apss_reference(jnp.asarray(D), T, K))


@pytest.mark.parametrize("mesh_shape", [(2, 2), (4, 2)])
def test_sparse_2d_exact_when_blocks_take_both_routes(mesh_shape):
    """300 rows, not a multiple of q · block_rows, at a capacity that some
    (query block, ring step) tiles overflow on a chip of their column group
    and others do not: the overflowing tiles take the all-reduce of the
    whole partial tile, the rest the Lemma-1 candidates, and the join is
    the oracle's. Route counts cover every tile of every row shard."""
    D = _dense_corpus(300, 200, 0.08, seed=1)
    ref = apss_reference(jnp.asarray(D), T, K)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    q = mesh_shape[0]
    got, stats = apss_2d(
        from_dense(D), T, K, mesh, block_rows=32, candidate_capacity=12,
        return_stats=True,
    )
    _check(got, ref)
    np.testing.assert_allclose(
        np.sort(np.asarray(got.values), axis=1),
        np.sort(np.asarray(ref.values), axis=1), rtol=1e-6, atol=1e-6,
    )
    assert int(stats.blocks_exact) > 0 and int(stats.blocks_pruned) > 0
    assert int(stats.overflow_rows) > 0
    # The routes, counted on the host: a tile (row shard i, query block b,
    # ring step s) is exact where some column's partials of some query row
    # reach t/2 in more than 12 columns of the row shard it meets.
    n_loc = -(-300 // (q * 32)) * 32
    owner, _ = deal_dims(from_dense(D), 2)
    Dp = np.pad(D.astype(np.float64), ((0, q * n_loc - 300), (0, 0)))
    real = np.arange(q * n_loc) < 300
    exact = 0
    for j in range(2):
        cols = Dp[:, owner == j]
        over = (((cols @ cols.T) >= T / 2) & real[None, :]).reshape(
            q * n_loc, q, n_loc).sum(axis=2) > 12
        tiles = over.reshape(q, n_loc // 32, 32, q).any(axis=2)  # (i, b, src)
        exact = tiles if j == 0 else exact | tiles
    assert int(stats.blocks_exact) == int(exact.sum())
    assert int(stats.blocks_pruned) == exact.size - int(exact.sum())


def test_sparse_2d_spills_ride_the_ring(one_long_row):
    """One row of 200 nonzeros among 1,200 short ones spills past the cut
    width; with 601 rows a row shard, it and the row quoting it (about
    0.71) sit on different row shards, so each meets the other's spill
    only as the cell that traveled the ring."""
    from repro.core import distributed as dist

    sp, ref = one_long_row
    mesh = make_mesh((2, 2), ("data", "model"))
    assert dist._twod_rows(sp.n, 2, 601) == (601, 1202)
    _, _, (rows, _, vals), _, _ = dist._split_on_mesh(
        sp, 1202, mesh, "data", "model", "twod"
    )
    assert np.asarray(vals).any()  # the long row spilled
    got = apss_2d(sp, 0.5, K, mesh, block_rows=601)
    _check(got, ref)
    assert 601 in np.asarray(got.indices)[600].tolist()
    assert 600 in np.asarray(got.indices)[601].tolist()


def test_sparse_2d_padding_rows_never_match():
    """At a negative threshold every real pair matches; the empty rows that
    pad 130 rows to 4 row shards of 48 (one shard all padding) neither
    appear nor count, on the exact route and the pruned one alike."""
    D = _dense_corpus(130, 100, 0.1, seed=2)
    mesh = make_mesh((4, 2), ("data", "model"))
    for capacity in (16, 256):
        got = apss_2d(
            from_dense(D), -0.1, 8, mesh, block_rows=16,
            candidate_capacity=capacity,
        )
        np.testing.assert_array_equal(np.asarray(got.counts), 129)
        idx = np.asarray(got.indices)
        assert idx.min() >= 0 and idx.max() < 130


def _host_twod_split(sp, q, r, block_rows):
    """NumPy oracle of the 2-D split: ``shard_dims``' packing cut to the
    width ``_cut_width`` gives, rows padded to ``n_pad``, and each cell's
    entries past the width listed in row-major order with row ids local to
    its row shard, at ``[j, i · E:(i + 1) · E]`` of the spill arrays. The
    spill length ``E`` is sized here, from the cells as this oracle cuts
    them: the most entries past the width in any cell, rounded up to
    ``_SPILL_QUANTUM``."""
    from repro.core import distributed as dist

    bs, n_pad = dist._twod_rows(sp.n, q, block_rows)
    n_loc = n_pad // q
    idx_s, val_s, nnz_s, m_loc = shard_dims(sp, r)
    width, _ = dist._cut_width(nnz_s.T, q, n_pad)
    grow = ((0, 0), (0, n_pad - sp.n), (0, max(width - idx_s.shape[-1], 0)))
    idx_s, val_s = np.pad(idx_s, grow), np.pad(val_s, grow)
    nnz_s = np.pad(nnz_s, ((0, 0), (0, n_pad - sp.n)))
    slots = np.arange(idx_s.shape[-1])
    past = (slots >= width) & (slots < nnz_s[..., None])
    most = past.reshape(r, q, -1).sum(axis=2).max()
    E = dist._SPILL_QUANTUM * max(1, -(-int(most) // dist._SPILL_QUANTUM))
    spill = [np.zeros((r, q * E), t) for t in (np.int32, np.int32, np.float32)]
    for j in range(r):
        for i in range(q):
            rows, c = np.nonzero(past[j, i * n_loc:(i + 1) * n_loc])
            at = slice(i * E, i * E + rows.size)
            spill[0][j, at] = rows
            spill[1][j, at] = idx_s[j, i * n_loc + rows, c]
            spill[2][j, at] = val_s[j, i * n_loc + rows, c]
    cut = [a[..., :width] for a in (idx_s, val_s)]
    cells = nnz_s.reshape(r, q, n_loc).sum(axis=2).T.ravel()
    return n_pad, cut, spill, cells, m_loc


@pytest.mark.parametrize("corpus", ["quoted200", "one_long_row", "adversarial"])
def test_sparse_2d_split_on_the_mesh_equals_host_packing(corpus, request):
    """The 2-D split dealt, counted and packed on a (2, 2) mesh equals the
    host packing of ``shard_dims`` cut to the same width, bit for bit:
    cells, spill lists and each cell's nonzeros. ``one_long_row`` spills;
    ``adversarial`` has duplicate coordinates, empty rows and rows narrower
    than the width."""
    from repro.core import distributed as dist

    if corpus == "adversarial":
        sp = random_csr(5, 90, 37, 6, dup_prob=1.0)
    else:
        sp, _ = request.getfixturevalue(corpus)
    mesh = make_mesh((2, 2), ("data", "model"))
    n_pad, (want_idx, want_val), want_spill, want_cells, want_m_loc = (
        _host_twod_split(sp, 2, 2, 64)
    )
    idx_s, val_s, spill, cells, m_loc = dist._split_on_mesh(
        sp, n_pad, mesh, "data", "model", "twod"
    )
    assert m_loc == want_m_loc
    np.testing.assert_array_equal(cells, want_cells)
    np.testing.assert_array_equal(np.asarray(idx_s), want_idx)
    np.testing.assert_array_equal(np.asarray(val_s), want_val)
    for got, want in zip(spill, want_spill):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert idx_s.sharding.spec[:2] == ("model", "data")  # a cell a chip
    if corpus == "one_long_row":
        assert np.asarray(spill[2]).any()  # the long row spilled


def _long_rows_in_the_gap():
    """2,050 short rows over 300 dimensions, two of them (rows 0 and 600)
    holding every dimension. On a (4, 2) mesh with 128-row blocks a row
    shard is 640 rows, while equal shards of the 2,050 rows would be 513:
    both long rows fall in the first row shard, so its cells spill twice
    what a cut at 513 rows would size them for."""
    rng = np.random.default_rng(11)
    D = np.abs(rng.standard_normal((2050, 300))).astype(np.float32)
    D *= rng.random((2050, 300)) < 0.03
    D[[0, 600]] = np.abs(rng.standard_normal((2, 300))) + 0.05
    return np.asarray(normalize_rows(jnp.asarray(D)))


def test_sparse_2d_spill_is_sized_over_the_packed_row_shards():
    """The spill length is sized over the row shards the pack cuts
    (``n_pad / q`` rows), not over ``n / q``: with both long rows in one
    packed row shard, the split on the mesh keeps every entry (it equals an
    oracle that sizes the spill on its own), and the join is exact."""
    from repro.core import distributed as dist

    D = _long_rows_in_the_gap()
    sp = from_dense(D)
    mesh = make_mesh((4, 2), ("data", "model"))
    n_pad, (want_idx, want_val), want_spill, want_cells, _ = _host_twod_split(
        sp, 4, 2, 128
    )
    assert n_pad // 4 > -(-sp.n // 4)  # the packed shards are the longer
    idx_s, val_s, spill, cells, _ = dist._split_on_mesh(
        sp, n_pad, mesh, "data", "model", "twod"
    )
    assert want_spill[0].shape[-1] == 4 * 256  # two long rows' spill in one cell
    np.testing.assert_array_equal(cells, want_cells)
    np.testing.assert_array_equal(np.asarray(idx_s), want_idx)
    for got, want in zip(spill, want_spill):
        np.testing.assert_array_equal(np.asarray(got), want)
    got = apss(sp, T, K, mesh, distribution="2d", block_rows=128)
    _check(got, apss_reference(jnp.asarray(D), T, K))


def test_sparse_2d_compiles_once_a_shape_and_traces_its_split():
    """The post-split program is jitted: a second join of the same shapes
    adds no compiled program, with telemetry on or off. Under a tracer the
    ``twod/*`` spans carry the split's sizes and the route counts, and the
    telemetry record carries each cell's nonzeros."""
    from repro.core import distributed as dist
    from repro.obs.trace import Tracer

    D = _dense_corpus(300, 200, 0.08, seed=1)
    sp = from_dense(D)
    mesh = make_mesh((2, 2), ("data", "model"))

    def join():
        return apss(
            sp, T, K, mesh, distribution="2d", block_rows=32,
            candidate_capacity=16,
        )

    join()
    before = dist._2d_sparse_post_split._cache_size()
    join()
    assert dist._2d_sparse_post_split._cache_size() == before
    with Tracer() as tr:
        join()
    traced = dist._2d_sparse_post_split._cache_size()
    with Tracer() as tr:
        join()
    assert dist._2d_sparse_post_split._cache_size() == traced
    spans = {s.name: s for s in tr.walk()}
    shard, pad, dispatch = (spans[f"twod/{x}"] for x in ("shard", "pad", "dispatch"))
    assert (shard.attrs["q"], shard.attrs["r"], shard.attrs["cap_loc"]) == (2, 2, 32)
    assert set(shard.attrs) >= {"m_loc", "spilled", "nnz_max", "nnz_mean"}
    assert (pad.attrs["n"], pad.attrs["n_pad"]) == (300, 320)
    assert dispatch.attrs["accumulation"] == "compressed"
    assert dispatch.attrs["capacity"] == 16 and dispatch.attrs["block_rows"] == 32
    routes = dispatch.attrs["blocks_pruned"] + dispatch.attrs["blocks_exact"]
    assert routes == 2 * 2 * 160 // 32 and dispatch.attrs["overflow_rows"] >= 0
    (rec,) = [r for s in tr.walk() for r in s.records if r.variant == "2d/compressed"]
    assert len(rec.extra["shard_nnz"]) == 4 and sum(rec.extra["shard_nnz"]) == int(
        np.count_nonzero(D)
    )
    assert rec.extra["n_pad"] == 320 and rec.extra["cap_loc"] == 32
    assert rec.extra["blocks_exact"] == dispatch.attrs["blocks_exact"]
    assert len(rec.step_ticker.step_times()) == 2  # one tick set a ring step


# -- per-cell pruning soundness (Lemma 1 under the dimension split) -----------


def test_checkerboard_live_mask_sound_and_prunes():
    """The OR-union of per-cell masks at t/r keeps every tile containing a
    global match (Lemma 1: some slice sees partial ≥ t/r) while still
    pruning dead tiles on a clustered corpus — local pruning survives the
    composition. Per-cell minsize runs in its unit-norm form even though
    cell norms are < 1, which only over-bounds (cell-norm ≤ 1 argument)."""
    sp = sparse_clustered_corpus(128, 2048, 8.0, n_clusters=8, seed=7)
    t, bs, r = 0.4, 16, 4
    cells = dim_slices(sp, r)
    assert len(cells) == r and all(c.m == 2048 // r for c in cells)
    live = np.asarray(checkerboard_live_mask(cells, t, bs))
    Dn = np.asarray(to_dense(sp))
    S = Dn @ Dn.T
    np.fill_diagonal(S, 0.0)
    nb = 128 // bs
    has_match = S.reshape(nb, bs, nb, bs).max(axis=(1, 3)) >= t
    assert not (has_match & ~live).any()  # soundness: no match in a dead tile
    assert (~live).any()                  # and it actually prunes


def test_dim_slices_partition_is_lossless():
    sp = from_dense(_dense_corpus(32, 64, 0.3, seed=8))
    cells = dim_slices(sp, 4)
    owner, local = deal_dims(sp, 4)
    back = np.zeros((32, 64), np.float32)
    for d, c in enumerate(cells):
        mine = np.nonzero(owner == d)[0]
        back[:, mine] = np.asarray(to_dense(c))[:, local[mine]]
    np.testing.assert_allclose(back, np.asarray(to_dense(sp)), rtol=1e-6)


# -- planner integration ------------------------------------------------------


def test_planner_enumerates_and_prices_2d_sparse(mesh4x2):
    """`candidate_configs` emits the 2-D-sparse family (the last planner
    gate), every such config prices finite, and the sparse cell's modeled
    ring wire undercuts its dense twin's."""
    from repro.planner import default_profile, estimate_cost
    from repro.planner.plan import candidate_configs, summarize_corpus

    sp = from_dense(_dense_corpus(128, 1024, 0.01, seed=9))
    s = summarize_corpus(sp, T)
    cfgs = candidate_configs(s, mesh4x2, K, include_kernel=False)
    twod = {(c.sparse, c.accumulation): c for c in cfgs if c.kind == "2d"}
    assert (True, "compressed") in twod and (False, "compressed") in twod
    prof = default_profile()
    ests = {
        key: estimate_cost(c, s, dict(mesh4x2.shape), prof, K)
        for key, c in twod.items()
    }
    for e in ests.values():
        assert np.isfinite(e.total_s) and e.total_s > 0
    assert (
        ests[(True, "compressed")].wire_bytes
        < ests[(False, "compressed")].wire_bytes
    )


def test_distribution_auto_runs_sparse_on_2d_mesh(mesh4x2):
    """`distribution="auto"` prices the full (representation × distribution)
    matrix on a 2-axis mesh and whatever it picks stays exact."""
    from repro.planner import default_profile

    sp = from_dense(_dense_corpus(128, 1024, 0.01, seed=10))
    got = apss(
        sp, T, K, mesh4x2, distribution="auto",
        profile=default_profile(), include_kernel=False,
    )
    _check(got, apss_reference(to_dense(sp), T, K))


def test_sparse_2d_cell_cap_is_realized_split_width():
    """The traveling cell pair is exactly as wide as the realized per-cell
    max row count — the host pre-split's whole point (a traced-side split
    would pad every cell to the global cap)."""
    sp = from_dense(_dense_corpus(64, 96, 0.2, seed=11))
    idx_s, _, nnz_s, m_loc = shard_dims(sp, 2)
    assert m_loc == 48
    assert idx_s.shape[-1] == int(nnz_s.max())  # tight, not global-cap padded
    assert idx_s.shape[-1] <= sp.cap
