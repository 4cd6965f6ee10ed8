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
    """Host pre-split constraints fail loudly: n % q raises. Any m splits
    (the dealt dimension split pads each shard to ⌈m/r⌉ local ids), and
    the join over an odd m stays exact."""
    mesh = make_mesh((4, 2), ("data", "model"))
    D = _dense_corpus(64, 99, 0.2, seed=6)
    got = apss_2d(from_dense(D), T, K, mesh, block_rows=16)
    _check(got, apss_reference(jnp.asarray(D), T, K))
    with pytest.raises(ValueError, match="multiple"):
        apss_2d(from_dense(_dense_corpus(66, 96, 0.2, seed=6)), T, K, mesh)


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
