"""Candidate pruning bounds, lifted from element- to block-granularity.

The sequential optimizations of Bayardo et al. (partial indexing / remscore /
minsize) all exploit per-dimension ``maxweight`` upper bounds to skip work.
Element-granular conditionals are poison for a systolic array, so we evaluate
the same bounds at *tile* granularity: a cheap summary matmul yields a
``(row_blocks × col_blocks)`` boolean mask of provably-below-threshold block
pairs, which the Pallas kernel skips with ``@pl.when`` (and which the roofline
accounting credits as saved FLOPs).

All bounds are conservative: a pruned block pair can contain **no** match, so
pruned execution remains exact (asserted by the property tests).

Local pruning (paper Lemma 1): if ``sim(x, y) ≥ t`` then at least one of the
``p`` dimension-shards sees a partial score ``≥ t/p``. :func:`local_threshold`
is that bound; the vertical distributed algorithm uses it to compact partial
scores before accumulation.

For CSR corpora (``core.sparse``) the same bounds are computed straight from
the sparse layout (:func:`sparse_block_prune_mask`): block maxima by
scatter-max, per-row sizes from the stored ``nnz`` (exact, not a densified
recount), plus the inverted-index candidacy test — tiles whose blocks share
no dimension support are never candidates at all (the paper's partial
indexing, DESIGN.md §5).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.matches import SCORE_PRECISION


class BlockStats(NamedTuple):
    """Per-row-block pruning summaries — the *index-build* half of pruning.

    Everything the maxweight/minsize/inverted-index bounds need to know
    about one side of a join, separated from the scoring-time mask
    evaluation (:func:`live_tile_mask`) so a serving index can compute the
    corpus side ONCE and reuse it across queries (``serving.index``).

    Attributes:
      maxw:    ``(nb, m)`` per-block per-dimension max ``|weight|`` — the
               paper's ``maxweight_d(V)`` at tile granularity. ``maxw > 0``
               is exactly the tile-granular posting-list support.
      mw:      ``(nb,)`` per-block max weight (max of ``maxw`` over dims).
      max_nnz: ``(nb,)`` per-block max row nnz (the paper's ``|y|``).
    """

    maxw: jax.Array
    mw: jax.Array
    max_nnz: jax.Array


def dense_block_stats(D: jax.Array, block_rows: int, eps: float = 0.0) -> BlockStats:
    """Block pruning summaries from a dense ``(n, m)`` array."""
    maxw = block_maxweight_bounds(D, block_rows)
    mw, max_nnz = block_minsize_bounds(D, block_rows, eps)
    return BlockStats(maxw=maxw, mw=mw, max_nnz=max_nnz)


def sparse_block_stats(sp, block_rows: int) -> BlockStats:
    """Block pruning summaries straight from padded CSR (never densified).

    ``max_nnz`` uses the corpus's EXACT stored per-row nnz (the dense path
    has to recount from a densified array); stored nnz over-counts
    duplicate coordinates, which only loosens (never unsounds) the minsize
    bound.
    """
    maxw = sparse_block_maxweight(sp, block_rows)
    max_nnz = jnp.max(sp.nnz.reshape(-1, block_rows), axis=1)
    return BlockStats(maxw=maxw, mw=jnp.max(maxw, axis=1), max_nnz=max_nnz)


def live_tile_mask(
    stats_rows: BlockStats,
    stats_cols: BlockStats,
    threshold: jax.Array | float,
    *,
    use_minsize: bool = True,
    normalized: bool = True,
    return_ub: bool = False,
):
    """``(n_row_blocks, n_col_blocks)`` LIVE mask from precomputed stats.

    The *scoring-time* half of pruning: a cheap summary matmul over
    whatever :class:`BlockStats` the caller has — freshly computed (the
    self-join paths) or prebuilt once per corpus (the serving index).
    Semantics identical to :func:`block_prune_mask` /
    :func:`sparse_block_prune_mask`, which are now thin wrappers.

    The maxweight upper bound IS the inverted-index candidacy test in
    weighted form: blocks sharing no posting list have ``ub = 0`` and die
    for any ``t > 0``. ``normalized`` refers to the COLUMN side (the
    minsize bound needs ``||y|| = 1``; query rows may be anything).

    ``return_ub=True`` additionally returns the ``(nb_r, nb_c)`` f32 upper
    bounds — the adaptive worklist ordering key (``compact_worklist``).
    """
    t = jnp.asarray(threshold, jnp.float32)
    ub = block_upper_bounds(stats_rows.maxw, stats_cols.maxw)
    live = ub >= t
    if use_minsize and normalized:
        ms_ub = (
            stats_rows.mw[:, None]
            * jnp.sqrt(stats_cols.max_nnz.astype(jnp.float32))[None, :]
        )
        live &= ms_ub >= t
        ub = jnp.minimum(ub, ms_ub)
    if return_ub:
        return live, ub
    return live


def block_maxweight_bounds(D: jax.Array, block_rows: int) -> jax.Array:
    """Per-block, per-dimension max absolute weight: ``(n/b, m)``.

    ``maxw[B, d] = max_{i in block B} |D[i, d]|`` — the block-granular analogue
    of the paper's ``maxweight_d(V)``.
    """
    n, m = D.shape
    assert n % block_rows == 0, (n, block_rows)
    return jnp.max(
        jnp.abs(D).reshape(n // block_rows, block_rows, m), axis=1
    )


def block_upper_bounds(maxw_rows: jax.Array, maxw_cols: jax.Array) -> jax.Array:
    """Upper bound on any cross-block similarity: ``ub[I,J] ≥ max sim``.

    ``sim(x, y) = Σ_d x[d]·y[d] ≤ Σ_d maxw_I[d]·maxw_J[d]`` for ``x ∈ I``,
    ``y ∈ J``. One small matmul over block summaries (paper's partial-indexing
    bound at tile granularity).
    """
    return jnp.einsum(
        "im,jm->ij", maxw_rows, maxw_cols,
        precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )


def row_nnz(D: jax.Array, eps: float = 0.0) -> jax.Array:
    """Number of non-zero components per row (paper's ``|x|``)."""
    return jnp.sum(jnp.abs(D) > eps, axis=-1, dtype=jnp.int32)


def block_minsize_bounds(
    D: jax.Array, block_rows: int, eps: float = 0.0
) -> tuple[jax.Array, jax.Array]:
    """Block summaries for the minsize bound.

    Returns ``(max_weight, max_nnz)`` per row block, where ``max_weight[B] =
    max_{x∈B} maxweight(x)`` and ``max_nnz[B] = max_{y∈B} |y|``. For rows
    normalized to unit L2 norm, Cauchy-Schwarz over the nonzero support gives
    ``sim(x, y) ≤ maxweight(x) · sqrt(|y|)`` — a strictly tighter form of the
    paper's ``|y| ≥ t / maxweight(x)`` minsize test.
    """
    n, m = D.shape
    assert n % block_rows == 0
    absD = jnp.abs(D).reshape(n // block_rows, block_rows, m)
    max_weight = jnp.max(absD, axis=(1, 2))
    nnz = jnp.sum(absD > eps, axis=-1, dtype=jnp.int32)
    max_nnz = jnp.max(nnz, axis=1)
    return max_weight, max_nnz


def block_prune_mask(
    D_rows: jax.Array,
    D_cols: jax.Array,
    threshold: jax.Array | float,
    block_rows: int,
    block_cols: int | None = None,
    *,
    use_minsize: bool = True,
    normalized: bool = True,
    return_ub: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """``(n_row_blocks, n_col_blocks)`` bool mask; True = block pair is LIVE.

    A False entry certifies every pair in that tile has ``sim < t`` and may be
    skipped. Combines the maxweight bound with the (optional) minsize bound.

    ``D_rows`` are query rows, ``D_cols`` corpus rows (self-join: same array).
    Thin wrapper over :func:`dense_block_stats` + :func:`live_tile_mask`
    (the separable index-build / scoring-time halves).
    """
    block_cols = block_cols or block_rows
    stats_r = dense_block_stats(D_rows, block_rows)
    stats_c = (
        stats_r
        if D_cols is D_rows and block_cols == block_rows
        else dense_block_stats(D_cols, block_cols)
    )
    return live_tile_mask(
        stats_r, stats_c, threshold,
        use_minsize=use_minsize, normalized=normalized, return_ub=return_ub,
    )


class PruneStats(NamedTuple):
    live_blocks: jax.Array    # scalar i32
    total_blocks: jax.Array   # scalar i32
    live_fraction: jax.Array  # scalar f32


def prune_stats(mask: jax.Array) -> PruneStats:
    total = jnp.int32(mask.size)
    live = jnp.sum(mask, dtype=jnp.int32)
    return PruneStats(
        live_blocks=live,
        total_blocks=total,
        live_fraction=live.astype(jnp.float32) / total.astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# Sparse-exact bounds: inverted-index candidate generation + tile bounds
# computed straight from the padded-CSR corpus (core.sparse.SparseCorpus),
# never from a densified array.
# ---------------------------------------------------------------------------


def sparse_block_maxweight(sp, block_rows: int) -> jax.Array:
    """Per-block per-dimension max weight ``(n/b, m)`` from CSR, by scatter-max.

    The exact analogue of :func:`block_maxweight_bounds` without
    densification. Duplicate coordinates are combined first
    (``core.sparse.dedupe_rows``) so the bound sees the effective
    per-component magnitude ``|Σ slots|``, not per-slot values — a per-slot
    max would UNDER-bound concentrated duplicates and unsoundly prune.
    Padding slots carry ``(index 0, value 0)`` and are inert under
    max-with-0.
    """
    from repro.core.sparse import dedupe_rows

    n = sp.n
    assert n % block_rows == 0, (n, block_rows)
    idx, comp = dedupe_rows(sp.indices, sp.values)
    blk = (jnp.arange(n, dtype=jnp.int32) // block_rows)[:, None]
    out = jnp.zeros((n // block_rows, sp.m), jnp.float32)
    return out.at[blk, idx].max(jnp.abs(comp))


def sparse_block_support(sp, block_rows: int) -> jax.Array:
    """Tile-granular posting lists: ``sup[B, d]`` ⇔ dimension ``d``'s posting
    list intersects row block ``B`` (the paper's inverted index ``I_d``,
    quantized to blocks)."""
    return sparse_block_maxweight(sp, block_rows) > 0


def sparse_candidate_mask(sup_rows: jax.Array, sup_cols: jax.Array) -> jax.Array:
    """Inverted-index candidate generation at tile granularity.

    A tile ``(I, J)`` is a candidate iff some dimension's posting list hits
    both blocks — the paper's partial indexing: pairs sharing no indexed
    dimension are never generated at all. One boolean-as-f32 matmul over the
    block-support summaries; everything else is provably zero-similarity.

    Inside :func:`sparse_block_prune_mask` this test is enforced through the
    weighted maxweight bound instead (no-shared-support ⇒ ``ub = 0 < t`` for
    any ``t > 0``), which also stays sound at ``t ≤ 0`` where zero-similarity
    pairs DO match; this boolean form exists for index statistics and
    candidate accounting.
    """
    hits = jnp.einsum(
        "im,jm->ij",
        sup_rows.astype(jnp.float32),
        sup_cols.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return hits > 0


def sparse_block_prune_mask(
    sp_rows,
    sp_cols,
    threshold: jax.Array | float,
    block_rows: int,
    block_cols: int | None = None,
    *,
    use_minsize: bool = True,
    normalized: bool = True,
    return_ub: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """``(n_row_blocks, n_col_blocks)`` LIVE mask from CSR inputs only.

    Conjunction of two exact certificates (False ⇒ no pair in the tile
    reaches ``t``; pruned execution stays exact):

    1. the maxweight upper bound ``Σ_d maxw_I[d]·maxw_J[d] ≥ t`` over
       sparse block maxima — which IS the inverted-index candidacy test in
       weighted form: blocks sharing no posting list have ``ub = 0`` and
       die for any ``t > 0`` (identical bound to the dense path, no
       densification),
    2. the minsize bound ``maxweight(I) · √(max nnz in J) ≥ t`` using the
       corpus's EXACT stored per-row nnz — the dense path has to recount
       nonzeros from a densified array; here ``|y|`` is native. Stored nnz
       over-counts duplicate coordinates, which only loosens (never
       unsounds) the bound. Like its dense twin, the minsize bound assumes
       unit row norms and is gated on ``normalized`` (pass False for
       unnormalized corpora). A symmetrized self-join worklist
       (``live | live.T``, as built by ``apss_sparse_compacted``) is
       additionally sound for any ``t < 1`` even unnormalized: both
       orientations failing implies ``sim ≤ mw_I·√|J| · mw_J·√|I| < t² <
       t``.

    Both certificates are trivially live at ``t ≤ 0`` (their left sides are
    ≥ 0), where every pair — including zero-similarity ones — matches.
    Thin wrapper over :func:`sparse_block_stats` + :func:`live_tile_mask`.
    """
    block_cols = block_cols or block_rows
    stats_r = sparse_block_stats(sp_rows, block_rows)
    stats_c = (
        stats_r  # self-join: skip the second dedupe + scatter-max pass
        if sp_cols is sp_rows and block_cols == block_rows
        else sparse_block_stats(sp_cols, block_cols)
    )
    return live_tile_mask(
        stats_r, stats_c, threshold,
        use_minsize=use_minsize, normalized=normalized, return_ub=return_ub,
    )


def checkerboard_live_mask(
    cells,
    threshold: jax.Array | float,
    block_rows: int,
    *,
    use_minsize: bool = True,
) -> jax.Array:
    """Self-join LIVE mask under a 2-D checkerboard dimension split.

    ``cells`` are the ``r`` dimension slices of one corpus
    (:func:`~repro.core.sparse.dim_slices`) — each cell column of the
    checkerboard sees only its ``m/r`` posting lists. The composed mask is
    the OR over cells of the per-cell :func:`sparse_block_prune_mask` at the
    Lemma-1 **local threshold** ``t/r``:

    - *Lemma 1 (local pruning)*: a pair with global ``sim ≥ t`` has partial
      similarity ``≥ t/r`` in at least one dimension slice, so its tile is
      live in that cell's mask and survives the union — the bound stays
      sound under the composition.
    - *Per-cell minsize*: the minsize certificate assumes unit row norms,
      but a cell of a normalized corpus has ``||y_cell|| ≤ 1`` and
      Cauchy–Schwarz gives ``partial ≤ maxw_cell(x)·√|y_cell|·||y_cell||``,
      so the unit-norm form only over-bounds — per-cell evaluation remains
      conservative (asserted by ``tests/test_sparse_2d.py``).

    The exact distributed rescoring (``_accumulate_block_scores``) needs
    every cell's partials at the candidate union, so this mask cannot skip
    partial-score compute without breaking exactness. It is the candidacy /
    soundness view of the composed schedule — exercised by the soundness
    tests and the hook for a future per-cell worklist path; it is NOT part
    of the telemetry record (evaluating it costs device work, which
    telemetry never performs).
    """
    t_local = local_threshold(threshold, len(cells))
    live = None
    for cell in cells:
        cell_live = sparse_block_prune_mask(
            cell, cell, t_local, block_rows,
            use_minsize=use_minsize, normalized=True,
        )
        live = cell_live if live is None else (live | cell_live)
    return live


def local_threshold(threshold: float | jax.Array, num_shards: int) -> jax.Array:
    """Paper Lemma 1: local pruning threshold ``t_local = t / p``.

    For any partition of the dimensions into ``num_shards`` parts, every global
    match ``sim(x,y) ≥ t`` has local partial similarity ``≥ t/p`` on at least
    one shard (otherwise the total would be ``< p·(t/p) = t``).
    """
    return jnp.asarray(threshold, jnp.float32) / num_shards
