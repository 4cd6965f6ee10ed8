"""Sparse corpus representation and sparse APSS scoring primitives.

The paper's entire experimental regime is sparse text (density ≲ 1%, Table
1), and its fast sequential algorithm lives on *partial indexing* — an
inverted index over dimensions. A dense ``(n, m)`` array wastes both memory
(``n·m`` floats for ``n·avg_nnz`` payload) and MXU work (mostly-zero tiles).

:class:`SparseCorpus` is the statically-shaped sparse layout JAX needs:
padded CSR (a.k.a. ELL) — every row stores exactly ``cap`` ``(index,
value)`` slots, real entries first, padding slots holding ``(0, 0.0)`` so
they are arithmetically inert in every consumer (scatter adds 0, gathers
multiply by 0, maxweight maxes with 0). ``nnz`` keeps the exact per-row
count, which also makes the paper's minsize bound exact instead of a dense
Cauchy–Schwarz surrogate (see ``core.pruning``).

Scoring never materializes ``(n, m)``; the two primitives are

- :func:`densify_rows` — scatter ONE row block to dense ``(block, m)``
  (the all-pairs-0-array score accumulator, built per block, not per
  corpus), and
- :func:`gather_dot` — CSR×dense tile scores ``s[r, c] = Σ_k
  qd[r, idx[c, k]] · val[c, k]`` in ``O(rows · cols · cap)`` FLOPs — the
  true sparse-dot cost, a factor ``m / cap ≈ 1/density`` below the dense
  tile matmul.

:func:`sparse_similarity_topk` composes them into the blocked join that
backs ``apss_blocked`` / ``apss_horizontal`` for sparse inputs; the 2-D
checkerboard scores its cells with :func:`gather_dot`. The
maximally-pruned single-device path (inverted-index worklist + CSR tile
kernel) lives in ``kernels/apss_block/sparse.py``; the sparse vertical
path scores its partial tiles with :func:`gather_dot` too.

Duplicate coordinates within a row are legal and mean *summation* (the COO
convention): ``to_dense`` scatter-adds and ``gather_dot`` sums every slot,
so all consumers agree. Consumers that need per-*component* magnitudes
(row norms, maxweight pruning bounds) combine duplicate slots first via
:func:`dedupe_rows`.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.compat import pvary
from repro.core.matches import (
    SCORE_PRECISION,
    Matches,
    empty_matches,
    extract_matches,
    merge_matches,
)


@jax.tree_util.register_pytree_node_class
class SparseCorpus:
    """Padded-CSR (ELL) corpus: statically shaped, JAX-transformable.

    Attributes:
      indices: ``(n, cap)`` int32 dimension ids; padding slots hold 0.
      values:  ``(n, cap)`` float32 weights; padding slots hold 0.0.
      nnz:     ``(n,)`` int32 exact per-row stored-entry count.
      m:       number of dimensions (static aux data — survives tracing).
    """

    def __init__(self, indices, values, nnz, m: int):
        self.indices = indices
        self.values = values
        self.nnz = nnz
        self.m = int(m)

    def tree_flatten(self):
        return (self.indices, self.values, self.nnz), self.m

    @classmethod
    def tree_unflatten(cls, m, children):
        return cls(*children, m)

    @property
    def n(self) -> int:
        return self.indices.shape[0]

    @property
    def cap(self) -> int:
        return self.indices.shape[1]

    @property
    def shape(self) -> tuple[int, int]:
        return (self.n, self.m)

    def __repr__(self) -> str:
        return f"SparseCorpus(n={self.n}, m={self.m}, cap={self.cap})"


def from_dense(D, cap: int | None = None) -> SparseCorpus:
    """Host-side dense → padded-CSR conversion (row indices sorted).

    ``cap`` may only widen the layout (extra inert padding slots); a cap
    below the realized max row nnz would silently drop values and break the
    exact-``nnz`` contract, so it raises instead.
    """
    D = np.asarray(D)
    n, m = D.shape
    nz = D != 0
    nnz = nz.sum(axis=1).astype(np.int32)
    need = int(max(1, nnz.max(initial=1)))
    if cap is not None and cap < need:
        raise ValueError(f"cap={cap} would truncate rows (max nnz {need})")
    cap = int(cap if cap is not None else need)
    indices = np.zeros((n, cap), np.int32)
    values = np.zeros((n, cap), np.float32)
    for i in range(n):
        cols = np.nonzero(nz[i])[0]
        indices[i, : len(cols)] = cols
        values[i, : len(cols)] = D[i, cols]
    return SparseCorpus(
        jnp.asarray(indices), jnp.asarray(values), jnp.asarray(nnz), m
    )


def to_dense(sp: SparseCorpus) -> jax.Array:
    """Jittable CSR → dense scatter; duplicate coordinates sum."""
    rows = jnp.arange(sp.n, dtype=jnp.int32)[:, None]
    out = jnp.zeros(sp.shape, jnp.float32)
    return out.at[rows, sp.indices].add(sp.values)


def dedupe_rows(indices: jax.Array, values: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Combine duplicate coordinates within each row: run-sums in place.

    Returns same-shape ``(indices, values)`` where each distinct dimension's
    slots are summed into the run's last slot and every other slot becomes
    the inert ``(0, 0.0)`` padding convention. Sort + cumsum, ``O(cap log
    cap)`` per row — never densifies. Consumers needing per-*component*
    quantities (norms, maxweight bounds) go through this; scoring paths
    don't need to (they sum every slot by construction).
    """
    order = jnp.argsort(indices, axis=1)
    si = jnp.take_along_axis(indices, order, axis=1)
    sv = jnp.take_along_axis(values.astype(jnp.float32), order, axis=1)
    c = jnp.cumsum(sv, axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, si.shape, 1)
    first = jnp.concatenate(
        [jnp.ones_like(si[:, :1], bool), si[:, 1:] != si[:, :-1]], axis=1
    )
    last = jnp.concatenate(
        [si[:, 1:] != si[:, :-1], jnp.ones_like(si[:, :1], bool)], axis=1
    )
    start = jax.lax.cummax(jnp.where(first, pos, 0), axis=1)
    run_sum = c - jnp.take_along_axis(c - sv, start, axis=1)  # Σ of the run
    return (
        jnp.where(last, si, 0),
        jnp.where(last, run_sum, 0.0),
    )


def normalize_sparse(sp: SparseCorpus, eps: float = 1e-12) -> SparseCorpus:
    """L2-normalize rows in CSR form (the paper's ``||x|| = 1``).

    Duplicate-correct: norms are taken over per-*component* sums
    (:func:`dedupe_rows`), and uniform slot scaling scales every effective
    component uniformly.
    """
    _, comp = dedupe_rows(sp.indices, sp.values)
    nrm = jnp.sqrt(jnp.sum(comp * comp, axis=1))
    scale = 1.0 / jnp.maximum(nrm, eps)
    return SparseCorpus(sp.indices, sp.values * scale[:, None], sp.nnz, sp.m)


def pad_rows_sparse(sp: SparseCorpus, multiple: int) -> tuple[SparseCorpus, int]:
    """Zero-pad rows to a multiple; padding rows are empty (nnz 0)."""
    n = sp.n
    rem = (-n) % multiple
    if rem:
        sp = SparseCorpus(
            jnp.pad(sp.indices, ((0, rem), (0, 0))),
            jnp.pad(sp.values, ((0, rem), (0, 0))),
            jnp.pad(sp.nnz, (0, rem)),
            sp.m,
        )
    return sp, n


def density(sp: SparseCorpus) -> float:
    """Host-side exact density (stored entries / n·m)."""
    return float(np.asarray(sp.nnz).sum()) / float(sp.n * sp.m)


def deal_dims(sp: SparseCorpus, p: int) -> tuple[np.ndarray, np.ndarray]:
    """Host-side assignment of every dimension to one of ``p`` shards.

    Dimensions are dealt round-robin in order of posting-list length (the
    stored entries that name them, most first; ties by id): the one of
    rank ``r`` goes to shard ``r % p`` as its local dimension ``r // p``.
    On a Zipf vocabulary contiguous ranges would put nearly every nonzero
    on the shard that holds the head; dealing spreads the head, so every
    shard holds about ``nnz / p`` entries and ``⌈m/p⌉`` dimensions.

    Returns ``owner (m,)`` (the shard of each dimension) and ``local
    (m,)`` (its id inside that shard), both int64.
    """
    idx = np.asarray(sp.indices)
    nnz = np.asarray(sp.nnz)
    valid = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    postings = np.bincount(idx[valid], minlength=sp.m)
    rank = np.empty(sp.m, np.int64)
    rank[np.lexsort((np.arange(sp.m), -postings))] = np.arange(sp.m)
    return rank % p, rank // p


def shard_dims(
    sp: SparseCorpus, p: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side vertical (dimension) split into ``p`` shards.

    The paper's 1-D vertical distribution in its natural habitat: device
    ``d`` owns the posting lists of the dimensions :func:`deal_dims` deals
    it — a shard of the inverted index — and sees every row restricted to
    them. Any ``m`` is accepted.

    Returns stacked ``(p, n, cap_loc)`` indices (LOCAL, shard-relative, in
    ``[0, m_loc)``) and values, ``(p, n)`` local nnz (so ``nnz.sum(1)`` is
    each shard's nonzero count), and ``m_loc = ⌈m/p⌉``. ``cap_loc`` is the
    max per-device per-row count (uniform so the stack is rectangular).
    """
    owner, local = deal_dims(sp, p)
    m_loc = -(-sp.m // p)
    idx = np.asarray(sp.indices)
    val = np.asarray(sp.values)
    nnz = np.asarray(sp.nnz)
    n, cap = idx.shape
    valid = np.arange(cap)[None, :] < nnz[:, None]
    # Each row's slots in shard order (padding, shard p, last), stable, so
    # that shard d's slots are one run per row, in their original order.
    shard = np.where(valid, owner.astype(np.int16)[idx], np.int16(p))
    order = np.argsort(shard, axis=1, kind="stable")
    sorted_idx = local.astype(np.int32)[np.take_along_axis(idx, order, axis=1)]
    sorted_val = np.take_along_axis(val, order, axis=1)
    counts = np.stack([(shard == d).sum(axis=1) for d in range(p)])  # (p, n)
    starts = np.cumsum(counts, axis=0) - counts  # (p, n): where each run begins
    cap_loc = max(1, int(counts.max(initial=1)))
    slot = np.arange(cap_loc)[None, :]
    out_idx = np.zeros((p, n, cap_loc), np.int32)
    out_val = np.zeros((p, n, cap_loc), np.float32)
    for d in range(p):
        at = np.minimum(starts[d][:, None] + slot, cap - 1)
        mine = slot < counts[d][:, None]
        out_idx[d] = np.where(mine, np.take_along_axis(sorted_idx, at, axis=1), 0)
        out_val[d] = np.where(mine, np.take_along_axis(sorted_val, at, axis=1), 0.0)
    return out_idx, out_val, counts.astype(np.int32), m_loc


def dim_slices(sp: SparseCorpus, p: int) -> list[SparseCorpus]:
    """The ``p`` per-slice corpora of :func:`shard_dims` as SparseCorpus views.

    Slice ``d`` holds every row restricted to the dimensions dealt to
    shard ``d`` (:func:`deal_dims`) with SLICE-RELATIVE indices and ``m =
    ⌈m/p⌉`` — exactly the cell contents of one checkerboard column in
    ``apss_2d``. Used by the per-cell pruning bounds
    (``core.pruning.checkerboard_live_mask``) and tests; the distributed
    path consumes the stacked arrays directly.
    """
    idx_s, val_s, nnz_s, m_loc = shard_dims(sp, p)
    return [
        SparseCorpus(
            jnp.asarray(idx_s[d]), jnp.asarray(val_s[d]),
            jnp.asarray(nnz_s[d]), m_loc,
        )
        for d in range(p)
    ]


# ---------------------------------------------------------------------------
# Scoring primitives
# ---------------------------------------------------------------------------


def densify_rows(sp: SparseCorpus, start, rows: int) -> jax.Array:
    """Scatter one row block to dense ``(rows, m)`` (traced ``start`` ok).

    This is the only densification the sparse path ever performs — one
    query block at a time, never the corpus.
    """
    idx = lax.dynamic_slice_in_dim(sp.indices, start, rows, axis=0)
    val = lax.dynamic_slice_in_dim(sp.values, start, rows, axis=0)
    r = jnp.arange(rows, dtype=jnp.int32)[:, None]
    return jnp.zeros((rows, sp.m), jnp.float32).at[r, idx].add(val)


def gather_dot(
    qd: jax.Array, idx: jax.Array, val: jax.Array, *, chunk: int = 32
) -> jax.Array:
    """Sparse tile scores: dense query block × CSR corpus block.

    ``s[r, c] = Σ_k qd[r, idx[c, k]] · val[c, k]`` — exactly the sparse
    dot product cost ``O(rows · cols · cap)`` FLOPs; padding slots (val 0)
    contribute nothing, duplicate coordinates sum. The cap axis is folded
    in ``chunk``-sized pieces so the gathered intermediate peaks at
    ``O(rows · cols · chunk)`` — bounded regardless of corpus density —
    instead of materializing the full ``(rows, cols, cap)`` tensor.
    """
    rows = qd.shape[0]
    cols, cap = idx.shape
    rem = (-cap) % chunk
    if rem:  # pad with inert (0, 0.0) slots to a chunk multiple
        idx = jnp.pad(idx, ((0, 0), (0, rem)))
        val = jnp.pad(val, ((0, 0), (0, rem)))
    nch = (cap + rem) // chunk
    idxc = jnp.moveaxis(idx.reshape(cols, nch, chunk), 1, 0)
    valc = jnp.moveaxis(
        val.astype(jnp.float32).reshape(cols, nch, chunk), 1, 0
    )

    def step(acc, iv):
        i, v = iv  # (cols, chunk) each
        g = jnp.take(qd, i, axis=1)  # (rows, cols, chunk)
        return acc + jnp.einsum(
            "rck,ck->rc", g, v,
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        ), None

    acc, _ = lax.scan(step, jnp.zeros((rows, cols), jnp.float32), (idxc, valc))
    return acc


def sparse_similarity_topk(
    Q: SparseCorpus,
    C: SparseCorpus,
    threshold: float,
    k: int = 32,
    *,
    block_rows: int = 512,
    exclude_self: bool = False,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    vary_axes: Sequence[str] = (),
) -> Matches:
    """Blocked sparse similarity join of ``Q (nq, m)`` vs ``C (nc, m)``.

    The sparse twin of ``core.apss.similarity_topk``: query blocks are
    densified one at a time (``densify_rows``), corpus blocks stay CSR and
    are scored with :func:`gather_dot`, so FLOPs and peak memory are
    ``O(block² · cap)`` and ``O(block · m)`` — never ``O(n · m)``.

    Fully traceable (offsets may be traced), so it drops into the
    shard_map'd distributed schedules; ``vary_axes`` marks internal carry
    inits as device-varying there (same role as ``_pvary`` in
    ``core.distributed``).
    """
    if Q.m != C.m:
        # Fail loudly like the dense einsum would: out-of-range gathers
        # would otherwise quietly NaN every affected score.
        raise ValueError(f"dimension mismatch: Q.m={Q.m} vs C.m={C.m}")
    nq = Q.n
    Qp, _ = pad_rows_sparse(Q, block_rows)
    Cp, nc = pad_rows_sparse(C, block_rows)
    nqb = Qp.n // block_rows
    ncb = Cp.n // block_rows
    Ci = Cp.indices.reshape(ncb, block_rows, Cp.cap)
    Cv = Cp.values.reshape(ncb, block_rows, Cp.cap)

    def _vary(tree):
        for ax in vary_axes:
            tree = jax.tree.map(lambda a: pvary(a, ax), tree)
        return tree

    def q_block(carry, qi):
        qd = densify_rows(Qp, qi * block_rows, block_rows)

        def c_block(mm, ci):
            s = gather_dot(qd, Ci[ci], Cv[ci])
            col_valid = (
                jnp.arange(block_rows, dtype=jnp.int32) + ci * block_rows
            ) < nc
            m_new = extract_matches(
                s, threshold, k,
                row_offset=row_offset + qi * block_rows,
                col_offset=col_offset + ci * block_rows,
                exclude_self=exclude_self,
                col_valid=col_valid,
            )
            return merge_matches(mm, m_new), None

        m0 = _vary(empty_matches(block_rows, k))
        mm, _ = lax.scan(c_block, m0, jnp.arange(ncb))
        return carry, mm

    _, ms = lax.scan(q_block, 0, jnp.arange(nqb))
    out = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), ms)
    return jax.tree.map(lambda x: x[:nq], out)
