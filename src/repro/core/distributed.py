"""Distributed APSS: the paper's 1-D and 2-D data distributions on a TPU mesh.

Paper → TPU mapping (see DESIGN.md §2 for the full table):

- **1-D horizontal** (paper Alg. 6, vectors/rows distributed):
  ``schedule="allgather"`` is the paper-faithful variant — every device
  all-gathers the full corpus and matches its local rows (MPI_Allgather of
  query blocks ≡ all-gather of row shards, the block-processing optimization
  taken to its limit). ``schedule="ring"`` is the beyond-paper variant:
  ``lax.ppermute`` rotates row blocks so peak memory is O(n/p · m) and the
  send of step s+1 overlaps the matmul of step s. ``schedule="halfring"``
  additionally exploits S = Sᵀ: only ⌈p/2⌉ block rotations, with small
  top-k "backward match" packets returned to the transposed owner.

- **1-D vertical** (paper Alg. 3/4, dimensions/columns distributed): every
  device computes partial scores in its dimension slice.
  ``accumulation="allreduce"`` ≈ paper's vertical-noopt (communicate all
  scores); ``accumulation="scatter"`` is the paper's flat accumulation §5.1.7
  (result partitioned over processors); ``accumulation="compressed"``
  implements **local pruning (Lemma 1)**: partials are thresholded at ``t/p``,
  compacted to top-C (value, index) candidates, all-gathered, and exactly
  re-scored with one small psum — the collective volume drops from O(n) to
  O(p·C) per query row, exactly the 10-100× score-volume reduction of paper
  Tables 5-6. ``accumulation="recursive"`` is the recursive pruning /
  hypercube algorithm (paper §5.1.5-5.1.8, Alg. 5): log₂p pairwise exchanges
  with per-level thresholds ``t·s/p`` and upper-bound tracking for exactness.

- **2-D** (paper Alg. 7): checkerboard over ``(data, model)``; a ring over the
  row axis composes with the vertical accumulation over the column axis —
  the same elegant reuse as the paper's Alg. 7 (which passes the row
  communicator into the vertical code).

Row blocks (``block_rows``) are the paper's §5.1.9 block-processing knob: all
variants process a block of query rows per collective step.

Every variant is exact (validated against ``apss_reference``). The 1-D
compressed and recursive accumulations score a query block whose Lemma-1
candidates overflow the capacity on any shard by the all-reduce of its whole
partial tile instead, and count the blocks each route took; the 2-D
composition carries an overflow counter, so its capacity truncation is
visible, never silent.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import pvary, shard_map
from repro.core.apss import similarity_topk
from repro.core.matches import (
    SCORE_PRECISION,
    Matches,
    NEG_INF,
    extract_matches,
    matches_from_candidates,
    merge_matches,
)
from repro.core.pruning import local_threshold
from repro.core.sparse import (
    SparseCorpus,
    densify_rows,
    gather_dot,
    shard_dims,
    sparse_similarity_topk,
)
from repro.obs import trace
from repro.planner import telemetry


class ApssStats(NamedTuple):
    """Exactness accounting for capacity-bounded candidate sets.

    ``overflow_rows`` counts rows whose candidates overflowed the capacity:
    the 1-D vertical accumulations scored their blocks exactly instead, the
    2-D composition truncated them. ``blocks_pruned`` / ``blocks_exact``
    count the query blocks a 1-D vertical accumulation scored through its
    Lemma-1 candidates and through the all-reduce of the whole partial
    tile; ``None`` where a path does not count routes (the 2-D sweep).
    """

    overflow_rows: jax.Array  # i32 scalar
    blocks_pruned: jax.Array | None = None  # i32 scalar
    blocks_exact: jax.Array | None = None   # i32 scalar


def _matches_specs(axis) -> Matches:
    return Matches(values=P(axis, None), indices=P(axis, None), counts=P(axis))


def _pvary(tree, axis_name):
    """Mark constants as device-varying over `axis_name` (loop-carry typing)."""
    return jax.tree.map(lambda a: pvary(a, axis_name), tree)


def _to_wire(x: jax.Array) -> jax.Array:
    """Bitcast bf16 ring buffers to u16 for transport.

    Forces the *wire format* of traveling blocks to stay 2 bytes/element:
    without this, backends lacking native bf16 matmuls (the CPU dry-run)
    legally convert the loop carry to f32 once and permute 4-byte payloads,
    which would misrepresent the TPU collective volume (the MXU consumes
    bf16 directly). A no-op for f32 inputs.
    """
    if x.dtype == jnp.bfloat16:
        return lax.bitcast_convert_type(x, jnp.uint16)
    return x


def _from_wire(x: jax.Array, dtype) -> jax.Array:
    if x.dtype == jnp.uint16:
        return lax.bitcast_convert_type(x, dtype)
    return x


def default_candidate_capacity(k: int) -> int:
    """Candidate-capacity default of the compressed/recursive accumulations
    — the single definition shared by dispatch, telemetry records, and the
    planner's cost models (``planner.costmodel``)."""
    return max(4 * k, 32)


def _wire_itemsize(dtype) -> int:
    """Bytes/element a traveling block occupies on the wire (bf16 → 2)."""
    return 2 if dtype == jnp.bfloat16 else jnp.dtype(dtype).itemsize


def _axis_label(axis_name) -> str:
    if isinstance(axis_name, (tuple, list)):
        return "+".join(axis_name)
    return str(axis_name)


def _ring_perm(p: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % p) for i in range(p)]


def _shift_perm(p: int, s: int) -> list[tuple[int, int]]:
    return [(i, (i - s) % p) for i in range(p)]


# ---------------------------------------------------------------------------
# 1-D horizontal (paper Alg. 6): vectors distributed over `axis_name`
# ---------------------------------------------------------------------------


def apss_horizontal(
    D: jax.Array,
    threshold: float,
    k: int,
    mesh: Mesh,
    axis_name: str = "data",
    *,
    schedule: str = "ring",
    block_rows: int = 512,
    use_kernel: bool = False,
) -> Matches:
    """Distributed APSS with row (vector) sharding.

    ``D (n, m)`` global; rows sharded over ``axis_name`` (a name or tuple of
    names — tuples treat the axes jointly/row-major); ``n`` must divide
    evenly. Returns global :class:`Matches` with rows sharded the same way.

    ``use_kernel=True`` scores every local×visiting block pair with the
    fused streaming Pallas kernel (``O(rows·k)`` output, VMEM-resident score
    tiles) instead of the XLA einsum + ``extract_matches`` pair — the ring
    step's dynamic column offset feeds the kernel directly.

    ``D`` may be a :class:`~repro.core.sparse.SparseCorpus` (allgather,
    ring and halfring schedules): the CSR triple shards/travels instead of
    dense rows — collective volume drops from ``O(n_loc · m)`` to
    ``O(n_loc · cap)`` per hop, a factor ``≈ 1/density`` — and every block
    pair is scored with the gather-dot sparse tile primitive.
    """
    if isinstance(D, SparseCorpus):
        return _apss_horizontal_sparse(
            D, threshold, k, mesh, axis_name,
            schedule=schedule, block_rows=block_rows, use_kernel=use_kernel,
        )
    if isinstance(axis_name, (tuple, list)):
        axis_name = tuple(axis_name)
        p = 1
        for a in axis_name:
            p *= mesh.shape[a]
    else:
        p = mesh.shape[axis_name]

    if isinstance(axis_name, tuple) and schedule != "allgather":
        raise ValueError(
            "ring/halfring need a single axis; use "
            "apss_horizontal_hierarchical for multi-axis row sharding"
        )
    if schedule == "allgather":
        body = functools.partial(
            _horizontal_allgather, threshold=threshold, k=k,
            axis_name=axis_name, block_rows=block_rows,
            use_kernel=use_kernel,
        )
    elif schedule == "ring":
        body = functools.partial(
            _horizontal_ring, threshold=threshold, k=k,
            axis_name=axis_name, p=p, block_rows=block_rows,
            use_kernel=use_kernel,
        )
    elif schedule == "halfring":
        body = functools.partial(
            _horizontal_halfring, threshold=threshold, k=k,
            axis_name=axis_name, p=p, block_rows=block_rows,
            use_kernel=use_kernel,
        )
    else:
        raise ValueError(f"unknown horizontal schedule: {schedule}")

    if telemetry.enabled():
        n, m = D.shape
        n_loc = n // p
        telemetry.record(telemetry.ApssStats(
            variant=f"horizontal/{schedule}",
            n=n, m=m, devices=p, block_rows=block_rows, sparse=False,
            hops=telemetry.horizontal_hops(
                schedule, p, _axis_label(axis_name),
                telemetry.dense_block_bytes(n_loc, m, _wire_itemsize(D.dtype)),
                telemetry.matches_bytes(n_loc, k),
            ),
            flops=telemetry.dense_join_flops(n_loc, n, m)
            * (0.55 if schedule == "halfring" and not use_kernel else 1.0),
            extra={"use_kernel": use_kernel},
        ))
    # The replication checker has no rule for pallas_call on some JAX
    # versions; the kernel path is verified numerically by tests instead.
    return shard_map(
        body,
        mesh=mesh,
        in_specs=P(axis_name, None),
        out_specs=_matches_specs(axis_name),
        check_vma=not use_kernel,
    )(D)


def _flat_axis_index(axis_name):
    """Row-major flat rank over one axis name or a tuple of axis names."""
    if isinstance(axis_name, tuple):
        flat = jnp.int32(0)
        for a in axis_name:
            flat = flat * lax.psum(1, a) + lax.axis_index(a)
        return flat
    return lax.axis_index(axis_name)


def _horizontal_allgather(
    D_loc, *, threshold, k, axis_name, block_rows, use_kernel=False
):
    """Paper-faithful Alg. 6: all-gather the corpus, match local rows."""
    n_loc = D_loc.shape[0]
    me = _flat_axis_index(axis_name)
    D_all = lax.all_gather(D_loc, axis_name, axis=0, tiled=True)
    return similarity_topk(
        D_loc,
        D_all,
        threshold,
        k,
        block_rows=min(block_rows, n_loc),
        exclude_self=True,
        row_offset=me * n_loc,
        use_kernel=use_kernel,
    )


def _horizontal_ring(
    D_loc, *, threshold, k, axis_name, p, block_rows, use_kernel=False
):
    """Ring schedule: rotate row blocks; overlap send with compute."""
    n_loc, m = D_loc.shape
    me = lax.axis_index(axis_name)
    row_off = me * n_loc
    bs = min(block_rows, n_loc)

    def compute(buf, s, matches):
        src = jnp.mod(me - s, p)
        m_new = similarity_topk(
            D_loc, buf, threshold, k,
            block_rows=bs, exclude_self=True,
            row_offset=row_off, col_offset=src * n_loc,
            use_kernel=use_kernel,
        )
        return merge_matches(matches, m_new)

    def step(s, carry):
        buf, matches = carry
        # Send the current block onward *before* using it: XLA overlaps the
        # collective-permute with the (much longer) local matmul.
        nxt = lax.ppermute(buf, axis_name, perm=_ring_perm(p))
        matches = compute(buf, s, matches)
        return nxt, matches

    matches0 = _pvary(_empty_local_matches(n_loc, k), axis_name)
    buf, matches = lax.fori_loop(0, p - 1, step, (D_loc, matches0))
    matches = compute(buf, p - 1, matches)  # last block: no trailing send
    return matches


def _horizontal_halfring(
    D_loc, *, threshold, k, axis_name, p, block_rows, use_kernel=False
):
    """Half-ring: exploit S = Sᵀ — only ⌈(p-1)/2⌉ block hops.

    Each traveling block carries a "return caravan": the top-k backward
    (transposed) matches accumulated by every visitor. At offset ``s`` the
    visitor computes the cross tile once, keeps forward matches (its own
    rows), and folds backward matches (the block owner's rows) into the
    caravan, which hops along with the block. After ``p//2`` hops one static
    shift delivers the caravan home. Halves the large block traffic of the
    full ring; the caravan adds only O(k) words/row/hop.

    Kernel path: the fused kernel extracts matches in-flight (the score
    tile never leaves VMEM), so the two orientations are two kernel joins
    with swapped offsets instead of one XLA einsum read twice. That keeps
    the schedule's halved *wire* traffic but recomputes the tile's MXU work
    for the mirror (≈ ring-fused compute); folding both orientations into
    one kernel needs per-tile candidate packets + a cross-tile merge (the
    ``apss_fused_compacted`` architecture) lifted into the ring loop — an
    open item, see DESIGN.md §3.
    """
    n_loc, m = D_loc.shape
    me = lax.axis_index(axis_name)
    row_off = me * n_loc
    bs = min(block_rows, n_loc)
    half = p // 2

    # Step 0: self block.
    matches = similarity_topk(
        D_loc, D_loc, threshold, k, block_rows=bs,
        exclude_self=True, row_offset=row_off, col_offset=row_off,
        use_kernel=use_kernel,
    )
    if p == 1:
        return matches

    def cross_tile(buf, s, need_bwd=True):
        src = jnp.mod(me - s, p)  # owner of `buf`
        col_off = src * n_loc
        if use_kernel:
            fwd = similarity_topk(
                D_loc, buf, threshold, k, block_rows=bs,
                exclude_self=True, row_offset=row_off, col_offset=col_off,
                use_kernel=True,
            )
            if not need_bwd:
                # The kernel path's backward orientation is a second full
                # fused join, not a cheap transposed extraction — skip it
                # deterministically when the caller discards it (even-p
                # final step) instead of hoping XLA DCEs a custom-call.
                return fwd, None
            bwd = similarity_topk(
                buf, D_loc, threshold, k, block_rows=bs,
                exclude_self=True, row_offset=col_off, col_offset=row_off,
                use_kernel=True,
            )
            return fwd, bwd
        S = jnp.einsum(
            "im,jm->ij", D_loc, buf,
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        fwd = extract_matches(
            S, threshold, k, row_offset=row_off, col_offset=col_off,
            exclude_self=True,
        )
        bwd = extract_matches(
            S.T, threshold, k, row_offset=col_off, col_offset=row_off,
            exclude_self=True,
        )
        return fwd, bwd

    def hop(x):
        return lax.ppermute(x, axis_name, perm=_ring_perm(p))

    def step(s, carry):
        buf, caravan, mm = carry
        buf = hop(buf)
        caravan = jax.tree.map(hop, caravan)
        fwd, bwd = cross_tile(buf, s)
        return buf, merge_matches(caravan, bwd), merge_matches(mm, fwd)

    caravan = _pvary(_empty_local_matches(n_loc, k), axis_name)
    buf, caravan, matches = lax.fori_loop(
        1, half, step, (D_loc, caravan, matches)
    )
    # Final offset s = half: forward always; backward only when p is odd
    # (for even p both orientations of the antipodal pair are covered
    # forward, and a backward copy would double-count).
    if p % 2 == 1:
        buf, caravan, matches = step(half, (buf, caravan, matches))
    else:
        buf = hop(buf)
        caravan = jax.tree.map(hop, caravan)
        fwd, _ = cross_tile(buf, jnp.int32(half), need_bwd=False)
        matches = merge_matches(matches, fwd)
    # Send the caravan home: its rows belong to device (me - half).
    home = jax.tree.map(
        lambda x: lax.ppermute(x, axis_name, perm=_shift_perm(p, half)),
        caravan,
    )
    return merge_matches(matches, home)


def _empty_local_matches(rows: int, k: int) -> Matches:
    return Matches(
        values=jnp.full((rows, k), NEG_INF, jnp.float32),
        indices=jnp.full((rows, k), -1, jnp.int32),
        counts=jnp.zeros((rows,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# 1-D horizontal, sparse corpora: the CSR triple shards/travels
# ---------------------------------------------------------------------------


def _apss_horizontal_sparse(
    D: SparseCorpus, threshold, k, mesh, axis_name, *,
    schedule, block_rows, use_kernel,
):
    if use_kernel:
        raise ValueError(
            "sparse use_kernel is the self-join worklist path "
            "(kernels.apss_block.sparse); distributed sparse schedules "
            "score with the XLA gather-dot primitive"
        )
    if isinstance(axis_name, (tuple, list)):
        raise ValueError("sparse horizontal needs a single axis name")
    p = mesh.shape[axis_name]
    if schedule == "allgather":
        body = functools.partial(
            _sparse_horizontal_allgather, m=D.m, threshold=threshold, k=k,
            axis_name=axis_name, block_rows=block_rows,
        )
    elif schedule == "ring":
        body = functools.partial(
            _sparse_horizontal_ring, m=D.m, threshold=threshold, k=k,
            axis_name=axis_name, p=p, block_rows=block_rows,
        )
    elif schedule == "halfring":
        body = functools.partial(
            _sparse_horizontal_halfring, m=D.m, threshold=threshold, k=k,
            axis_name=axis_name, p=p, block_rows=block_rows,
        )
    else:
        raise ValueError(
            f"sparse horizontal supports allgather|ring|halfring, "
            f"got: {schedule}"
        )
    if telemetry.enabled():
        n, n_loc = D.n, D.n // p
        telemetry.record(telemetry.ApssStats(
            variant=f"horizontal/{schedule}",
            n=n, m=D.m, devices=p, block_rows=block_rows, sparse=True,
            hops=telemetry.horizontal_hops(
                schedule, p, _axis_label(axis_name),
                telemetry.csr_block_bytes(n_loc, D.cap),
                telemetry.matches_bytes(n_loc, k),
                payload="csr_block",
            ),
            flops=telemetry.sparse_join_flops(n_loc, n, D.cap),
            extra={"cap": D.cap},
        ))
    # The VMA checker has no rule for the scatter/gather ops inside the
    # sparse tile primitive on some JAX versions; verified numerically.
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None), P(axis_name)),
        out_specs=_matches_specs(axis_name),
        check_vma=False,
    )(D.indices, D.values, D.nnz)


def _sparse_horizontal_allgather(
    idx, val, nnz, *, m, threshold, k, axis_name, block_rows
):
    """Paper-faithful Alg. 6 on CSR: all-gather the (small) CSR triple."""
    n_loc = idx.shape[0]
    me = _flat_axis_index(axis_name)

    def g(x):
        return lax.all_gather(x, axis_name, axis=0, tiled=True)

    return sparse_similarity_topk(
        SparseCorpus(idx, val, nnz, m),
        SparseCorpus(g(idx), g(val), g(nnz), m),
        threshold,
        k,
        block_rows=min(block_rows, n_loc),
        exclude_self=True,
        row_offset=me * n_loc,
        vary_axes=(axis_name,),
    )


def _sparse_horizontal_ring(
    idx, val, nnz, *, m, threshold, k, axis_name, p, block_rows
):
    """Ring schedule on CSR: the traveling block is the CSR triple, so each
    hop moves ``O(n_loc · cap)`` words instead of ``O(n_loc · m)``."""
    n_loc = idx.shape[0]
    me = lax.axis_index(axis_name)
    row_off = me * n_loc
    bs = min(block_rows, n_loc)
    loc = SparseCorpus(idx, val, nnz, m)

    def compute(buf, s, matches):
        src = jnp.mod(me - s, p)
        m_new = sparse_similarity_topk(
            loc, SparseCorpus(*buf, m), threshold, k,
            block_rows=bs, exclude_self=True,
            row_offset=row_off, col_offset=src * n_loc,
            vary_axes=(axis_name,),
        )
        return merge_matches(matches, m_new)

    def step(s, carry):
        buf, matches = carry
        nxt = jax.tree.map(
            lambda x: lax.ppermute(x, axis_name, perm=_ring_perm(p)), buf
        )
        matches = compute(buf, s, matches)
        return nxt, matches

    matches0 = _pvary(_empty_local_matches(n_loc, k), axis_name)
    buf, matches = lax.fori_loop(0, p - 1, step, ((idx, val, nnz), matches0))
    return compute(buf, p - 1, matches)


def _sparse_horizontal_halfring(
    idx, val, nnz, *, m, threshold, k, axis_name, p, block_rows
):
    """Half-ring on CSR: the traveling CSR triple makes only ⌈(p-1)/2⌉ hops.

    Identical caravan structure to the dense ``_horizontal_halfring`` —
    the S = Sᵀ wire-halving is schedule-level, so the CSR triple rides it
    unchanged: each hop moves ``O(n_loc · cap)`` words (the triple) plus
    the ``O(n_loc · k)`` caravan of backward matches, half as many block
    hops as the sparse ring. Like the dense *kernel* halfring path, the
    two orientations of a cross tile are two sparse joins with swapped
    arguments rather than one score matrix read twice (the blocked sparse
    scorer never materializes the tile's scores to transpose), so compute
    matches the ring while wire traffic halves. Parity with the sparse
    ring is asserted by ``tests/test_sparse.py``.
    """
    n_loc = idx.shape[0]
    me = lax.axis_index(axis_name)
    row_off = me * n_loc
    bs = min(block_rows, n_loc)
    half = p // 2
    loc = SparseCorpus(idx, val, nnz, m)

    def join(Q, C, row_o, col_o):
        return sparse_similarity_topk(
            Q, C, threshold, k, block_rows=bs, exclude_self=True,
            row_offset=row_o, col_offset=col_o, vary_axes=(axis_name,),
        )

    # Step 0: self block.
    matches = join(loc, loc, row_off, row_off)
    if p == 1:
        return matches

    def cross_tile(buf, s, need_bwd=True):
        src = jnp.mod(me - s, p)  # owner of `buf`
        col_off = src * n_loc
        cur = SparseCorpus(*buf, m)
        fwd = join(loc, cur, row_off, col_off)
        if not need_bwd:  # even-p final step: mirror covered forward
            return fwd, None
        bwd = join(cur, loc, col_off, row_off)
        return fwd, bwd

    def hop(x):
        return lax.ppermute(x, axis_name, perm=_ring_perm(p))

    def step(s, carry):
        buf, caravan, mm = carry
        buf = jax.tree.map(hop, buf)
        caravan = jax.tree.map(hop, caravan)
        fwd, bwd = cross_tile(buf, s)
        return buf, merge_matches(caravan, bwd), merge_matches(mm, fwd)

    caravan = _pvary(_empty_local_matches(n_loc, k), axis_name)
    buf, caravan, matches = lax.fori_loop(
        1, half, step, ((idx, val, nnz), caravan, matches)
    )
    # Final offset s = half: forward always; backward only when p is odd
    # (for even p both orientations of the antipodal pair are covered
    # forward, and a backward copy would double-count).
    if p % 2 == 1:
        buf, caravan, matches = step(half, (buf, caravan, matches))
    else:
        buf = jax.tree.map(hop, buf)
        caravan = jax.tree.map(hop, caravan)
        fwd, _ = cross_tile(buf, jnp.int32(half), need_bwd=False)
        matches = merge_matches(matches, fwd)
    # Send the caravan home: its rows belong to device (me - half).
    home = jax.tree.map(
        lambda x: lax.ppermute(x, axis_name, perm=_shift_perm(p, half)),
        caravan,
    )
    return merge_matches(matches, home)


# ---------------------------------------------------------------------------
# 1-D vertical (paper Algs. 3-5): dimensions distributed over `axis_name`
# ---------------------------------------------------------------------------


def apss_vertical(
    D: jax.Array,
    threshold: float,
    k: int,
    mesh: Mesh,
    axis_name: str = "model",
    *,
    accumulation: str = "compressed",
    block_rows: int = 512,
    candidate_capacity: int | None = None,
    return_stats: bool = False,
) -> Matches | tuple[Matches, ApssStats]:
    """Distributed APSS with dimension (feature) sharding.

    ``D (n, m)`` global; columns sharded over ``axis_name``; every device sees
    all rows in an ``m/p`` dimension slice and computes *partial* scores which
    are then accumulated (paper's score-accumulation phase).

    ``D`` may be a :class:`~repro.core.sparse.SparseCorpus`: dimension
    sharding then splits the **inverted index** — each device owns the
    posting lists dealt to it by frequency (dealt and packed on the mesh,
    sized on the host, so the sparse entry is not traceable), rows are
    padded with empty rows to a
    multiple of ``block_rows`` (never matched, sliced off the answer), and
    partials come from the sparse gather-dot primitive. All four
    accumulations apply unchanged: they only ever see the ``(block, n)``
    partial-score tiles.
    """
    if isinstance(D, SparseCorpus):
        return _apss_vertical_sparse(
            D, threshold, k, mesh, axis_name,
            accumulation=accumulation, block_rows=block_rows,
            candidate_capacity=candidate_capacity, return_stats=return_stats,
        )
    n = D.shape[0]

    def make_partials(D_loc):
        return functools.partial(_partial_scores, D_loc, block_rows=block_rows)

    out = _vertical_dispatch(
        D, make_partials, n, threshold, k, mesh, axis_name,
        accumulation=accumulation, block_rows=block_rows,
        candidate_capacity=candidate_capacity, return_stats=return_stats,
        in_specs=P(None, axis_name), strict_vma=True,
    )
    if telemetry.enabled():
        p = mesh.shape[axis_name]
        C = candidate_capacity or default_candidate_capacity(k)
        telemetry.record(telemetry.ApssStats(
            variant=f"vertical/{accumulation}",
            n=n, m=D.shape[1], devices=p, block_rows=block_rows, sparse=False,
            hops=telemetry.vertical_hops(
                accumulation, str(axis_name), p, n, block_rows, C
            ),
            flops=telemetry.dense_join_flops(n, n, D.shape[1]) / p,
            extra={"capacity": C},
        ))
    return out


def _vertical_dispatch(
    args, make_partials, n, threshold, k, mesh, axis_name, *,
    accumulation, block_rows, candidate_capacity, return_stats, in_specs,
    strict_vma, n_valid=None,
):
    """Shared accumulation dispatch for dense and sparse vertical inputs.

    ``make_partials(*local_args) -> (blk -> (block_rows, n) partials)``
    builds the per-device partial-score closure; everything downstream
    (Lemma-1 compaction, flat/recursive accumulation) is representation-
    agnostic. Columns from ``n_valid`` on are padding rows: their partials
    read ``-inf``, so they never match or count.
    """
    p = mesh.shape[axis_name]
    C = candidate_capacity or default_candidate_capacity(k)
    if n % block_rows != 0:
        raise ValueError(f"n={n} must be a multiple of block_rows={block_rows}")
    args = args if isinstance(args, tuple) else (args,)
    nb = n // block_rows
    n_valid = n if n_valid is None else n_valid

    def local_partials(*local):
        partials = make_partials(*local)
        real = jnp.arange(n) < n_valid

        def scoped(blk):
            with jax.named_scope("vertical/partials"):
                A = partials(blk)
                return A if n_valid == n else jnp.where(real, A, NEG_INF)

        return scoped

    replicated = Matches(values=P(), indices=P(), counts=P())
    all_exact = ApssStats(
        overflow_rows=jnp.int32(0), blocks_pruned=jnp.int32(0),
        blocks_exact=jnp.int32(nb),
    )
    if accumulation == "allreduce":
        def fn(*local):
            return _vertical_allreduce(
                local_partials(*local), n, threshold=threshold, k=k,
                axis_name=axis_name, block_rows=block_rows,
            )
        out = shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=replicated,
            check_vma=strict_vma,
        )(*args)
        stats = all_exact
    elif accumulation == "scatter":
        if block_rows % p != 0:
            raise ValueError("scatter accumulation needs block_rows % p == 0")
        def fn(*local):
            return _vertical_scatter(
                local_partials(*local), n, threshold=threshold, k=k,
                axis_name=axis_name, p=p, block_rows=block_rows,
            )
        stacked = shard_map(
            fn, mesh=mesh, in_specs=in_specs,
            out_specs=Matches(
                values=P(None, axis_name, None),
                indices=P(None, axis_name, None),
                counts=P(None, axis_name),
            ),
            check_vma=strict_vma,
        )(*args)
        out = jax.tree.map(lambda x: x.reshape(n, *x.shape[2:]), stacked)
        stats = all_exact
    elif accumulation in ("compressed", "recursive"):
        if accumulation == "recursive" and p & (p - 1):
            raise ValueError("recursive accumulation needs power-of-two shards")
        accumulate = (
            _vertical_compressed if accumulation == "compressed"
            else _vertical_recursive
        )
        def fn(*local):
            return accumulate(
                local_partials(*local), n, threshold=threshold, k=k,
                axis_name=axis_name, p=p, block_rows=block_rows, capacity=C,
            )
        # NOTE: outputs are value-replicated (all devices compute the same
        # candidate union and psum-accumulated scores) but the static VMA
        # checker cannot see through all_gather-derived indexing; verified
        # numerically by tests instead.
        out, stats = shard_map(
            fn, mesh=mesh, in_specs=in_specs,
            out_specs=(replicated, ApssStats(P(), P(), P())),
            check_vma=False,
        )(*args)
    else:
        raise ValueError(f"unknown vertical accumulation: {accumulation}")

    if return_stats:
        return out, stats
    return out


# All but this share of the rows fit a shard's row width; the entries past
# it, of the few longest rows, ride a spill list instead of widening every
# row (the partial tile's gathers scale with the width).
SPILL_SHARE = 1e-3
_GATHER_CHUNK = 32  # the partial tiles' gather_dot chunk; widths are multiples
_SPILL_QUANTUM = 128


@functools.partial(jax.jit, static_argnames=("m", "p"))
def _deal_on_device(idx, nnz, *, m, p):
    """Deal the dimensions to ``p`` shards as :func:`~repro.core.sparse.deal_dims`
    does (round-robin by posting-list length, ties by id) and count each
    row's entries on each shard. Returns the ``(n, cap)`` shard of every
    slot (``p`` for padding slots), its shard-local dimension id, and the
    ``(n, p)`` counts."""
    n, cap = idx.shape
    valid = jnp.arange(cap)[None, :] < nnz[:, None]
    postings = jnp.zeros((m,), jnp.int32).at[jnp.where(valid, idx, m)].add(
        1, mode="drop"
    )
    rank = jnp.zeros((m,), jnp.int32).at[jnp.argsort(-postings, stable=True)].set(
        jnp.arange(m, dtype=jnp.int32)
    )
    shard = jnp.where(valid, rank[idx] % p, p)
    counts = jnp.stack([(shard == d).sum(axis=1) for d in range(p)], axis=1)
    return shard, rank[idx] // p, counts


def _cut_width(counts: np.ndarray) -> tuple[int, int]:
    """The shards' row width ``W`` and spill length ``E`` from the ``(n, p)``
    counts. ``W`` fits all but the longest ``SPILL_SHARE`` of the rows,
    rounded up to ``gather_dot``'s chunk, so that it does not follow the
    longest row: that moves from corpus to corpus, and across a chunk
    boundary adds a third to the gathers. ``E`` holds the most entries any
    shard has past ``W``, rounded up to ``_SPILL_QUANTUM``."""
    q = int(np.quantile(counts.max(axis=1), 1.0 - SPILL_SHARE, method="higher"))
    width = max(1, -(-q // _GATHER_CHUNK) * _GATHER_CHUNK)
    most = int(np.maximum(counts - width, 0).sum(axis=0).max())
    return width, _SPILL_QUANTUM * max(1, -(-most // _SPILL_QUANTUM))


@functools.partial(
    jax.jit, static_argnames=("width", "spill", "n_pad", "mesh", "axis_name")
)
def _pack_on_device(shard, local, val, *, width, spill, n_pad, mesh, axis_name):
    """Each chip packs its own shard from the replicated slots. One stable
    sort moves a row's slots of the chip's shard to its front in stored
    order; the first ``width`` form the ``(n_pad, width)`` rows (rows past
    ``n`` empty), and the rest go to the ``(spill,)`` list ``(row, idx,
    val)`` in row-major order, padded with inert ``(0, 0, 0.0)``. Returns
    the ``(p, n_pad, width)`` stacks and the three ``(p, spill)`` lists,
    sharded over ``axis_name``."""

    def pack(shard, local, val):
        n, cap = shard.shape
        mine = shard == lax.axis_index(axis_name)
        _, idx, val = lax.sort(
            ((~mine).astype(jnp.int32), local, val.astype(jnp.float32)),
            dimension=1, is_stable=True, num_keys=1,
        )
        grow = ((0, 0), (0, max(width - cap, 0)))
        idx, val = jnp.pad(idx, grow), jnp.pad(val, grow)
        count = mine.sum(axis=1)[:, None]
        slot = jnp.arange(idx.shape[1])[None, :]
        kept = slot[:, :width] < count
        rows = ((0, n_pad - n), (0, 0))
        out_idx = jnp.pad(jnp.where(kept, idx[:, :width], 0), rows)
        out_val = jnp.pad(jnp.where(kept, val[:, :width], 0.0), rows)
        past = (slot >= width) & (slot < count)
        r, c = jnp.nonzero(past, size=spill, fill_value=0)
        live = jnp.arange(spill) < past.sum()
        spilled = (
            jnp.where(live, r, 0), jnp.where(live, idx[r, c], 0),
            jnp.where(live, val[r, c], 0.0),
        )
        return tuple(a[None] for a in (out_idx, out_val, *spilled))

    shards, spills, everywhere = P(axis_name, None, None), P(axis_name, None), P()
    return shard_map(
        pack, mesh=mesh, in_specs=(everywhere,) * 3,
        out_specs=(shards, shards, spills, spills, spills),
    )(shard, local, val)


def _vertical_sparse_split(D: SparseCorpus, block_rows: int, mesh, axis_name):
    """Split stage of the sparse vertical path, on the mesh. The corpus,
    replicated over the mesh (put there if it is not), is dealt and counted
    on the devices (:func:`_deal_on_device`); the host reads the ``(n, p)``
    counts to fix the width and the spill length (:func:`_cut_width`); each
    chip packs its own shard, rows padded with empty rows to a multiple of
    ``block_rows`` (:func:`_pack_on_device`). The stacks equal
    :func:`~repro.core.sparse.shard_dims`' cut to the width, with the cut
    entries in the spill. Returns ``(idx_s, val_s, spill, shard_nnz,
    m_loc)``: ``(p, n_pad, W)`` local indices and values and the three
    ``(p, E)`` spill arrays, sharded over ``axis_name``, and the nonzeros
    of each shard."""
    p = mesh.shape[axis_name]
    m_loc = -(-D.m // p)
    n_pad = D.n + (-D.n) % block_rows
    everywhere = NamedSharding(mesh, P())
    idx, val, nnz = (
        jax.device_put(a, everywhere) for a in (D.indices, D.values, D.nnz)
    )
    with trace.span("vertical/shard", p=p):
        shard, local, counts = _deal_on_device(idx, nnz, m=D.m, p=p)
        counts = np.asarray(counts)  # the host's one read of the split
        width, spill = _cut_width(counts)
        shard_nnz = counts.sum(axis=0)
        trace.annotate(
            m_loc=m_loc, cap_loc=width,
            spilled=int(np.maximum(counts - width, 0).sum()),
            nnz_max=int(shard_nnz.max()), nnz_mean=float(shard_nnz.mean()),
        )
    with trace.span("vertical/pad", n=D.n, n_pad=n_pad):  # the pack pads
        idx_s, val_s, *spilled = _pack_on_device(
            shard, local, val, width=width, spill=spill, n_pad=n_pad,
            mesh=mesh, axis_name=axis_name,
        )
    return idx_s, val_s, tuple(spilled), shard_nnz, m_loc


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_valid", "m_loc", "threshold", "k", "mesh", "axis_name",
        "accumulation", "block_rows", "candidate_capacity", "return_stats",
    ),
)
def _vertical_sparse_post_split(
    idx_s, val_s, spill_row, spill_idx, spill_val, *, n_valid, m_loc,
    threshold, k, mesh, axis_name, accumulation, block_rows,
    candidate_capacity, return_stats,
):
    """Everything AFTER the split (:func:`_vertical_sparse_split`) — one
    jitted program, so a join of the same shapes compiles once (the
    compile audit AOT-compiles the sparse vertical family through this
    seam; the public entry stays staged because the host sizes the split
    from the data). Returns the ``n_valid`` real rows' matches.

    A partial tile is ``gather_dot`` over the shard's ``(n, W)`` rows plus
    the spill list's entries, which are added to the query block's dense
    rows and, gathered, to their columns.
    """
    n, width = idx_s.shape[1:]
    ncb = n // block_rows  # divisibility validated by _vertical_dispatch

    def make_partials(idxL, valL, rowS, idxS, valS):
        # shard dim (1, ...) → local
        idxL, valL, rowS, idxS, valS = (
            a[0] for a in (idxL, valL, rowS, idxS, valS)
        )
        sp_loc = SparseCorpus(idxL, valL, jnp.zeros((n,), jnp.int32), m_loc)
        Ci = idxL.reshape(ncb, block_rows, width)
        Cv = valL.reshape(ncb, block_rows, width)

        def partials(blk):
            base = blk * block_rows
            qd = densify_rows(sp_loc, base, block_rows)
            here = (rowS >= base) & (rowS < base + block_rows)
            qd = qd.at[jnp.where(here, rowS - base, block_rows), idxS].add(
                valS, mode="drop"
            )

            def chunk(_, ci):
                return _, gather_dot(qd, Ci[ci], Cv[ci], chunk=_GATHER_CHUNK)

            _, ss = lax.scan(chunk, 0, jnp.arange(ncb))  # (ncb, b, block)
            A = jnp.moveaxis(ss, 0, 1).reshape(block_rows, n)
            return A.at[:, rowS].add(qd[:, idxS] * valS)

        return partials

    shards = P(axis_name, None, None)
    spills = P(axis_name, None)
    out, stats = _vertical_dispatch(
        (idx_s, val_s, spill_row, spill_idx, spill_val), make_partials, n,
        threshold, k, mesh, axis_name,
        accumulation=accumulation, block_rows=block_rows,
        candidate_capacity=candidate_capacity, return_stats=True,
        in_specs=(shards, shards, spills, spills, spills),
        # The VMA checker has no rule for the scatter/gather ops inside the
        # sparse partial-score primitive; verified numerically by tests.
        strict_vma=False, n_valid=n_valid,
    )
    out = jax.tree.map(lambda x: x[:n_valid], out)
    return (out, stats) if return_stats else out


def _apss_vertical_sparse(
    D: SparseCorpus, threshold, k, mesh, axis_name, *,
    accumulation, block_rows, candidate_capacity, return_stats,
):
    p = mesh.shape[axis_name]
    n = D.n
    C = candidate_capacity or default_candidate_capacity(k)
    idx_s, val_s, spill, shard_nnz, m_loc = _vertical_sparse_split(
        D, block_rows, mesh, axis_name
    )
    n_pad, width = idx_s.shape[1:]
    with trace.span(
        "vertical/dispatch", accumulation=accumulation, capacity=C,
        block_rows=block_rows,
    ):
        out, stats = _vertical_sparse_post_split(
            idx_s, val_s, *spill, n_valid=n, m_loc=m_loc, threshold=threshold, k=k, mesh=mesh,
            axis_name=axis_name, accumulation=accumulation,
            block_rows=block_rows, candidate_capacity=candidate_capacity,
            return_stats=True,
        )
        routes = {}
        if telemetry.enabled():
            # waits for the join: the counts exist once it has run
            routes = {
                "blocks_pruned": int(stats.blocks_pruned),
                "blocks_exact": int(stats.blocks_exact),
            }
            trace.annotate(**routes)
    if telemetry.enabled():
        telemetry.record(telemetry.ApssStats(
            variant=f"vertical/{accumulation}",
            n=n, m=D.m, devices=p, block_rows=block_rows, sparse=True,
            hops=telemetry.vertical_hops(
                accumulation, str(axis_name), p, n_pad, block_rows, C
            ),
            flops=telemetry.sparse_join_flops(n_pad, n_pad, width),
            extra={
                "capacity": C, "cap_loc": width, "n_pad": n_pad,
                "shard_nnz": [int(x) for x in shard_nnz], **routes,
            },
        ))
    if return_stats:
        return out, stats
    return out


def _partial_scores(D_loc, blk, block_rows):
    """Partial similarity of one query row block in the local dim slice."""
    q = lax.dynamic_slice_in_dim(D_loc, blk * block_rows, block_rows, axis=0)
    return jnp.einsum(
        "im,jm->ij", q, D_loc,
        precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _allreduce_block(A, blk, *, threshold, k, axis_name, block_rows):
    """Exact matches of one query block: the all-reduce of its whole
    ``(block_rows, n)`` partial tile."""
    S = lax.psum(A, axis_name)
    return extract_matches(
        S, threshold, k, row_offset=blk * block_rows, exclude_self=True
    )


def _vertical_allreduce(partials_fn, n, *, threshold, k, axis_name, block_rows):
    """vertical-noopt: all-reduce the full dense score block (paper baseline)."""
    nb = n // block_rows

    def body(_, blk):
        A = partials_fn(blk)
        with jax.named_scope("vertical/accumulate"):
            m = _allreduce_block(
                A, blk, threshold=threshold, k=k, axis_name=axis_name,
                block_rows=block_rows,
            )
        return _, m

    _, ms = lax.scan(body, None, jnp.arange(nb))
    return jax.tree.map(lambda x: x.reshape(n, *x.shape[2:]), ms)


def _vertical_scatter(partials_fn, n, *, threshold, k, axis_name, p, block_rows):
    """Paper §5.1.7 flat accumulation: scores reduced AND partitioned."""
    nb = n // block_rows
    rows_per_dev = block_rows // p
    me = lax.axis_index(axis_name)

    def body(_, blk):
        A = partials_fn(blk)  # (b, n)
        with jax.named_scope("vertical/accumulate"):
            S_slice = lax.psum_scatter(
                A, axis_name, scatter_dimension=0, tiled=True
            )
            m = extract_matches(
                S_slice, threshold, k,
                row_offset=blk * block_rows + me * rows_per_dev,
                exclude_self=True,
            )
        return _, m

    _, ms = lax.scan(body, None, jnp.arange(nb))
    return ms  # stacked (nb, rows_per_dev, ...) per device


def _overflow_rows(A, t_local, capacity):
    """Rows of ``A`` with more Lemma-1 candidates (partials ``≥ t/p``)
    than the capacity holds."""
    cc = min(capacity, A.shape[-1])
    n_cand = jnp.sum(A >= t_local, axis=-1, dtype=jnp.int32)
    return jnp.sum(n_cand > cc, dtype=jnp.int32)


def _local_candidates(A, t_local, capacity):
    """Top-`capacity` local candidates at the Lemma-1 threshold ``t/p``."""
    masked = jnp.where(A >= t_local, A, NEG_INF)
    cc = min(capacity, A.shape[-1])
    c_val, c_idx = lax.top_k(masked, cc)
    c_idx = jnp.where(c_val > NEG_INF, c_idx, -1).astype(jnp.int32)
    return c_val, c_idx, _overflow_rows(A, t_local, capacity)


def _rescore_candidates(A, c_idx, blk, *, threshold, k, axis_name, block_rows):
    """Exact matches at the union of every shard's candidate ids: a small
    all-gather of the ids, then one psum of the partials there."""
    all_idx = lax.all_gather(c_idx, axis_name, axis=1, tiled=True)  # (b, p*C)
    safe = jnp.maximum(all_idx, 0)
    mine = jnp.take_along_axis(A, safe, axis=1)
    mine = jnp.where(all_idx >= 0, mine, 0.0)
    total = lax.psum(mine, axis_name)  # exact scores at the union
    return matches_from_candidates(
        total, all_idx, threshold, k,
        row_offset=blk * block_rows, exclude_self=True, dedupe=True,
    )


def _pruned_or_exact(A, overflow, candidates, blk, **kw):
    """One query block's matches, and whether it took the exact route.

    Lemma-1 pruning is exact only while no shard's candidates overflow the
    capacity. Where any shard's do (``overflow`` is this shard's count of
    such rows), the block is scored by the all-reduce of its whole partial
    tile instead; every shard takes the same branch. ``candidates()``
    gives this shard's candidate ids, on the pruned route only.
    """
    exact = lax.pmax(overflow, kw["axis_name"]) > 0
    m = lax.cond(
        exact,
        lambda: _allreduce_block(A, blk, **kw),
        lambda: _rescore_candidates(A, candidates(), blk, **kw),
    )
    return m, exact.astype(jnp.int32)


def _route_stats(overflow, n_exact, nb, axis_name) -> ApssStats:
    # Overflow counts are device-local; expose the global max.
    return ApssStats(
        overflow_rows=lax.pmax(overflow, axis_name),
        blocks_pruned=nb - n_exact,
        blocks_exact=n_exact,
    )


def _vertical_compressed(
    partials_fn, n, *, threshold, k, axis_name, p, block_rows, capacity
):
    """Local pruning (Lemma 1) + candidate compaction (paper §5.1.3-5.1.4).

    Per query block: threshold partials at ``t/p``; compact to top-C
    ``(idx, val)``; all-gather the candidate ids (volume p·C « n); every
    device contributes its partial at the union via one small psum; filter
    exactly at ``t``. Matches paper's two-step accumulate: candidate-set
    union (Reduce-All ∪) then parallel score addition. A block whose
    candidates overflow C on any device is all-reduced whole instead
    (:func:`_pruned_or_exact`); the overflow is counted before the top-C
    selection, which only the pruned route runs.
    """
    nb = n // block_rows
    t_local = local_threshold(threshold, p)
    kw = dict(threshold=threshold, k=k, axis_name=axis_name,
              block_rows=block_rows)

    def body(carry, blk):
        A = partials_fn(blk)  # (b, n) partials
        with jax.named_scope("vertical/accumulate"):
            overflow = _overflow_rows(A, t_local, capacity)
            m, exact = _pruned_or_exact(
                A, overflow, lambda: _local_candidates(A, t_local, capacity)[1],
                blk, **kw,
            )
        return (carry[0] + overflow, carry[1] + exact), m

    zero = _pvary(jnp.int32(0), axis_name)
    (overflow, n_exact), ms = lax.scan(body, (zero, zero), jnp.arange(nb))
    out = jax.tree.map(lambda x: x.reshape(n, *x.shape[2:]), ms)
    return out, _route_stats(overflow, n_exact, nb, axis_name)


def _pairwise_merge_candidates(idx_a, val_a, ub_a, idx_b, val_b, ub_b, capacity):
    """Merge two per-row candidate lists, summing values on shared indices.

    Inputs are ``(rows, C)`` each; at most two copies of any index exist, so a
    sort + adjacent-combine is exact. Keeps the top-`capacity` by upper bound.
    """
    idx = jnp.concatenate([idx_a, idx_b], axis=-1)
    val = jnp.concatenate([val_a, val_b], axis=-1)
    ub = jnp.concatenate([ub_a, ub_b], axis=-1)
    order = jnp.argsort(idx, axis=-1)
    idx = jnp.take_along_axis(idx, order, axis=-1)
    val = jnp.take_along_axis(val, order, axis=-1)
    ub = jnp.take_along_axis(ub, order, axis=-1)
    nxt_same = jnp.concatenate(
        [idx[:, 1:] == idx[:, :-1], jnp.zeros_like(idx[:, :1], bool)], axis=-1
    )
    prv_same = jnp.concatenate(
        [jnp.zeros_like(idx[:, :1], bool), idx[:, 1:] == idx[:, :-1]], axis=-1
    )
    # Push duplicates' contribution into the *second* copy, invalidate first.
    val_shift = jnp.concatenate([jnp.zeros_like(val[:, :1]), val[:, :-1]], axis=-1)
    ub_shift = jnp.concatenate([jnp.zeros_like(ub[:, :1]), ub[:, :-1]], axis=-1)
    val = jnp.where(prv_same, val + val_shift, val)
    ub = jnp.where(prv_same, ub + ub_shift, ub)
    dead = nxt_same | (idx < 0)
    ub = jnp.where(dead, NEG_INF, ub)
    sel_ub, sel = lax.top_k(ub, capacity)
    out_idx = jnp.take_along_axis(idx, sel, axis=-1)
    out_val = jnp.take_along_axis(val, sel, axis=-1)
    live = sel_ub > NEG_INF
    # Capacity truncation breaks the exactness argument (an absent candidate
    # no longer implies it was below the level threshold) — count it.
    n_live = jnp.sum(~dead, axis=-1, dtype=jnp.int32)
    overflow = jnp.sum(n_live > capacity, dtype=jnp.int32)
    return (
        jnp.where(live, out_idx, -1),
        jnp.where(live, out_val, 0.0),
        jnp.where(live, sel_ub, NEG_INF),
        overflow,
    )


def _vertical_recursive(
    partials_fn, n, *, threshold, k, axis_name, p, block_rows, capacity
):
    """Recursive local pruning on a hypercube (paper §5.1.5-5.1.6, Alg. 5).

    log₂p pairwise exchanges; at level ℓ (subcube of s=2^{ℓ+1} shards) the
    candidate filter is the subcube threshold ``t·s/p`` (pigeonhole over the
    partition: every true match survives along its strongest branch). To stay
    exact with one-sided candidate knowledge we track an *upper bound*
    ``ub = val + (missing half's threshold)`` and filter on ``ub`` — the
    paper's "completing partial scores" problem solved bound-side. A final
    psum over the (replicated) top-level candidate set yields exact scores;
    a block whose candidates overflowed C at any level on any device is
    all-reduced whole instead (:func:`_pruned_or_exact`).
    """
    nb = n // block_rows
    t = jnp.float32(threshold)
    t_leaf = local_threshold(threshold, p)
    me = lax.axis_index(axis_name)
    levels = p.bit_length() - 1
    kw = dict(threshold=threshold, k=k, axis_name=axis_name,
              block_rows=block_rows)

    def body(carry, blk):
        A = partials_fn(blk)
        with jax.named_scope("vertical/accumulate"):
            c_val, c_idx, overflow = _local_candidates(A, t_leaf, capacity)
            c_ub = jnp.where(c_idx >= 0, c_val, NEG_INF)

            for lvl in range(levels):
                bit = 1 << lvl
                sub_t = t * (2.0 * bit) / p      # threshold of the merged subcube
                half_t = t * float(bit) / p      # missing-half bound
                perm = [(i, i ^ bit) for i in range(p)]
                o_idx, o_val, o_ub = (
                    lax.ppermute(x, axis_name, perm=perm)
                    for x in (c_idx, c_val, c_ub)
                )
                # One-sided candidates get the partner-half headroom added to ub.
                c_ub_adj = jnp.where(c_idx >= 0, c_ub + half_t, NEG_INF)
                o_ub_adj = jnp.where(o_idx >= 0, o_ub + half_t, NEG_INF)
                # Two-sided duplicates: pairwise merge sums val and adjusted ub,
                # double-counting the +half_t headroom — looser but still sound
                # (ub only ever overestimates the true subcube partial).
                m_idx, m_val, m_ub, merge_ovf = _pairwise_merge_candidates(
                    c_idx, c_val, c_ub_adj, o_idx, o_val, o_ub_adj, capacity
                )
                overflow = overflow + merge_ovf
                # A summed pair has ub = ub_a + ub_b + 2*half_t but no missing
                # half: we cannot tell pairs apart post-merge, so keep the looser
                # bound (still sound: ub only ever overestimates).
                keep = m_ub >= sub_t
                c_idx = jnp.where(keep, m_idx, -1)
                c_val = jnp.where(keep, m_val, 0.0)
                c_ub = jnp.where(keep, m_ub, NEG_INF)

            # Top level: candidate ids are level-merged but may still differ per
            # device (capacity effects); take the union once, then exact-rescore
            # — or all-reduce the whole tile where any level overflowed.
            m, exact = _pruned_or_exact(A, overflow, lambda: c_idx, blk, **kw)
        return (carry[0] + overflow, carry[1] + exact), m

    zero = _pvary(jnp.int32(0), axis_name)
    (overflow, n_exact), ms = lax.scan(body, (zero, zero), jnp.arange(nb))
    out = jax.tree.map(lambda x: x.reshape(n, *x.shape[2:]), ms)
    return out, _route_stats(overflow, n_exact, nb, axis_name)


# ---------------------------------------------------------------------------
# 2-D checkerboard (paper Alg. 7)
# ---------------------------------------------------------------------------


def apss_2d(
    D: jax.Array,
    threshold: float,
    k: int,
    mesh: Mesh,
    row_axis: str = "data",
    col_axis: str = "model",
    *,
    accumulation: str = "compressed",
    block_rows: int = 512,
    candidate_capacity: int | None = None,
    return_stats: bool = False,
) -> Matches | tuple[Matches, ApssStats]:
    """2-D distribution: rows over ``row_axis``, dimensions over ``col_axis``.

    Ring over the row axis (horizontal outer loop) composed with vertical
    score accumulation over the column axis per ring step — paper Alg. 7's
    re-use of the vertical algorithm with the row communicator, verbatim in
    mesh-axis form.

    ``D`` may be a :class:`~repro.core.sparse.SparseCorpus`: the checkerboard
    cell ``(i, j)`` holds row shard ``i`` restricted to posting-list slice
    ``j`` (host-side ``shard_dims`` pre-split — see ``_apss_2d_sparse`` for
    the traced-vs-host tradeoff), the per-cell CSR pair rides the row-axis
    ring, and the identical column-axis accumulations apply — both
    representations run the one checkerboard driver through its
    ``partials_fn`` seam (``_checkerboard_sweep``).
    """
    if isinstance(D, SparseCorpus):
        return _apss_2d_sparse(
            D, threshold, k, mesh, row_axis, col_axis,
            accumulation=accumulation, block_rows=block_rows,
            candidate_capacity=candidate_capacity, return_stats=return_stats,
        )
    q = mesh.shape[row_axis]
    r = mesh.shape[col_axis]
    C = candidate_capacity or default_candidate_capacity(k)

    ticker = None
    if telemetry.enabled():
        from repro.distributed.straggler import StepTicker

        ticker = StepTicker()
        n, m = D.shape
        n_loc = n // q
        bs = min(block_rows, n_loc)
        while n_loc % bs:  # mirror _apss_2d_local's block clamp
            bs -= 1
        telemetry.record(telemetry.ApssStats(
            variant=f"2d/{accumulation}",
            n=n, m=m, devices=q * r, block_rows=bs, sparse=False,
            hops=telemetry.twod_hops(
                q, r, str(row_axis), str(col_axis), n_loc, m,
                _wire_itemsize(D.dtype), bs, C, accumulation,
            ),
            flops=telemetry.dense_join_flops(n_loc, n, m) / r,
            extra={"mesh": {str(row_axis): q, str(col_axis): r}},
            step_ticker=ticker,
        ))

    fn = functools.partial(
        _apss_2d_local,
        threshold=threshold, k=k, row_axis=row_axis, col_axis=col_axis,
        q=q, r=r, block_rows=block_rows, capacity=C, accumulation=accumulation,
        ticker=ticker,
    )
    out, stats = shard_map(
        fn,
        mesh=mesh,
        in_specs=P(row_axis, col_axis),
        out_specs=(
            Matches(
                values=P(row_axis, None),
                indices=P(row_axis, None),
                counts=P(row_axis),
            ),
            ApssStats(overflow_rows=P()),
        ),
        check_vma=False,
    )(D)
    if return_stats:
        return out, stats
    return out


def _accumulate_block_scores(
    A, *, col_axis, r, threshold, k, capacity, accumulation,
    row_offset, col_offset,
):
    """Vertical accumulation of one (rows × cols) partial tile over col_axis."""
    if accumulation == "allreduce":
        S = lax.psum(A, col_axis)
        m = extract_matches(
            S, threshold, k, row_offset=row_offset, col_offset=col_offset,
            exclude_self=True,
        )
        return m, jnp.int32(0)
    if accumulation == "compressed":
        t_local = local_threshold(threshold, r)
        c_val, c_idx, overflow = _local_candidates(A, t_local, capacity)
        all_idx = lax.all_gather(c_idx, col_axis, axis=1, tiled=True)
        safe = jnp.maximum(all_idx, 0)
        mine = jnp.take_along_axis(A, safe, axis=1)
        mine = jnp.where(all_idx >= 0, mine, 0.0)
        total = lax.psum(mine, col_axis)
        # Candidate ids are tile-local columns; globalize before extraction.
        gidx = jnp.where(all_idx >= 0, all_idx + col_offset, -1)
        m = matches_from_candidates(
            total, gidx, threshold, k, row_offset=row_offset,
            exclude_self=True, dedupe=True,
        )
        return m, overflow
    raise ValueError(f"unknown 2-D accumulation: {accumulation}")


def _block_clamp(block_rows: int, n_loc: int) -> int:
    """Largest divisor of ``n_loc`` not exceeding ``block_rows``."""
    bs = min(block_rows, n_loc)
    while n_loc % bs:
        bs -= 1
    return bs


def _checkerboard_sweep(
    partials_fn, buf0, n_loc, *, threshold, k, row_axis, col_axis, q, r,
    bs, capacity, accumulation, ticker=None,
):
    """The one 2-D checkerboard driver both representations run through.

    Ring over ``row_axis`` of an opaque traveling pytree ``buf0`` (a dense
    wire-format cell or a sparse CSR pair); per ring step,
    ``partials_fn(buf, blk) -> (bs, n_loc)`` scores local query block
    ``blk`` against the traveling corpus cell in the local dimension slice
    (einsum or gather-dot — the same seam the vertical dispatch uses), and
    ``_accumulate_block_scores`` composes the column-axis accumulation.

    ``ticker`` (a ``distributed.straggler.StepTicker``) plants one host
    tick per rank per ring step — the dep argument is data computed by the
    step, so the callback cannot be hoisted out of the loop.
    """
    nb = n_loc // bs
    me_r = lax.axis_index(row_axis)
    row_off = me_r * n_loc

    def compute_vs(buf, s, matches, overflow):
        """Match my rows against the row block owned by (me_r - s)."""
        src = jnp.mod(me_r - s, q)
        col_off = src * n_loc

        def body(carry, blk):
            A = partials_fn(buf, blk)
            mm, o = _accumulate_block_scores(
                A, col_axis=col_axis, r=r, threshold=threshold, k=k,
                capacity=capacity, accumulation=accumulation,
                row_offset=row_off + blk * bs, col_offset=col_off,
            )
            return carry + o, mm

        ov, ms = lax.scan(body, jnp.int32(0), jnp.arange(nb))
        m_new = jax.tree.map(lambda x: x.reshape(n_loc, *x.shape[2:]), ms)
        if ticker is not None:
            rank = me_r * r + lax.axis_index(col_axis)
            ticker.emit(s, rank, jnp.sum(m_new.counts) + ov)
        return merge_matches(matches, m_new), overflow + ov

    def step(s, carry):
        buf, matches, overflow = carry
        nxt = jax.tree.map(
            lambda x: lax.ppermute(x, row_axis, perm=_ring_perm(q)), buf
        )
        matches, overflow = compute_vs(buf, s, matches, overflow)
        return nxt, matches, overflow

    matches0 = _pvary(_empty_local_matches(n_loc, k), (row_axis, col_axis))
    buf, matches, overflow = lax.fori_loop(
        0, q - 1, step,
        (buf0, matches0, _pvary(jnp.int32(0), (row_axis, col_axis))),
    )
    matches, overflow = compute_vs(buf, q - 1, matches, overflow)
    overflow = lax.pmax(lax.pmax(overflow, col_axis), row_axis)
    return matches, ApssStats(overflow_rows=overflow)


def _apss_2d_local(
    D_loc, *, threshold, k, row_axis, col_axis, q, r, block_rows,
    capacity, accumulation, ticker=None,
):
    n_loc, _ = D_loc.shape
    bs = _block_clamp(block_rows, n_loc)

    def partials(buf, blk):
        qrows = lax.dynamic_slice_in_dim(D_loc, blk * bs, bs, axis=0)
        return jnp.einsum(
            "im,jm->ij", qrows, _from_wire(buf, D_loc.dtype),
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )

    return _checkerboard_sweep(
        partials, _to_wire(D_loc), n_loc,
        threshold=threshold, k=k, row_axis=row_axis, col_axis=col_axis,
        q=q, r=r, bs=bs, capacity=capacity, accumulation=accumulation,
        ticker=ticker,
    )


def _apss_2d_sparse(
    D: SparseCorpus, threshold, k, mesh, row_axis, col_axis, *,
    accumulation, block_rows, candidate_capacity, return_stats,
):
    """Sparse 2-D checkerboard: sparse row ring ∘ posting-list-sharded
    accumulation (the last cell of the variant matrix).

    The dimension split is a HOST pre-split (``shard_dims``, like the sparse
    vertical path): each checkerboard cell gets slice-relative indices and
    the exact realized per-cell capacity ``cap_loc``, so the traveling CSR
    pair is as narrow as the data allows. The alternative — a traced-side
    split — would have to pad every cell to the GLOBAL row cap (traced
    shapes cannot depend on the data), inflating ring wire volume by
    ``≈ r·cap/cap_loc`` and scoring FLOPs to match; the price of the host
    split is that (like sparse vertical) the entry is not traceable under
    an outer ``jit`` (``planner.plan._has_host_stage``). See DESIGN.md §5.

    Local (Lemma-1) pruning survives the composition: the compressed
    accumulation thresholds per-cell partials at ``t/r`` exactly as the 1-D
    vertical algorithm does, and the per-cell tile bounds stay conservative
    (``core.pruning.checkerboard_live_mask``).
    """
    q = mesh.shape[row_axis]
    r = mesh.shape[col_axis]
    n = D.n
    if n % q:
        raise ValueError(f"n={n} must be a multiple of {row_axis}={q}")
    C = candidate_capacity or default_candidate_capacity(k)
    # Host split: (r, n, cap_loc) slice-relative indices/values + (r, n) nnz.
    idx_s, val_s, nnz_s, m_loc = shard_dims(D, r)
    cap_loc = idx_s.shape[-1]
    n_loc = n // q
    bs = _block_clamp(block_rows, n_loc)

    ticker = None
    if telemetry.enabled():
        from repro.distributed.straggler import StepTicker

        ticker = StepTicker()
        telemetry.record(telemetry.ApssStats(
            variant=f"2d/{accumulation}",
            n=n, m=D.m, devices=q * r, block_rows=bs, sparse=True,
            hops=telemetry.twod_hops(
                q, r, str(row_axis), str(col_axis), n_loc, D.m, 4, bs, C,
                accumulation, cap_loc=cap_loc,
            ),
            flops=telemetry.sparse_join_flops(n_loc, n, cap_loc),
            extra={
                "mesh": {str(row_axis): q, str(col_axis): r},
                "cap_loc": cap_loc,
            },
            step_ticker=ticker,
        ))

    out, stats = _2d_sparse_post_split(
        idx_s, val_s, nnz_s, m_loc=m_loc, threshold=threshold, k=k,
        mesh=mesh, row_axis=row_axis, col_axis=col_axis,
        accumulation=accumulation, block_rows=block_rows,
        candidate_capacity=C, ticker=ticker,
    )
    if return_stats:
        return out, stats
    return out


def _2d_sparse_post_split(
    idx_s, val_s, nnz_s, *, m_loc, threshold, k, mesh, row_axis, col_axis,
    accumulation, block_rows, candidate_capacity, ticker=None,
):
    """Everything AFTER the host ``shard_dims`` split — jit-lowerable, so
    the compile audit can AOT-compile the sparse checkerboard family
    through this seam (mirrors ``_vertical_sparse_post_split``)."""
    q = mesh.shape[row_axis]
    r = mesh.shape[col_axis]
    fn = functools.partial(
        _apss_2d_sparse_local,
        m_loc=m_loc, threshold=threshold, k=k, row_axis=row_axis,
        col_axis=col_axis, q=q, r=r, block_rows=block_rows,
        capacity=candidate_capacity, accumulation=accumulation,
        ticker=ticker,
    )
    # Same VMA caveat as every sparse schedule: no checker rule for the
    # scatter/gather ops inside the sparse tile primitive.
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(col_axis, row_axis, None),
            P(col_axis, row_axis, None),
            P(col_axis, row_axis),
        ),
        out_specs=(
            Matches(
                values=P(row_axis, None),
                indices=P(row_axis, None),
                counts=P(row_axis),
            ),
            ApssStats(overflow_rows=P()),
        ),
        check_vma=False,
    )(jnp.asarray(idx_s), jnp.asarray(val_s), jnp.asarray(nnz_s))


def _apss_2d_sparse_local(
    idx, val, nnz, *, m_loc, threshold, k, row_axis, col_axis, q, r,
    block_rows, capacity, accumulation, ticker=None,
):
    # Shard dims (1, n_loc, cap_loc) / (1, n_loc) → local cell.
    idx, val, nnz = idx[0], val[0], nnz[0]
    n_loc = idx.shape[0]
    bs = _block_clamp(block_rows, n_loc)
    sp_loc = SparseCorpus(idx, val, nnz, m_loc)

    def partials(buf, blk):
        qd = densify_rows(sp_loc, blk * bs, bs)  # (bs, m_loc)
        bi, bv = buf
        return gather_dot(qd, bi, bv)            # (bs, n_loc)

    # The traveling cell is the CSR pair only: scoring sums every slot and
    # padding is inert, so the nnz vector never needs to ride the ring.
    return _checkerboard_sweep(
        partials, (idx, val), n_loc,
        threshold=threshold, k=k, row_axis=row_axis, col_axis=col_axis,
        q=q, r=r, bs=bs, capacity=capacity, accumulation=accumulation,
        ticker=ticker,
    )


# ---------------------------------------------------------------------------
# Multi-pod hierarchical horizontal schedule
# ---------------------------------------------------------------------------


def _nested_ring_sweep(mesh, axes, carry0, join, *, ticker=None, rank=None):
    """Shared N-level nested-ring driver (dense blocks or CSR triples).

    ``carry0 = (buf, owner, matches)``: ``buf`` is an arbitrary pytree that
    hops with its 1-element i32 ``owner`` id; ``join(buf, owner, matches)``
    scores the local rows against the traveling block. The innermost axis
    rings most often; each outer axis hops once per full inner sweep.

    ``ticker`` (with ``rank``, the caller's flat rank) plants one host tick
    per rank per compute — ``∏ sizes`` ticks per rank for a full sweep. A
    traced step counter rides the carry to number them; it is replicated
    (identical on every rank), so it never perturbs the VMA analysis.
    """
    sizes = [mesh.shape[a] for a in axes]

    def compute(carry):
        buf, own, matches, stepno = carry
        matches = join(buf, own, matches)
        if ticker is not None:
            ticker.emit(stepno, rank, jnp.sum(matches.counts))
        return buf, own, matches, stepno + 1

    def hop(carry, axis):
        buf, own, matches, stepno = carry
        perm = _ring_perm(mesh.shape[axis])
        pp = functools.partial(lax.ppermute, axis_name=axis, perm=perm)
        return jax.tree.map(pp, buf), pp(own), matches, stepno

    def sweep(level, carry):
        if level == len(axes):
            return compute(carry)
        axis, p = axes[level], sizes[level]

        def step(_, c):
            c = sweep(level + 1, c)
            return hop(c, axis)

        carry = lax.fori_loop(0, p - 1, step, carry)
        return sweep(level + 1, carry)  # last sub-sweep: no trailing hop

    _, _, matches, _ = sweep(0, (*carry0, jnp.int32(0)))
    return matches


def apss_horizontal_hierarchical(
    D: jax.Array,
    threshold: float,
    k: int,
    mesh: Mesh,
    axes: Sequence[str] = ("pod", "data"),
    *,
    block_rows: int = 512,
    use_kernel: bool = False,
) -> Matches:
    """N-level nested ring for hierarchical interconnects.

    Rows shard over ``axes`` jointly (row-major); the innermost axis rings
    most often (cheap ICI hops), each outer axis hops once per full inner
    sweep — so slow links (pod-to-pod DCN) carry ``∏inner`` fewer transfers
    than they would in a flat ring, each overlapping an entire inner sweep
    of compute.

    The traveling block carries its **owner id** (a 1-element i32 that hops
    with it), which replaces all modular-offset bookkeeping: the column
    offset of the current block is simply ``owner · n_loc``.

    ``D`` may be a :class:`~repro.core.sparse.SparseCorpus`: the CSR triple
    rides the nested ring exactly like the flat sparse ring/halfring — each
    hop moves ``O(n_loc · cap)`` words instead of ``O(n_loc · m)`` — and
    every block pair is scored with the gather-dot sparse tile primitive
    (parity with the sparse ring asserted by ``tests/test_sparse.py``).
    """
    axes = tuple(axes)
    sizes = [mesh.shape[a] for a in axes]
    ptot = 1
    for s in sizes:
        ptot *= s
    if isinstance(D, SparseCorpus) and use_kernel:
        # validate BEFORE the telemetry record: a raising call must not
        # log wire bytes for an execution that never happens
        raise ValueError(
            "sparse use_kernel is the self-join worklist path "
            "(kernels.apss_block.sparse); distributed sparse schedules "
            "score with the XLA gather-dot primitive"
        )

    ticker = None
    if telemetry.enabled():
        from repro.distributed.straggler import StepTicker

        ticker = StepTicker()
        n = D.shape[0]
        n_loc = n // ptot
        sparse_in = isinstance(D, SparseCorpus)
        block_bytes = (
            telemetry.csr_block_bytes(n_loc, D.cap) if sparse_in
            else telemetry.dense_block_bytes(
                n_loc, D.shape[1], _wire_itemsize(D.dtype)
            )
        )
        telemetry.record(telemetry.ApssStats(
            variant="hierarchical",
            n=n, m=D.shape[1], devices=ptot, block_rows=block_rows,
            sparse=sparse_in,
            hops=telemetry.hierarchical_hops(
                tuple(sizes), axes, block_bytes,
                payload="csr_block" if sparse_in else "dense_block",
            ),
            flops=(
                telemetry.sparse_join_flops(n_loc, n, D.cap) if sparse_in
                else telemetry.dense_join_flops(n_loc, n, D.shape[1])
            ),
            extra={"axes": dict(zip(axes, sizes)), "use_kernel": use_kernel},
            step_ticker=ticker,
        ))

    if isinstance(D, SparseCorpus):
        return _sparse_horizontal_hierarchical(
            D, threshold, k, mesh, axes, block_rows=block_rows, ticker=ticker
        )

    def body(D_loc):
        n_loc = D_loc.shape[0]
        bs = min(block_rows, n_loc)
        flat = _flat_axis_index(axes)  # row-major rank over `axes`
        row_off = flat * n_loc

        def join(buf, own, matches):
            m_new = similarity_topk(
                D_loc, _from_wire(buf, D_loc.dtype), threshold, k,
                block_rows=bs, exclude_self=True, row_offset=row_off,
                col_offset=own[0] * n_loc, use_kernel=use_kernel,
            )
            return merge_matches(matches, m_new)

        matches0 = _pvary(_empty_local_matches(n_loc, k), axes)
        return _nested_ring_sweep(
            mesh, axes, (_to_wire(D_loc), flat[None], matches0), join,
            ticker=ticker, rank=flat,
        )

    return shard_map(
        body,
        mesh=mesh,
        in_specs=P(axes, None),
        out_specs=_matches_specs(axes),
        check_vma=not use_kernel,
    )(D)


def _sparse_horizontal_hierarchical(
    D: SparseCorpus, threshold, k, mesh, axes, *, block_rows, ticker=None,
):
    """Nested pod ring on CSR: the sparse twin of the dense hierarchical.

    The traveling block is the CSR triple (plus its owner id), hopping the
    same nested-ring pattern via the shared :func:`_nested_ring_sweep`
    driver — the wire-volume win of the sparse ring (``O(n_loc · cap)``
    words/hop) composed with the hierarchical schedule's hop economy on
    slow links. Every block pair is scored with the fully-traceable blocked
    gather-dot join; exactness and parity with the flat sparse ring are
    asserted by ``tests/test_sparse.py``.
    """
    m = D.m

    def body(idx, val, nnz):
        n_loc = idx.shape[0]
        bs = min(block_rows, n_loc)
        loc = SparseCorpus(idx, val, nnz, m)
        flat = _flat_axis_index(axes)  # row-major rank over `axes`
        row_off = flat * n_loc

        def join(buf, own, matches):
            m_new = sparse_similarity_topk(
                loc, SparseCorpus(*buf, m), threshold, k,
                block_rows=bs, exclude_self=True, row_offset=row_off,
                col_offset=own[0] * n_loc, vary_axes=axes,
            )
            return merge_matches(matches, m_new)

        matches0 = _pvary(_empty_local_matches(n_loc, k), axes)
        return _nested_ring_sweep(
            mesh, axes, ((idx, val, nnz), flat[None], matches0), join,
            ticker=ticker, rank=flat,
        )

    # Same VMA caveat as every sparse schedule: the scatter/gather ops in
    # the sparse tile primitive have no checker rule; verified numerically.
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None), P(axes)),
        out_specs=_matches_specs(axes),
        check_vma=False,
    )(D.indices, D.values, D.nnz)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------


def apss(
    D: jax.Array,
    threshold: float,
    k: int,
    mesh: Mesh,
    *,
    distribution: str = "2d",
    **kwargs,
) -> Matches | tuple[Matches, ApssStats]:
    """Top-level entry: pick a data distribution (the paper's core finding is
    that the best one is dataset-dependent, so all are first-class).

    ``distribution="auto"`` hands the choice to the execution planner
    (``planner.plan_apss``): corpus statistics are sampled, every valid
    ``(variant, block_rows, use_kernel)`` configuration is priced by the
    calibrated cost models, and the cheapest one runs. Extra ``kwargs``
    (``profile=``, ``autotune=``, ``block_rows_choices=`` …) are forwarded
    to the planner.
    """
    # Span wrap covers dispatch (trace time under jit, dispatch+execute in
    # eager callers); per-ring-step child spans arrive via the StepTicker
    # on the ApssStats record each entry point emits inside this span.
    with trace.span("apss", distribution=distribution):
        if distribution == "auto":
            from repro.planner.plan import plan_apss

            return plan_apss(D, threshold, k, mesh, **kwargs).run()
        if distribution == "horizontal":
            return apss_horizontal(D, threshold, k, mesh, **kwargs)
        if distribution == "vertical":
            return apss_vertical(D, threshold, k, mesh, **kwargs)
        if distribution == "2d":
            return apss_2d(D, threshold, k, mesh, **kwargs)
        if distribution == "hierarchical":
            return apss_horizontal_hierarchical(
                D, threshold, k, mesh, **kwargs
            )
        raise ValueError(f"unknown distribution: {distribution}")
