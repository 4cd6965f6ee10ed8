"""CSR tile kernels: the sparse APSS worklist path.

The dense worklist path (``ops.apss_fused_compacted``) still does
``O(bm·bn·m)`` MXU work per live tile — mostly zeros at the paper's
densities. This module adds the sparse twin, built on **per-block support
compaction** (the gather-densify-per-tile form of the paper's partial
indexing):

1. On the device, each row block ``B`` gets its sorted unique dimension
   list ``bdims[B] (S,)`` (``S`` = max support size over blocks,
   lane-padded) and its rows densified onto that list: ``bx[B] (bm, S)``
   (:func:`block_support_gather`). One program marks the dimensions each
   block holds and counts them; the host reads back only the largest
   count, which fixes the static ``S``; a second program ranks the marks
   into ``bdims`` and gathers each block onto its own support. For
   support-coherent corpora ``S « m``.
2. The live-tile worklist comes from ``core.pruning.sparse_block_prune_mask``
   — inverted-index candidacy ∧ maxweight ∧ exact minsize — computed from
   CSR only.
3. Per live tile ``(I, J)``: block ``J``'s CSR rows are gathered onto
   ``bdims[I]`` (XLA) giving ``yg (bn, S)``: each CSR index is looked up in
   block ``I``'s dimension→slot table (one gather; the table is built once
   a join from ``bdims``, see :func:`_slot_table`), then scatter-added.
   Tile scores are the **dense** matmul ``bx[I] · ygᵀ`` — exact, because
   every nonzero of block ``I`` lies inside its own support and dimensions
   outside it contribute zero. MXU work per tile drops from ``O(bm·bn·m)``
   to ``O(bm·bn·S)``.
4. :func:`sparse_tile_candidates_pallas` consumes ``(bx, yg)`` on a
   scalar-prefetched worklist grid (live tile × support chunk, so VMEM
   never holds a whole ``(bm, S)`` block) and emits forward/mirror candidate
   packets exactly like the dense ``apss_tile_candidates_pallas``
   (S = Sᵀ halves work; ``ops.fold_packets`` folds them into ``Matches``).
   ``use_kernel=False`` runs the same tiles through an XLA scan instead —
   that is the production path off-TPU (Pallas interpret mode is a
   debugger, not a backend).

Exactness contract: identical ``match_set``/``counts`` to
``apss_reference`` on the densified corpus (``tests/test_sparse.py``),
duplicates-sum semantics included (duplicate coordinates land in the same
gathered slot and accumulate).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.matches import SCORE_PRECISION, Matches, empty_matches
from repro.core.pruning import sparse_block_prune_mask
from repro.core.sparse import SparseCorpus, pad_rows_sparse
from repro.kernels.apss_block.fused import (
    _rect_tile_packets,
    _tile_dot,
    _tile_packets,
    _topk_sort,
)
from repro.kernels.apss_block.ops import (
    _on_tpu,
    compact_worklist,
    fold_packets,
    fold_ranks,
)
from repro.obs import trace


def block_support_gather(
    sp: SparseCorpus, block_m: int, *, pad_to: int = 128
) -> tuple[jax.Array, jax.Array]:
    """Per-block support compaction, built on the device.

    Returns ``bdims (nb, S)`` — sorted unique dims per row block, padded
    with the sentinel ``m`` (sorts last, matches nothing) — and
    ``bx (nb, bm, S)`` — the block's rows densified onto its own support.
    ``S`` is the largest block support, lane-padded (``pad_to``) for MXU
    alignment. The static ``S`` is the one value the host reads back.
    """
    n = sp.indices.shape[0]
    assert n % block_m == 0, (n, block_m)
    present, widest = _support_presence(
        sp.indices, sp.nnz, block_m=block_m, m=sp.m
    )
    S = -(-max(1, int(widest)) // pad_to) * pad_to
    return _support_build(
        present, sp.indices, sp.values, sp.nnz, block_m=block_m, S=S
    )


def _valid_keys(indices, nnz, m):
    """CSR indices with the slots past each row's ``nnz`` keyed to the
    sentinel ``m``, which no slot table maps into a support."""
    valid = jnp.arange(indices.shape[1])[None, :] < nnz[:, None]
    return jnp.where(valid, indices, m)


@functools.partial(jax.jit, static_argnames=("block_m", "m"))
def _support_presence(indices, nnz, *, block_m, m):
    """Which dimensions each row block holds, ``(nb, m) bool``, and the
    largest count over the blocks (an int32 scalar).

    One block at a time (``lax.map``), each an int32 scatter into a
    ``(1, m + 1)`` row: the TPU compiler takes seconds over the same
    scatter into a bool or a 1-D operand, or vmapped over the blocks.
    """
    with jax.named_scope("support_gather"):
        keys = _valid_keys(indices, nnz, m)
        cap = keys.shape[1]

        def marks(k):
            row = jnp.zeros((1, m + 1), jnp.int32)
            return row.at[jnp.zeros_like(k), k].set(1)[0, :m] > 0

        present = lax.map(marks, keys.reshape(-1, block_m, cap))
        widest = jnp.max(present.sum(1, dtype=jnp.int32), initial=0)
    return present, widest


@functools.partial(jax.jit, static_argnames=("block_m", "S"))
def _support_build(present, indices, values, nnz, *, block_m, S):
    """``bdims (nb, S)`` and ``bx (nb, bm, S)`` from the presence table.

    A dimension's slot is its rank among the block's present dimensions
    (an inclusive count less one); ``bdims[b, s]`` is the first dimension
    whose count passes ``s``, or ``m`` past the block's support. ``bx`` is
    each block gathered onto its own support by :func:`_gather_block`, one
    block at a time like the tile gather.
    """
    nb, m = present.shape
    with jax.named_scope("support_gather"):
        count = jnp.cumsum(present, axis=1, dtype=jnp.int32)
        bdims = jax.vmap(
            lambda c: jnp.searchsorted(c, jnp.arange(1, S + 1, dtype=jnp.int32))
        )(count).astype(jnp.int32)
        slot = jnp.pad(
            jnp.where(present, count - 1, S), ((0, 0), (0, 1)),
            constant_values=S,
        )
        idx = _valid_keys(indices, nnz, m)
        cap = idx.shape[1]
        bx = lax.map(
            lambda a: _gather_block(*a, S),
            (slot, idx.reshape(nb, block_m, cap),
             values.reshape(nb, block_m, cap)),
        )
    return bdims, bx


def _slot_table(bdims: jax.Array, m: int) -> jax.Array:
    """Per-row-block dimension→slot table ``(nb, m + 1) int32``.

    ``slot[b, d]`` is the position of dimension ``d`` in ``bdims[b]``, or
    ``S`` (a miss) where ``d`` is outside block ``b``'s support. ``bdims``
    pads with the sentinel ``m``, hence the extra column: no CSR index
    equals ``m``, so which padded slot lands there does not matter.
    """
    nb, S = bdims.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (nb, S))
    b = jnp.arange(nb, dtype=jnp.int32)[:, None]
    return jnp.full((nb, m + 1), S, jnp.int32).at[b, bdims].set(pos)


def _gather_block(
    slot: jax.Array, idx: jax.Array, val: jax.Array, S: int
) -> jax.Array:
    """Gather one CSR block onto a row block's support → ``(bn, S)``.

    ``slot`` is the row block's slot-table row ``(m + 1,)``. Misses (dims
    outside the support, padding slots) read ``S`` and are dropped;
    duplicate coordinates accumulate.
    """
    pos = jnp.take(slot, idx)  # (bn, cap), S on a miss
    r = jnp.arange(idx.shape[0], dtype=jnp.int32)[:, None]
    out = jnp.zeros((idx.shape[0], S), jnp.float32)
    return out.at[r, pos].add(val.astype(jnp.float32), mode="drop")


# ---------------------------------------------------------------------------
# Pallas kernels: worklist grid (tile t, support chunk s) over
# support-compacted tiles
# ---------------------------------------------------------------------------


def _support_tile(S: int) -> int:
    """Support-axis chunk of the CSR kernels: the largest of 512/256/128
    that divides ``S`` (``S`` itself when it is not lane-aligned).

    The support is a reduction grid axis, like ``kf`` in the dense kernels,
    so VMEM holds ``(block, chunk)`` operand slices and never a whole
    ``(block, S)`` block: at the radikal shape (S = 13,824 at block 256)
    one whole f32 block is 13.5 MiB, and two double-buffered operands
    would want about 54 MiB of VMEM.
    """
    return next((c for c in (512, 256, 128) if S % c == 0), S)


def _sparse_tile_kernel(
    ij_ref,     # scalar-prefetch (2, T) i32 — live (i, j) tile coordinates
    bx_ref,     # (1, bm, bs) — row block densified on its own support
    yg_ref,     # (1, bm, bs) — col block gathered onto the row block support
    fv_ref,     # out (1, bm, k) f32 — forward candidates (tile rows)
    fi_ref,     # out (1, bm, k) i32
    fc_ref,     # out (1, bm, 1) i32
    bv_ref,     # out (1, bm, k) f32 — backward candidates (mirror rows)
    bi_ref,     # out (1, bm, k) i32
    bc_ref,     # out (1, bm, 1) i32
    acc_ref,    # scratch (bm, bm) f32
    *,
    threshold: float,
    k: int,
    block_m: int,
    n_valid: int,
):
    t = pl.program_id(0)
    sc = pl.program_id(1)

    @pl.when(sc == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(bx_ref[0], yg_ref[0])

    @pl.when(sc == pl.num_programs(1) - 1)
    def _emit():
        fv, fi, fc, bv, bi, bc = _tile_packets(
            acc_ref[...], ij_ref[0, t], ij_ref[1, t],
            threshold=threshold, k=k, block_m=block_m, block_n=block_m,
            n_valid=n_valid,
        )
        fv_ref[0] = fv
        fi_ref[0] = fi
        fc_ref[0] = fc
        bv_ref[0] = bv
        bi_ref[0] = bi
        bc_ref[0] = bc


def sparse_tile_candidates_pallas(
    bx: jax.Array,
    yg: jax.Array,
    ij: jax.Array,
    threshold: float,
    k: int,
    *,
    block_m: int,
    n_valid: int,
    interpret: bool = False,
):
    """Per-live-tile candidate packets from support-compacted operands.

    ``bx (nb, bm, S)`` rides the scalar-prefetched row-block index
    ``ij[0, t]``; ``yg (T, bm, S)`` is per-worklist-tile. One ``(bm, S)×(S,
    bm)`` MXU contraction per live tile, accumulated over support chunks
    (:func:`_support_tile`) — the sparse analogue of
    ``apss_tile_candidates_pallas`` with ``S`` in place of ``m``.
    """
    nb, bm, S = bx.shape
    T = ij.shape[1]
    assert yg.shape == (T, bm, S), (yg.shape, (T, bm, S))
    assert ij.shape == (2, T)
    bs = _support_tile(S)

    kernel = functools.partial(
        _sparse_tile_kernel,
        threshold=threshold, k=k, block_m=block_m, n_valid=n_valid,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, S // bs),
        in_specs=[
            pl.BlockSpec((1, bm, bs), lambda t, sc, ij: (ij[0, t], 0, sc)),
            pl.BlockSpec((1, bm, bs), lambda t, sc, ij: (t, 0, sc)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, k), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, bm, k), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, bm, 1), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, bm, k), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, bm, k), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, bm, 1), lambda t, sc, ij: (t, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bm), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, bm, k), jnp.float32),
            jax.ShapeDtypeStruct((T, bm, k), jnp.int32),
            jax.ShapeDtypeStruct((T, bm, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, bm, k), jnp.float32),
            jax.ShapeDtypeStruct((T, bm, k), jnp.int32),
            jax.ShapeDtypeStruct((T, bm, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(ij.astype(jnp.int32), bx, yg)


def _rect_sparse_tile_kernel(
    ij_ref,     # scalar-prefetch (2, T) i32 — live (qi, cj) tile coordinates
    qg_ref,     # (1, bq, bs) — query block gathered onto the corpus support
    bx_ref,     # (1, bm, bs) — corpus block densified on its own support
    fv_ref,     # out (1, bq, k) f32
    fi_ref,     # out (1, bq, k) i32
    fc_ref,     # out (1, bq, 1) i32
    acc_ref,    # scratch (bq, bm) f32
    *,
    threshold: float,
    k: int,
    block_q: int,
    block_c: int,
    nc_valid: int,
):
    t = pl.program_id(0)
    sc = pl.program_id(1)

    @pl.when(sc == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(qg_ref[0], bx_ref[0])

    @pl.when(sc == pl.num_programs(1) - 1)
    def _emit():
        fv, fi, fc = _rect_tile_packets(
            acc_ref[...], ij_ref[1, t],
            threshold=threshold, k=k, block_q=block_q, block_c=block_c,
            nc_valid=nc_valid,
        )
        fv_ref[0] = fv
        fi_ref[0] = fi
        fc_ref[0] = fc


def rect_sparse_tile_candidates_pallas(
    qg: jax.Array,
    bx: jax.Array,
    ij: jax.Array,
    threshold: float,
    k: int,
    *,
    block_q: int,
    block_c: int,
    nc_valid: int,
    interpret: bool = False,
):
    """Rectangular (query × sparse-corpus) per-live-tile forward packets.

    The serving twin of :func:`sparse_tile_candidates_pallas`: scoring a
    dense query block against a CSR corpus block reduces to the dense tile
    contraction ``qg · bxᵀ`` over the corpus block's OWN support — exact
    because every corpus nonzero lies inside its block support and query
    components outside it multiply stored zeros. ``qg (T, bq, S)`` is
    per-worklist-tile (the query rows' components at ``bdims[cj]``,
    gathered in XLA); ``bx (nb, bm, S)`` rides the scalar-prefetched
    corpus-block index. Forward packets only — no mirror, no self-pairs.
    The support axis is a reduction grid axis (:func:`_support_tile`).
    """
    nb, bm, S = bx.shape
    T = ij.shape[1]
    assert qg.shape == (T, block_q, S), (qg.shape, (T, block_q, S))
    assert bm == block_c, (bm, block_c)
    assert ij.shape == (2, T)
    bs = _support_tile(S)

    kernel = functools.partial(
        _rect_sparse_tile_kernel,
        threshold=threshold, k=k, block_q=block_q, block_c=block_c,
        nc_valid=nc_valid,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, S // bs),
        in_specs=[
            pl.BlockSpec((1, block_q, bs), lambda t, sc, ij: (t, 0, sc)),
            pl.BlockSpec(
                (1, block_c, bs), lambda t, sc, ij: (ij[1, t], 0, sc)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_q, k), lambda t, sc, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_q, 1), lambda t, sc, ij: (t, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, block_c), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, block_q, k), jnp.float32),
            jax.ShapeDtypeStruct((T, block_q, k), jnp.int32),
            jax.ShapeDtypeStruct((T, block_q, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(ij.astype(jnp.int32), qg, bx)


# ---------------------------------------------------------------------------
# The jitted inner: gather → score → packets (XLA scan or Pallas kernel)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_m", "n_valid", "grid_m", "m", "use_kernel",
        "interpret",
    ),
)
def _sparse_compacted_inner(
    bx, bdims, idxb, valb, ij, *,
    threshold, k, block_m, n_valid, grid_m, m, use_kernel, interpret,
):
    T = ij.shape[1]
    S = bdims.shape[1]
    with jax.named_scope("support_gather"):
        slot = _slot_table(bdims, m)

    def gather_t(t):
        return _gather_block(slot[ij[0, t]], idxb[ij[1, t]], valb[ij[1, t]], S)

    if use_kernel:
        # The kernel consumes per-tile gathered operands as a streamed
        # input, so the (T, bm, S) buffer is materialized; gathering
        # in-kernel would remove it (ROADMAP S4).
        with jax.named_scope("support_gather"):
            _, yg = lax.scan(lambda _, t: (_, gather_t(t)), 0, jnp.arange(T))
        fv, fi, fc, bv, bi, bc = sparse_tile_candidates_pallas(
            bx, yg, ij, float(threshold), k,
            block_m=block_m, n_valid=n_valid, interpret=interpret,
        )
    else:
        # XLA path gathers INSIDE the tile scan: peak extra memory is one
        # (bm, S) tile, never O(T · bm · S).
        def tile(_, t):
            with jax.named_scope("support_gather"):
                yg_t = gather_t(t)
            s = jnp.einsum(
                "rs,cs->rc", bx[ij[0, t]], yg_t,
                precision=SCORE_PRECISION,
                preferred_element_type=jnp.float32,
            )
            return _, _tile_packets(
                s, ij[0, t], ij[1, t],
                threshold=threshold, k=k, block_m=block_m, block_n=block_m,
                n_valid=n_valid, topk=_topk_sort,
            )

        _, (fv, fi, fc, bv, bi, bc) = lax.scan(tile, 0, jnp.arange(T))

    return fold_packets(
        ij, fv, fi, fc[..., 0], bv, bi, bc[..., 0],
        grid_m=grid_m, block_m=block_m, k=k,
    )


def apss_sparse_compacted(
    sp: SparseCorpus,
    threshold: float,
    k: int,
    *,
    block_m: int = 256,
    block_mask: jax.Array | None = None,
    block_ub: jax.Array | None = None,
    use_minsize: bool = True,
    use_kernel: bool = False,
    interpret: bool | None = None,
    lane_pad: int = 128,
) -> Matches:
    """Sparse self-join via inverted-index worklist + CSR tile scoring.

    The sparse twin of ``ops.apss_fused_compacted``: the live mask comes
    from CSR-only bounds (inverted-index candidacy included), the worklist
    is host-compacted (upper-triangular, S = Sᵀ mirrors, upper-bound
    ordered), and each live tile costs ``O(bm² · S)`` instead of
    ``O(bm² · m)``. ``use_kernel`` selects the Pallas worklist kernel
    (TPU; interpret off-TPU) over the jitted XLA scan. Host compaction
    makes the entry non-traceable — same contract as the dense compacted
    path. ``block_mask`` (``(nb, nb)`` LIVE bools over the row-padded
    corpus) skips the internal bound computation when the caller already
    has it (same convention as the dense ``apss_fused``); it must be
    conservative or exactness is lost. ``block_ub`` optionally carries the
    matching tile upper bounds for the adaptive worklist ordering.
    """
    if interpret is None:
        interpret = not _on_tpu()
    n = sp.n
    spp, _ = pad_rows_sparse(sp, block_m)
    grid_m = spp.n // block_m

    if block_mask is not None:
        mask, ub = block_mask, block_ub
    else:
        with trace.span("apss/bounds"):
            mask, ub = sparse_block_prune_mask(
                spp, spp, threshold, block_m, use_minsize=use_minsize,
                return_ub=True,
            )
            mask, ub = np.asarray(mask), np.asarray(ub)
    with trace.span("apss/worklist"):
        wl = compact_worklist(mask, ub)
        live = 0 if wl is None else int(wl.shape[1])
        # the worklist is not padded: every entry is a live tile, and
        # each gives the fold two packets (forward and mirror)
        trace.annotate(
            live=live, total=grid_m * (grid_m + 1) // 2, entries=live,
            fold_slots=grid_m * fold_ranks(2 * live, grid_m + 1),
        )
        if wl is None:
            return empty_matches(n, k)
        ij = jnp.asarray(wl)

    with trace.span("apss/support_gather"):
        bdims, bx = block_support_gather(spp, block_m, pad_to=lane_pad)
        S = bdims.shape[1]
        # lookup_bytes: device memory of the dimension→slot table
        # host_read_bytes: what the build reads back (the widest support)
        trace.annotate(
            blocks=grid_m, block_rows=block_m, support=S,
            support_lookup="table", lookup_bytes=grid_m * (spp.m + 1) * 4,
            host_read_bytes=4,
        )
        if use_kernel:
            trace.annotate(support_chunk=_support_tile(S))
    idxb = spp.indices.reshape(grid_m, block_m, spp.cap)
    valb = spp.values.reshape(grid_m, block_m, spp.cap)
    with trace.span("apss/dispatch"):
        values, indices, counts = _sparse_compacted_inner(
            bx, bdims, idxb, valb, ij,
            threshold=float(threshold), k=k, block_m=block_m, n_valid=n,
            grid_m=grid_m, m=spp.m, use_kernel=use_kernel,
            interpret=interpret,
        )
    return Matches(values=values[:n], indices=indices[:n], counts=counts[:n])
