"""Pallas TPU kernels: streaming fused APSS match extraction.

The seed ``apss_block`` kernel materializes the full thresholded ``n×n``
score matrix in HBM and leaves match extraction to XLA — exactly the memory
behaviour the paper's blocking/pruning lesson says must not scale with n².
The two kernels here keep the dense score tile **VMEM-resident only** and
emit a ``Matches``-shaped ``O(n·k)`` result, so HBM traffic is proportional
to surviving candidates:

1. :func:`apss_fused_pallas` — grid ``(i, j, kf)`` with column tiles scanned
   innermost-but-one per row block. A VMEM running top-k buffer (values,
   global column ids) plus exact per-row match counts persists across the
   ``j`` axis; each tile fuses matmul → threshold → top-k merge → count.
   The ``block_prune_mask`` gates MXU work per tile with ``@pl.when``
   (a pruned tile still burns a pipeline slot — see kernel 2).

2. :func:`apss_tile_candidates_pallas` — **live-tile compaction**: a 1-D
   grid over a dense worklist of live ``(i, j)`` tile coordinates, driven
   by scalar prefetch (``PrefetchScalarGridSpec``), so pruned tiles cost
   zero pipeline slots. The worklist enumerates only upper-triangular tiles
   of the self-join (S = Sᵀ) and the kernel emits per-tile top-k candidate
   packets for BOTH orientations (forward = tile rows, backward = the
   mirrored tile columns), halving MXU work; a small XLA scan folds the
   packets into per-row-block ``Matches`` (``ops.apss_fused_compacted``).

In-kernel top-k uses iterative max-extraction (max / first-argmax / mask),
VPU-only ops that lower on Mosaic — ``lax.top_k``/sort do not. Cost is
``k`` passes over ``(bm, k + bn)`` per tile, « the tile's MXU FLOPs.

VMEM per step (defaults 256×256×512, f32): x+y tiles 1 MB, acc 256 KB,
top-k buffers 2·256·k·4B « 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.matches import SCORE_PRECISION

# Finite stand-in for -inf inside the kernel (keeps Mosaic select/max
# NaN-free); converted to true -inf at the ops boundary. Any real similarity
# is a dot product of normalized rows, |s| « 1e30.
NEG_LARGE = -0.5e30
_VALID = -0.25e30  # values above this are real candidates


def _tile_dot(x, y):
    """``x · yᵀ`` of two row tiles, f32 accumulation at full f32 precision
    (``SCORE_PRECISION``): the one scoring contraction of every kernel."""
    return jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )


def _merge_topk(topv, topi, cand_v, cand_i, k: int):
    """Merge candidate columns into a per-row top-k buffer. Exact.

    ``topv/topi``: ``(bm, kb)`` running buffer (NEG_LARGE / -1 empty slots);
    ``cand_v/cand_i``: ``(bm, c)`` new candidates with *disjoint* ids.
    Returns the new ``(bm, kb)`` buffer holding the k best of the union
    (slots beyond k stay empty). Iterative max-extraction: k rounds of
    row-max, first-position select, mask-out — no sort, no lax.top_k.

    The rounds are a ``fori_loop``, not a Python unroll: unrolled, every
    round's ``(bm, kb + c)`` temporaries stay live at once, which overflows
    the kernel's VMEM stack at k = 32 on a v5e, and k = 64 compiles for
    minutes.
    """
    allv = jnp.concatenate([topv, cand_v], axis=1)
    alli = jnp.concatenate([topi, cand_i], axis=1)
    cols = allv.shape[1]
    colid = jax.lax.broadcasted_iota(jnp.int32, allv.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, topv.shape, 1)

    def round_(r, carry):
        allv, outv, outi = carry
        m = jnp.max(allv, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(allv >= m, colid, cols), axis=1, keepdims=True)
        sel = colid == pos
        idx = jnp.sum(jnp.where(sel, alli, 0), axis=1, keepdims=True)
        valid = m > _VALID
        outv = jnp.where(slot == r, jnp.where(valid, m, NEG_LARGE), outv)
        outi = jnp.where(slot == r, jnp.where(valid, idx, -1), outi)
        return jnp.where(sel, NEG_LARGE, allv), outv, outi

    empty_v, empty_i = _empty_buffers(*topv.shape)
    _, outv, outi = jax.lax.fori_loop(
        0, min(k, cols), round_, (allv, empty_v, empty_i)
    )
    return outv, outi


def _empty_buffers(bm: int, k: int):
    return (
        jnp.full((bm, k), NEG_LARGE, jnp.float32),
        jnp.full((bm, k), -1, jnp.int32),
    )


def _topk_sort(topv, topi, cand_v, cand_i, k: int):
    """``_merge_topk`` contract implemented with ``lax.top_k``.

    Exact same result (k best of the union, NEG_LARGE/-1 empties); for XLA
    consumers only — ``lax.top_k`` does not lower on Mosaic, which is why
    the kernels use the iterative ``_merge_topk`` instead.
    """
    allv = jnp.concatenate([topv, cand_v], axis=1)
    alli = jnp.concatenate([topi, cand_i], axis=1)
    kk = min(k, allv.shape[1])
    v, sel = jax.lax.top_k(allv, kk)
    i = jnp.take_along_axis(alli, sel, axis=1)
    valid = v > _VALID
    v = jnp.where(valid, v, NEG_LARGE)
    i = jnp.where(valid, i, -1)
    kb = topv.shape[1]
    if kk < kb:
        v = jnp.pad(v, ((0, 0), (0, kb - kk)), constant_values=NEG_LARGE)
        i = jnp.pad(i, ((0, 0), (0, kb - kk)), constant_values=-1)
    return v, i


def _tile_packets(
    s, ib, jb, *, threshold: float, k: int, block_m: int, block_n: int,
    n_valid: int, topk=_merge_topk,
):
    """One self-join tile's forward + mirror candidate packets (pure).

    THE single implementation of the exactness-critical packet convention —
    mirror candidate ids are ``grow.T``, NOT ``gcol.T`` (``gcol.T`` holds
    the mirrored row's own id); diagonal tiles emit an empty mirror (a copy
    would double-count). Shared verbatim by the dense worklist kernel
    (``_tile_cand_kernel``), the sparse CSR tile kernel, and the sparse XLA
    worklist scan (``kernels.apss_block.sparse``), so the Pallas and XLA
    paths cannot diverge. Only the top-k *selection primitive* is
    pluggable (``topk``): the default ``_merge_topk`` lowers on Mosaic,
    while XLA consumers pass :func:`_topk_sort` (same contract, faster
    under XLA) — the masking/id/mirror convention is not.

    Diagonal tiles compute and then discard the mirror selection
    (``jnp.where(diag, ...)``) — ~``k·bn·(k+bm)`` VPU ops, « the tile's
    MXU matmul — the deliberate price of keeping this branch-free and
    usable from both Pallas and XLA.

    Returns ``(fv, fi, fc, bv, bi, bc)`` with counts shaped ``(block, 1)``.
    """
    grow = ib * block_m + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    gcol = jb * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = (
        (s >= jnp.float32(threshold))
        & (grow != gcol)
        & (grow < n_valid)
        & (gcol < n_valid)
    )
    empty_v, empty_i = _empty_buffers(block_m, k)
    fv, fi = topk(
        empty_v, empty_i,
        jnp.where(ok, s, NEG_LARGE), jnp.where(ok, gcol, -1), k,
    )
    fc = jnp.sum(ok, axis=1, keepdims=True, dtype=jnp.int32)

    # S = Sᵀ: the same tile scores the mirrored pairs — rows become the
    # y-block's vectors, candidate ids the x-block's. The mirror mask is
    # rebuilt from the transposed f32/i32 operands: Mosaic transposes those
    # but not a bool mask, so ``ok.T`` would not compile for the chip.
    sT, growT, gcolT = s.T, grow.T, gcol.T
    okT = (
        (sT >= jnp.float32(threshold))
        & (growT != gcolT)
        & (growT < n_valid)
        & (gcolT < n_valid)
    )
    diag = ib == jb
    ev, ei = _empty_buffers(block_n, k)
    mv, mi = topk(
        ev, ei,
        jnp.where(okT, sT, NEG_LARGE), jnp.where(okT, growT, -1), k,
    )
    bv = jnp.where(diag, ev, mv)
    bi = jnp.where(diag, ei, mi)
    bc = jnp.where(
        diag,
        jnp.int32(0),
        jnp.sum(okT, axis=1, keepdims=True, dtype=jnp.int32),
    )
    return fv, fi, fc, bv, bi, bc


def _rect_tile_packets(
    s, jb, *, threshold: float, k: int, block_q: int, block_c: int,
    nc_valid: int, topk=_merge_topk,
):
    """One rectangular (query-block × corpus-block) tile's candidate packet.

    The asymmetric sibling of :func:`_tile_packets` for the serving path
    (``serving.query``): queries are NOT corpus members, so there is no
    self-pair to exclude and no S = Sᵀ mirror to emit — forward packets
    only. Column validity (``gcol < nc_valid``) masks corpus row padding;
    query-row padding needs no masking here because padded rows are sliced
    off after the fold (per-row results are independent).

    Returns ``(fv (block_q, k), fi (block_q, k), fc (block_q, 1))``.
    """
    gcol = jb * block_c + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = (s >= jnp.float32(threshold)) & (gcol < nc_valid)
    empty_v, empty_i = _empty_buffers(block_q, k)
    fv, fi = topk(
        empty_v, empty_i,
        jnp.where(ok, s, NEG_LARGE), jnp.where(ok, gcol, -1), k,
    )
    fc = jnp.sum(ok, axis=1, keepdims=True, dtype=jnp.int32)
    return fv, fi, fc


# ---------------------------------------------------------------------------
# Kernel 1: streaming fused extraction, (i, j, kf) grid
# ---------------------------------------------------------------------------


def _fused_kernel(
    mask_ref,   # SMEM (1, 1, nb_c) i32 — live flags of this row block's tiles
    meta_ref,   # SMEM (2,) i32 — [row_offset, col_offset] (dynamic)
    x_ref,      # (bm, bk)
    y_ref,      # (bn, bk)
    v_ref,      # out (bm, k) f32
    i_ref,      # out (bm, k) i32
    c_ref,      # out (bm, 1) i32
    acc_ref,    # scratch (bm, bn) f32
    topv_ref,   # scratch (bm, k) f32
    topi_ref,   # scratch (bm, k) i32
    cnt_ref,    # scratch (bm, 1) i32
    *,
    threshold: float,
    k: int,
    block_m: int,
    block_n: int,
    n_valid_cols: int,
    exclude_self: bool,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kf = pl.program_id(2)
    nj = pl.num_programs(1)
    nkf = pl.num_programs(2)
    live = mask_ref[0, 0, j] != 0

    @pl.when((j == 0) & (kf == 0))
    def _init_row_block():
        topv_ref[...] = jnp.full_like(topv_ref, NEG_LARGE)
        topi_ref[...] = jnp.full_like(topi_ref, -1)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    @pl.when(kf == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += _tile_dot(x_ref[...], y_ref[...])

    @pl.when((kf == nkf - 1) & live)
    def _merge_tile():
        row_off = meta_ref[0]
        col_off = meta_ref[1]
        s = acc_ref[...]
        lcol = j * block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        gcol = lcol + col_off
        ok = (s >= jnp.float32(threshold)) & (lcol < n_valid_cols)
        if exclude_self:
            grow = (
                row_off
                + i * block_m
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            )
            ok &= grow != gcol
        cnt_ref[...] += jnp.sum(ok, axis=1, keepdims=True, dtype=jnp.int32)
        cand_v = jnp.where(ok, s, NEG_LARGE)
        cand_i = jnp.where(ok, gcol, -1)
        newv, newi = _merge_topk(topv_ref[...], topi_ref[...], cand_v, cand_i, k)
        topv_ref[...] = newv
        topi_ref[...] = newi

    @pl.when((j == nj - 1) & (kf == nkf - 1))
    def _emit():
        v_ref[...] = topv_ref[...]
        i_ref[...] = topi_ref[...]
        c_ref[...] = cnt_ref[...]


def apss_fused_pallas(
    x: jax.Array,
    y: jax.Array,
    block_mask: jax.Array,
    meta: jax.Array,
    threshold: float,
    k: int,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    n_valid_cols: int,
    exclude_self: bool = True,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Raw pallas_call; shapes must be tile-divisible (see ops.py wrapper).

    Args:
      x: ``(n_rows, m)`` query rows (padded).
      y: ``(n_cols, m)`` corpus rows (padded).
      block_mask: ``(n_rows/bm, n_cols/bn)`` int32; 0 ⇒ tile provably dead.
      meta: ``(1, 2)`` int32 ``[row_offset, col_offset]`` — global ids of
        ``x[0]`` / ``y[0]`` (dynamic, for self-exclusion + global indices).
      n_valid_cols: number of non-padding rows of ``y`` (static).

    The kernel reads each tile's live flag as a scalar from SMEM, one row
    block's flags at a time (a ``(1, 1)`` VMEM block breaks Mosaic's
    (8, 128) block rule, and the whole ``(nb, nb)`` mask would take SMEM
    quadratic in the block count).

    Returns ``(values (n_rows, k) f32, indices (n_rows, k) i32,
    counts (n_rows, 1) i32)``. Empty slots are ``NEG_LARGE`` / ``-1``.
    """
    n_rows, m = x.shape
    n_cols, m2 = y.shape
    assert m == m2, (m, m2)
    assert n_rows % block_m == 0, (n_rows, block_m)
    assert n_cols % block_n == 0, (n_cols, block_n)
    assert m % block_k == 0, (m, block_k)
    grid = (n_rows // block_m, n_cols // block_n, m // block_k)
    assert block_mask.shape == grid[:2], (block_mask.shape, grid)

    kernel = functools.partial(
        _fused_kernel,
        threshold=threshold, k=k, block_m=block_m, block_n=block_n,
        n_valid_cols=n_valid_cols, exclude_self=exclude_self,
    )
    smem = pltpu.SMEM
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, grid[1]), lambda i, j, kf: (i, 0, 0), memory_space=smem
            ),
            pl.BlockSpec(memory_space=smem),
            pl.BlockSpec((block_m, block_k), lambda i, j, kf: (i, kf)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kf: (j, kf)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i, j, kf: (i, 0)),
            pl.BlockSpec((block_m, k), lambda i, j, kf: (i, 0)),
            pl.BlockSpec((block_m, 1), lambda i, j, kf: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, k), jnp.float32),
            jax.ShapeDtypeStruct((n_rows, k), jnp.int32),
            jax.ShapeDtypeStruct((n_rows, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, k), jnp.float32),
            pltpu.VMEM((block_m, k), jnp.int32),
            pltpu.VMEM((block_m, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(
        block_mask.astype(jnp.int32).reshape(grid[0], 1, grid[1]),
        meta.astype(jnp.int32).reshape(2),
        x, y,
    )


# ---------------------------------------------------------------------------
# Kernel 2: live-tile compacted worklist, 1-D grid via scalar prefetch
# ---------------------------------------------------------------------------


def _tile_cand_kernel(
    ij_ref,     # scalar-prefetch (2, T) i32 — live (i, j) tile coordinates
    x_ref,      # (bm, bk)
    y_ref,      # (bn, bk)
    fv_ref,     # out (1, bm, k) f32 — forward candidates (tile rows)
    fi_ref,     # out (1, bm, k) i32
    fc_ref,     # out (1, bm, 1) i32
    bv_ref,     # out (1, bn, k) f32 — backward candidates (mirror rows)
    bi_ref,     # out (1, bn, k) i32
    bc_ref,     # out (1, bn, 1) i32
    acc_ref,    # scratch (bm, bn) f32
    *,
    threshold: float,
    k: int,
    block_m: int,
    block_n: int,
    n_valid: int,
):
    t = pl.program_id(0)
    kf = pl.program_id(1)
    nkf = pl.num_programs(1)

    @pl.when(kf == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Every worklist tile is live: no @pl.when gate, no wasted pipeline slot.
    acc_ref[...] += _tile_dot(x_ref[...], y_ref[...])

    @pl.when(kf == nkf - 1)
    def _emit():
        fv, fi, fc, bv, bi, bc = _tile_packets(
            acc_ref[...], ij_ref[0, t], ij_ref[1, t],
            threshold=threshold, k=k, block_m=block_m, block_n=block_n,
            n_valid=n_valid,
        )
        fv_ref[0] = fv
        fi_ref[0] = fi
        fc_ref[0] = fc
        bv_ref[0] = bv
        bi_ref[0] = bi
        bc_ref[0] = bc


def _rect_cand_kernel(
    ij_ref,     # scalar-prefetch (2|3, T) i32 — live (qi, cj[, gj]) coords
    x_ref,      # (bq, bk) query tile
    y_ref,      # (bc, bk) corpus tile
    fv_ref,     # out (1, bq, k) f32
    fi_ref,     # out (1, bq, k) i32
    fc_ref,     # out (1, bq, 1) i32
    acc_ref,    # scratch (bq, bc) f32
    *,
    threshold: float,
    k: int,
    block_q: int,
    block_c: int,
    nc_valid: int,
):
    t = pl.program_id(0)
    kf = pl.program_id(1)
    nkf = pl.num_programs(1)

    @pl.when(kf == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(x_ref[...], y_ref[...])

    @pl.when(kf == nkf - 1)
    def _emit():
        # Packet column ids come from the LAST worklist row: for a (2, T)
        # worklist that is the local block id itself; a (3, T) worklist
        # (sharded serving) carries a separate GLOBAL block id so ids and
        # validity are evaluated in global coordinates while the DMA index
        # map still uses the device-local row 1.
        fv, fi, fc = _rect_tile_packets(
            acc_ref[...], ij_ref[ij_ref.shape[0] - 1, t],
            threshold=threshold, k=k, block_q=block_q, block_c=block_c,
            nc_valid=nc_valid,
        )
        fv_ref[0] = fv
        fi_ref[0] = fi
        fc_ref[0] = fc


def rect_tile_candidates_pallas(
    Q: jax.Array,
    C: jax.Array,
    ij: jax.Array,
    threshold: float,
    k: int,
    *,
    block_q: int = 128,
    block_c: int = 256,
    block_k: int = 512,
    nc_valid: int,
    interpret: bool = False,
):
    """Per-live-tile forward packets for the rectangular (serving) join.

    The query-time analogue of :func:`apss_tile_candidates_pallas`: ``ij``
    is the dense ``(2, T)`` worklist of live ``(query_block, corpus_block)``
    coordinates — no upper-triangular structure, no mirror packets (queries
    aren't corpus rows). The serving path bucket-pads ``T`` to a power of
    two so repeat queries never retrace; padding entries are masked at fold
    time (``ops.fold_rect_packets``), so the kernel just computes them.
    """
    nq, m = Q.shape
    nc, m2 = C.shape
    assert m == m2, (m, m2)
    assert nq % block_q == 0 and nc % block_c == 0, (nq, nc, block_q, block_c)
    assert m % block_k == 0, (m, block_k)
    T = ij.shape[1]
    assert ij.shape[0] in (2, 3), ij.shape
    nkf = m // block_k

    kernel = functools.partial(
        _rect_cand_kernel,
        threshold=threshold, k=k, block_q=block_q, block_c=block_c,
        nc_valid=nc_valid,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, nkf),
        in_specs=[
            pl.BlockSpec((block_q, block_k), lambda t, kf, ij: (ij[0, t], kf)),
            pl.BlockSpec((block_c, block_k), lambda t, kf, ij: (ij[1, t], kf)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_q, k), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_q, 1), lambda t, kf, ij: (t, 0, 0)),
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
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ij.astype(jnp.int32), Q, C)


def _rect_ee_cand_kernel(
    ij_ref,     # scalar-prefetch (2, T) i32 — live (qi, cj) tile coordinates
    ub_ref,     # scalar-prefetch (T,) f32 — tile upper bounds (NEG_LARGE on
                # padding)
    x_ref,      # (bq, bk) query tile
    y_ref,      # (bc, bk) corpus tile
    fv_ref,     # out (1, bq, k) f32
    fi_ref,     # out (1, bq, k) i32
    fc_ref,     # out (1, bq, 1) i32
    sk_ref,     # out SMEM (T,) i32 — 1 iff tile t was early-exit skipped
    acc_ref,    # scratch (bq, bc) f32
    topv_ref,   # scratch (nq, k) f32 — running top-k VALUES per query row
    *,
    threshold: float,
    k: int,
    block_q: int,
    block_c: int,
    nc_valid: int,
    nq_valid: int,
):
    t = pl.program_id(0)
    kf = pl.program_id(1)
    nkf = pl.num_programs(1)
    qi = ij_ref[0, t]

    @pl.when((t == 0) & (kf == 0))
    def _init_topv():
        topv_ref[...] = jnp.full_like(topv_ref, NEG_LARGE)

    # Early-exit test (recomputed per kf step — topv only moves at the last
    # kf of a *scored* tile, so every step of tile t sees the same answer):
    # the worklist is ordered by upper bound DESCENDING, so once every live
    # row of this query block already holds k real values ≥ this tile's
    # bound, no candidate in it (value ≤ ub) can enter any top-k buffer —
    # ties lose to the buffer under the stable merge. Padding rows
    # (global row ≥ nq_valid) are excluded or an unfull row would pin the
    # block forever; padding worklist entries carry ub = NEG_LARGE and are
    # always skipped.
    rows_q = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    cur = topv_ref[rows_q, :]
    kth = cur[:, k - 1:k]                                   # (bq, 1)
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )
    kth = jnp.where(rows < nq_valid, kth, -NEG_LARGE)
    skip = jnp.min(kth) >= ub_ref[t]

    @pl.when(~skip & (kf == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(~skip)
    def _accumulate():
        acc_ref[...] += _tile_dot(x_ref[...], y_ref[...])

    @pl.when((kf == nkf - 1) & skip)
    def _emit_neutral():
        fv_ref[0] = jnp.full((block_q, k), NEG_LARGE, jnp.float32)
        fi_ref[0] = jnp.full((block_q, k), -1, jnp.int32)
        fc_ref[0] = jnp.zeros((block_q, 1), jnp.int32)
        sk_ref[t] = jnp.int32(1)

    @pl.when((kf == nkf - 1) & ~skip)
    def _emit():
        fv, fi, fc = _rect_tile_packets(
            acc_ref[...], ij_ref[1, t],
            threshold=threshold, k=k, block_q=block_q, block_c=block_c,
            nc_valid=nc_valid,
        )
        fv_ref[0] = fv
        fi_ref[0] = fi
        fc_ref[0] = fc
        sk_ref[t] = jnp.int32(0)
        dummy = jnp.zeros((block_q, k), jnp.int32)
        merged_v, _ = _merge_topk(cur, dummy, fv, dummy, k)
        topv_ref[rows_q, :] = merged_v


def rect_tile_candidates_early_exit_pallas(
    Q: jax.Array,
    C: jax.Array,
    ij: jax.Array,
    ub: jax.Array,
    threshold: float,
    k: int,
    *,
    block_q: int = 128,
    block_c: int = 256,
    block_k: int = 512,
    nc_valid: int,
    nq_valid: int,
    interpret: bool = False,
):
    """Early-exit-aware variant of :func:`rect_tile_candidates_pallas`.

    Carries a per-query-row running top-k VALUES buffer in VMEM scratch
    across the (sequential) tile axis; a tile whose upper bound ``ub[t]`` is
    beaten by every live row's current k-th value skips its MXU work via
    ``@pl.when`` and emits a neutral packet plus a skip flag. A Pallas grid
    cannot terminate early, so — unlike the XLA while_loop path — skipped
    tiles still occupy pipeline slots; the win is the gated matmul.

    Returns ``(fv, fi, fc, skipped)`` where ``skipped`` is ``(T,)`` i32.
    Exactness contract matches the XLA early-exit fold: top-k values and
    indices are bit-identical to the non-early-exit path; only counts
    beyond k are lost (the caller saturates them at k).
    """
    nq, m = Q.shape
    nc, m2 = C.shape
    assert m == m2, (m, m2)
    assert nq % block_q == 0 and nc % block_c == 0, (nq, nc, block_q, block_c)
    assert m % block_k == 0, (m, block_k)
    T = ij.shape[1]
    assert ij.shape == (2, T)
    nkf = m // block_k

    kernel = functools.partial(
        _rect_ee_cand_kernel,
        threshold=threshold, k=k, block_q=block_q, block_c=block_c,
        nc_valid=nc_valid, nq_valid=nq_valid,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nkf),
        in_specs=[
            pl.BlockSpec(
                (block_q, block_k), lambda t, kf, ij, ub: (ij[0, t], kf)
            ),
            pl.BlockSpec(
                (block_c, block_k), lambda t, kf, ij, ub: (ij[1, t], kf)
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda t, kf, ij, ub: (t, 0, 0)),
            pl.BlockSpec((1, block_q, k), lambda t, kf, ij, ub: (t, 0, 0)),
            pl.BlockSpec((1, block_q, 1), lambda t, kf, ij, ub: (t, 0, 0)),
            # Skip flags stay whole in SMEM (a (1, 1) VMEM block per tile
            # breaks Mosaic's (8, 128) block rule).
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, block_c), jnp.float32),
            pltpu.VMEM((nq, k), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, block_q, k), jnp.float32),
            jax.ShapeDtypeStruct((T, block_q, k), jnp.int32),
            jax.ShapeDtypeStruct((T, block_q, 1), jnp.int32),
            jax.ShapeDtypeStruct((T,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            # Both axes "arbitrary": the running top-k scratch carried across
            # tiles makes the t axis order-dependent (vs "parallel" in the
            # non-early-exit kernel).
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(ij.astype(jnp.int32), ub.astype(jnp.float32).reshape(T), Q, C)


def apss_tile_candidates_pallas(
    D: jax.Array,
    ij: jax.Array,
    threshold: float,
    k: int,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    n_valid: int,
    interpret: bool = False,
):
    """Per-live-tile candidate packets for the self-join (see ops wrapper).

    ``ij`` is the dense ``(2, T)`` worklist of live upper-triangular tile
    coordinates (scalar-prefetched: the (i, j) → DMA index computation runs
    before the kernel body, so the pipeline streams exactly the live tiles
    and nothing else).

    Returns forward packets ``(T, bm, k)×2 + (T, bm, 1)`` and backward
    (mirror) packets ``(T, bn, k)×2 + (T, bn, 1)``. Total output is
    ``O(live_tiles · block · k)`` — candidate-proportional, never n².
    """
    n, m = D.shape
    assert n % block_m == 0 and n % block_n == 0, (n, block_m, block_n)
    assert m % block_k == 0, (m, block_k)
    T = ij.shape[1]
    assert ij.shape == (2, T)
    nkf = m // block_k

    kernel = functools.partial(
        _tile_cand_kernel,
        threshold=threshold, k=k, block_m=block_m, block_n=block_n,
        n_valid=n_valid,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T, nkf),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda t, kf, ij: (ij[0, t], kf)),
            pl.BlockSpec((block_n, block_k), lambda t, kf, ij: (ij[1, t], kf)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m, k), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_m, k), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_m, 1), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_n, k), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_n, k), lambda t, kf, ij: (t, 0, 0)),
            pl.BlockSpec((1, block_n, 1), lambda t, kf, ij: (t, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((T, block_m, k), jnp.float32),
            jax.ShapeDtypeStruct((T, block_m, k), jnp.int32),
            jax.ShapeDtypeStruct((T, block_m, 1), jnp.int32),
            jax.ShapeDtypeStruct((T, block_n, k), jnp.float32),
            jax.ShapeDtypeStruct((T, block_n, k), jnp.int32),
            jax.ShapeDtypeStruct((T, block_n, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ij.astype(jnp.int32), D, D)
