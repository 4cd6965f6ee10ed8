"""Public jit'd wrappers for the apss_block kernels.

Handles padding to tile multiples, optional automatic bound-mask computation
(``core.pruning``), and the CPU/TPU dispatch (interpret mode off-TPU).

Three entry points:

- :func:`apss_block_matmul` — the seed dense-output kernel: thresholded
  ``n×n`` score matrix in HBM (kept for benchmarks/validation; O(n²) HBM).
- :func:`apss_fused` — streaming fused extraction: matmul → threshold →
  top-k merge → count in one kernel, ``Matches``-shaped ``O(n·k)`` output.
  The ``n×n`` score matrix never exists in HBM.
- :func:`apss_fused_compacted` — fused extraction driven by a dense
  worklist of live upper-triangular tiles (scalar prefetch): pruned tiles
  cost zero pipeline slots and S = Sᵀ halves MXU work. Self-join only;
  the live mask is compacted on the host, so the call is not traceable
  under jit (the inner per-worklist computation is).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.matches import NEG_INF, Matches, empty_matches
from repro.core.pruning import block_prune_mask
from repro.kernels.apss_block.apss_block import apss_block_pallas
from repro.kernels.apss_block.fused import (
    _VALID,
    apss_fused_pallas,
    apss_tile_candidates_pallas,
)


def _pad_to(x: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "block_m", "block_n", "block_k",
        "auto_mask", "interpret",
    ),
)
def apss_block_matmul(
    x: jax.Array,
    y: jax.Array,
    threshold: float,
    *,
    block_mask: jax.Array | None = None,
    auto_mask: bool = True,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Thresholded similarity tile ``where(X·Yᵀ ≥ t, ·, 0)`` with tile
    skipping.

    If ``block_mask`` is None and ``auto_mask``, the maxweight/minsize bound
    mask is computed on the fly (one cheap summary matmul); pass
    ``auto_mask=False`` to run fully dense.
    """
    if interpret is None:
        interpret = not _on_tpu()

    n_rows, m = x.shape
    n_cols = y.shape[0]
    xp = _pad_to(x, block_m, block_k)
    yp = _pad_to(y, block_n, block_k)
    grid_m = xp.shape[0] // block_m
    grid_n = yp.shape[0] // block_n

    if block_mask is None:
        if auto_mask:
            block_mask = block_prune_mask(
                xp, yp, threshold, block_m, block_n, use_minsize=False
            )
        else:
            block_mask = jnp.ones((grid_m, grid_n), jnp.int32)

    out = apss_block_pallas(
        xp, yp, block_mask, threshold,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )
    return out[:n_rows, :n_cols]


def _pick_bk(m: int, block_k: int) -> int:
    """Feature-axis tile: requested size, shrunk for narrow inputs so the
    zero-padding stays < one tile (MXU lane alignment: multiples of 128)."""
    return min(block_k, max(128, -(-m // 128) * 128))


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_m", "block_n", "block_k",
        "auto_mask", "exclude_self", "interpret",
    ),
)
def apss_fused(
    x: jax.Array,
    y: jax.Array,
    threshold: float,
    k: int,
    *,
    block_mask: jax.Array | None = None,
    auto_mask: bool = True,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    exclude_self: bool = True,
    interpret: bool | None = None,
) -> Matches:
    """Fused streaming similarity join: ``Matches`` straight from the kernel.

    The thresholded score matrix never reaches HBM — the kernel scans column
    tiles per row block with a VMEM-resident running top-k + exact counts,
    so output memory is ``O(n_rows · k)``. Offsets are dynamic (traced), so
    this drops into the distributed ring/halfring schedules where the
    column offset depends on the ring step.
    """
    if interpret is None:
        interpret = not _on_tpu()

    nq, m = x.shape
    nc = y.shape[0]
    bk = _pick_bk(m, block_k)
    xp = _pad_to(x, block_m, bk)
    yp = _pad_to(y, block_n, bk)

    if block_mask is None:
        if auto_mask:
            block_mask = block_prune_mask(
                xp, yp, threshold, block_m, block_n, use_minsize=False
            )
        else:
            block_mask = jnp.ones(
                (xp.shape[0] // block_m, yp.shape[0] // block_n), jnp.int32
            )

    meta = jnp.stack(
        [jnp.asarray(row_offset, jnp.int32), jnp.asarray(col_offset, jnp.int32)]
    ).reshape(1, 2)
    values, indices, counts = apss_fused_pallas(
        xp, yp, block_mask, meta, float(threshold), k,
        block_m=block_m, block_n=block_n, block_k=bk,
        n_valid_cols=nc, exclude_self=exclude_self, interpret=interpret,
    )
    values = jnp.where(indices >= 0, values, NEG_INF)
    return Matches(
        values=values[:nq],
        indices=indices[:nq],
        counts=counts[:nq, 0],
    )


def _merge_packet(cv, ci, cc, blk, pv, pi, pc, k: int):
    """Fold one tile candidate packet into the per-row-block running top-k.

    The early-exit fold (``serving.query._ee_fold``) merges packets one at
    a time with it, to read the running k-th value as it goes; the folds
    below give the same answer with every block at once.

    Packet ids are disjoint from the buffer's (each column block is visited
    once per row block; forward/backward packets for the same row block come
    from disjoint column ranges), so a plain top-k over the concat is exact.
    """
    cur_v = jax.lax.dynamic_index_in_dim(cv, blk, 0, keepdims=False)
    cur_i = jax.lax.dynamic_index_in_dim(ci, blk, 0, keepdims=False)
    cur_c = jax.lax.dynamic_index_in_dim(cc, blk, 0, keepdims=False)
    vals = jnp.concatenate([cur_v, pv], axis=1)
    idxs = jnp.concatenate([cur_i, pi], axis=1)
    tv, sel = jax.lax.top_k(vals, k)
    ti = jnp.take_along_axis(idxs, sel, axis=1)
    ti = jnp.where(tv > _VALID, ti, -1)
    cv = jax.lax.dynamic_update_index_in_dim(cv, tv, blk, 0)
    ci = jax.lax.dynamic_update_index_in_dim(ci, ti, blk, 0)
    cc = jax.lax.dynamic_update_index_in_dim(cc, cur_c + pc, blk, 0)
    return cv, ci, cc


def compact_worklist(mask, ub=None) -> np.ndarray | None:
    """Host-side live-mask → dense upper-triangular worklist ``(2, T)``.

    Symmetrizes first (the minsize bound is asymmetric: a pair is live if
    either orientation is) then keeps ``j ≥ i`` only — each off-diagonal
    tile is computed once for both orientations (S = Sᵀ). Returns None when
    nothing is live. Shared by the dense and sparse compacted paths so the
    exactness-critical mirror convention lives in one place.

    ``ub`` (``(nb, nb)`` f32 tile upper bounds, as returned by
    ``core.pruning.live_tile_mask(return_ub=True)``) enables the paper's
    maxweight **adaptive ordering**: live tiles are sorted by upper bound
    descending, so the tiles most likely to carry matches run first and
    any future early-exit threshold tightens fastest while the worklist
    drains. Results are order-invariant (each tile's packet is folded into
    an exact running top-k — asserted by ``tests/test_apss_fused.py``);
    ordering only shifts WHERE the matches are found early.
    """
    live = np.asarray(mask)
    live = np.triu(live | live.T)
    iu, ju = np.nonzero(live)
    if iu.size == 0:
        return None
    if ub is not None:
        u = np.asarray(ub, np.float64)
        u = np.maximum(u, u.T)  # match the symmetrized liveness
        order = np.argsort(-u[iu, ju], kind="stable")
        iu, ju = iu[order], ju[order]
    return np.stack([iu, ju]).astype(np.int32)


def compact_rect_worklist(mask, ub=None) -> np.ndarray | None:
    """Host-side live-mask → dense rectangular worklist ``(2, T)``.

    The serving-path sibling of :func:`compact_worklist`: no symmetry, no
    triangular cut — every live ``(query_block, corpus_block)`` tile is
    listed once. Same optional upper-bound descending order.
    """
    live = np.asarray(mask)
    iu, ju = np.nonzero(live)
    if iu.size == 0:
        return None
    if ub is not None:
        order = np.argsort(-np.asarray(ub, np.float64)[iu, ju], kind="stable")
        iu, ju = iu[order], ju[order]
    return np.stack([iu, ju]).astype(np.int32)


def pad_worklist(wl: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Bucket-pad a ``(2, T)`` worklist to the next power of two.

    Serving calls see a different live-tile count per query batch; without
    bucketing every new ``T`` would retrace (and recompile) the jitted
    scoring path. Padding entries repeat tile ``(0, 0)`` (always valid
    memory) and are masked out of the fold by the returned ``(Tb,)`` bool
    validity vector — so the amortized trace count is O(log live-tiles),
    not O(distinct worklist lengths).
    """
    T = wl.shape[1]
    Tb = 1 << max(0, (T - 1).bit_length())
    valid = np.zeros((Tb,), bool)
    valid[:T] = True
    if Tb == T:
        return wl, valid
    pad = np.zeros((2, Tb - T), np.int32)
    return np.concatenate([wl, pad], axis=1), valid


def fold_ranks(n_packets: int, per_block: int) -> int:
    """Static bound on the packets one row block receives in a fold.

    ``per_block`` is what the worklist's shape allows a block: the corpus
    blocks a rect worklist can list once each (``grid_c``), or
    ``grid_m + 1`` forward and mirror packets in an upper-triangular one.
    The fold lays out ``grid · fold_ranks(...)`` slots (``fold_slots`` on
    the ``query/worklist`` and ``apss/worklist`` spans).
    """
    return min(n_packets, per_block)


def _fold_blocks(key, pv, pi, pc, *, grid: int, ranks: int, k: int):
    """Merge packets into every row block's top-k at once. Exact.

    ``key (P,)`` is each packet's row block (``grid`` drops the packet);
    the packets ``pv, pi (P, block, k)``, ``pc (P, block)`` lie in merge
    order. One stable argsort of the keys gives each packet its slot
    ``(block, rank)``, rank counted in merge order; a gather lays out the
    slots ``(grid, R, block, k)``, empty ones ``-∞`` with id −1, and one
    stable ``lax.top_k`` over each row's ``R · k`` candidates keeps the k
    best. That is the sequential merge's answer bit for bit — values, ids
    and ties: a stable top-k of ``concat(buffer, packet)`` keeps the first
    occurrence, and so does one top-k over every packet in merge order.
    (One top-k over the whole row beat a two-stage one, a top-k within
    chunks of packets and then over the chunk winners, on a v5e at both
    benchmark shapes.)

    ``ranks`` bounds the packets a block receives (:func:`fold_ranks`).
    The slots of one pass are capped near the packets' own number,
    ``R = min(ranks, ⌈P / grid⌉)``, so the slab never outgrows the packets
    by more than one packet a block: ``grid · R · block · k · 8`` bytes,
    at most ``grid · ranks · block · k · 8`` — the kernel's packets when
    every tile is live. Where ``R < ranks`` (a worklist far sparser than
    the grid), ``⌈max count / R⌉`` passes each merge R ranks, the running
    top-k taken as rank −1 of the next pass.
    """
    P, block = pv.shape[0], pv.shape[1]
    order = jnp.argsort(key, stable=True)
    count = jnp.bincount(key, length=grid + 1)[:grid]
    start = jnp.cumsum(count) - count
    R = min(ranks, -(-P // grid))

    def gather(r0):
        rank = r0 + jnp.arange(R, dtype=jnp.int32)
        live = rank[None, :] < count[:, None]                  # (grid, R)
        src = order[jnp.minimum(start[:, None] + rank[None, :], P - 1)]
        v = jnp.where(live[..., None, None], pv[src], -jnp.inf)
        i = jnp.where(live[..., None, None], pi[src], -1)
        c = jnp.sum(jnp.where(live[..., None], pc[src], 0), axis=1)
        # (grid, R, block, k) → rows (grid, block) of R · k candidates
        rows = lambda x: x.transpose(0, 2, 1, 3).reshape(grid, block, R * k)
        return rows(v), rows(i), c

    def merge(v, i):
        v, sel = jax.lax.top_k(v, k)
        return v, jnp.take_along_axis(i, sel, axis=-1)

    if R == ranks:
        v, i, cc = gather(0)
        cv, ci = merge(v, i)
    else:
        def body(state):
            r0, cv, ci, cc = state
            v, i, c = gather(r0)
            cv, ci = merge(
                jnp.concatenate([cv, v], axis=-1),
                jnp.concatenate([ci, i], axis=-1),
            )
            return r0 + R, cv, ci, cc + c

        _, cv, ci, cc = jax.lax.while_loop(
            lambda s: s[0] < jnp.max(count), body,
            (
                jnp.int32(0),
                jnp.full((grid, block, k), -jnp.inf, jnp.float32),
                jnp.full((grid, block, k), -1, jnp.int32),
                jnp.zeros((grid, block), jnp.int32),
            ),
        )
    ci = jnp.where(cv > _VALID, ci, -1)
    values = jnp.where(ci >= 0, cv, NEG_INF).reshape(grid * block, k)
    return values, ci.reshape(grid * block, k), cc.reshape(grid * block)


def fold_rect_packets(ij, tvalid, fv, fi, fc, *, grid_q, grid_c, block_q, k):
    """Fold rectangular forward packets into flat buffers, every query
    block at once (:func:`_fold_blocks`).

    The serving twin of :func:`fold_packets`: forward packets only (no
    mirror — queries aren't corpus rows), plus a ``(T,)`` validity mask for
    bucket padding (``pad_worklist``): an invalid entry is keyed past the
    last block, so a padding entry that aliases tile ``(0, 0)`` never
    reaches a slot. A rect worklist lists each ``(query block, corpus
    block)`` once, so a block receives at most ``min(T, grid_c)`` packets;
    the slab is at most ``grid_q · grid_c · block_q · k · 8`` bytes.
    """
    with jax.named_scope("fold"):
        key = jnp.where(tvalid, ij[0], grid_q).astype(jnp.int32)
        return _fold_blocks(
            key, fv, fi, fc, grid=grid_q,
            ranks=fold_ranks(ij.shape[1], grid_c), k=k,
        )


def fold_packets(ij, fv, fi, fc, bv, bi, bc, *, grid_m, block_m, k):
    """Fold per-live-tile candidate packets into flat buffers, every row
    block at once (:func:`_fold_blocks`).

    ``ij (2, T)`` worklist of upper-triangular tile coordinates; ``f*`` are
    the forward packets (rows of block ``ij[0, t]``), ``b*`` the mirror
    packets (rows of block ``ij[1, t]``; empty on diagonal tiles). Counts
    are ``(T, block_m)``. Merge order is entry t's forward packet, then its
    mirror. A row block receives at most ``grid_m + 1`` packets, so the
    slab is at most ``grid_m · (grid_m + 1) · block_m · k · 8`` bytes.
    Exactness relies on the worklist contract: packet ids entering one row
    block come from disjoint column ranges. Shared by the dense
    (:func:`apss_fused_compacted`) and sparse (``kernels.apss_block.sparse``)
    worklist paths.
    """
    with jax.named_scope("fold"):
        T = ij.shape[1]

        def interleave(f, b):
            return jnp.stack([f, b], axis=1).reshape(2 * T, *f.shape[1:])

        return _fold_blocks(
            interleave(ij[0], ij[1]).astype(jnp.int32),
            interleave(fv, bv), interleave(fi, bi), interleave(fc, bc),
            grid=grid_m, ranks=fold_ranks(2 * T, grid_m + 1), k=k,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_m", "block_k", "n_valid", "grid_m",
        "interpret",
    ),
)
def _compacted_inner(
    Dp, ij, *, threshold, k, block_m, block_k, n_valid, grid_m, interpret
):
    fv, fi, fc, bv, bi, bc = apss_tile_candidates_pallas(
        Dp, ij, float(threshold), k,
        block_m=block_m, block_n=block_m, block_k=block_k,
        n_valid=n_valid, interpret=interpret,
    )
    return fold_packets(
        ij, fv, fi, fc[..., 0], bv, bi, bc[..., 0],
        grid_m=grid_m, block_m=block_m, k=k,
    )


def apss_fused_compacted(
    D: jax.Array,
    threshold: float,
    k: int,
    *,
    block_m: int = 256,
    block_k: int = 512,
    use_minsize: bool = True,
    interpret: bool | None = None,
) -> Matches:
    """Self-join via the live-tile worklist kernel (maximum pruning win).

    The block bound mask is compacted ON THE HOST into a dense list of live
    upper-triangular ``(i, j)`` tile coordinates; the kernel's 1-D grid then
    runs exactly ``live`` steps (a pruned tile costs nothing, vs. a masked
    no-op pipeline slot in :func:`apss_fused`) and each off-diagonal tile is
    computed once for both orientations (S = Sᵀ). Host compaction makes this
    entry non-traceable; everything downstream of the worklist is jitted.
    """
    if interpret is None:
        interpret = not _on_tpu()
    n, m = D.shape
    bk = _pick_bk(m, block_k)
    Dp = _pad_to(D, block_m, bk)
    grid_m = Dp.shape[0] // block_m

    mask, ub = block_prune_mask(
        Dp, Dp, threshold, block_m, block_m, use_minsize=use_minsize,
        return_ub=True,
    )
    wl = compact_worklist(mask, ub)
    if wl is None:
        return empty_matches(n, k)
    ij = jnp.asarray(wl)

    values, indices, counts = _compacted_inner(
        Dp, ij, threshold=float(threshold), k=k, block_m=block_m,
        block_k=bk, n_valid=n, grid_m=grid_m, interpret=interpret,
    )
    return Matches(
        values=values[:n], indices=indices[:n], counts=counts[:n]
    )
