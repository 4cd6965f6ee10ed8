"""Single-device APSS: the reference oracle and the blocked production path.

``apss_reference`` is the pure-jnp oracle every other implementation (blocked,
Pallas kernel, all distributed variants) is validated against. It is the moral
equivalent of the paper's *all-pairs-0-array*: a dense score accumulator
(``S = D·Dᵀ``) filtered at threshold ``t`` — which the paper measured to be the
fastest sequential algorithm, and which maps 1:1 onto the MXU.

``apss_blocked`` is the tiled form (the paper's §5.1.9 "processing in vector
blocks", which also is the natural TPU shape): queries are processed in row
blocks of ``block_rows`` against the full corpus, with a running top-k match
buffer. Block-level pruning masks (``core.pruning``) can be threaded through to
account (and, in the Pallas kernel, actually skip) provably-dead tiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.matches import SCORE_PRECISION, Matches, extract_matches
from repro.core.pruning import (
    PruneStats,
    block_prune_mask,
    live_tile_mask,
    prune_stats,
    sparse_block_stats,
)
from repro.core.sparse import (
    SparseCorpus,
    pad_rows_sparse,
    sparse_similarity_topk,
)
from repro.obs import trace
from repro.planner import telemetry


def _mask_counts(mask):
    """Host-side live-tile accounting from a mask — (live, total, per-row
    counts), or Nones when the mask is traced (cannot be read without
    forcing device work, which telemetry never does)."""
    if mask is None:
        return None, None, None
    try:
        mk = np.asarray(mask)
    except Exception:  # jax tracer: leave unaccounted
        return None, None, None
    return int(mk.sum()), int(mk.size), tuple(int(x) for x in mk.sum(axis=1))


def normalize_rows(D: jax.Array, eps: float = 1e-12) -> jax.Array:
    """L2-normalize rows (the paper assumes ``||x|| = 1``)."""
    nrm = jnp.linalg.norm(D.astype(jnp.float32), axis=-1, keepdims=True)
    return (D / jnp.maximum(nrm, eps)).astype(D.dtype)


def pad_rows(x: jax.Array, multiple: int) -> tuple[jax.Array, int]:
    """Zero-pad axis 0 to a multiple; returns (padded, original_len)."""
    n = x.shape[0]
    rem = (-n) % multiple
    if rem:
        x = jnp.pad(x, ((0, rem),) + ((0, 0),) * (x.ndim - 1))
    return x, n


def apss_reference(
    D: jax.Array,
    threshold: float,
    k: int = 32,
    *,
    exclude_self: bool = True,
) -> Matches:
    """Oracle APSS self-join: dense ``D·Dᵀ``, threshold, per-row top-k.

    O(n²m) FLOPs, O(n²) memory — only for validation-scale inputs.
    """
    S = jnp.einsum(
        "im,jm->ij", D, D,
        precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )
    return extract_matches(S, threshold, k, exclude_self=exclude_self)


def similarity_topk(
    Q: jax.Array,
    C: jax.Array,
    threshold: float,
    k: int = 32,
    *,
    block_rows: int = 512,
    exclude_self: bool = False,
    row_offset: jax.Array | int = 0,
    col_offset: jax.Array | int = 0,
    col_valid: Optional[jax.Array] = None,
    use_kernel: bool = False,
    variant: Optional[str] = None,
    mesh=None,
) -> Matches:
    """Blocked similarity join of queries ``Q (nq, m)`` vs corpus ``C (nc, m)``.

    Streams ``block_rows`` queries at a time so peak memory is
    ``O(block_rows · nc)`` instead of ``O(nq · nc)``. This is the workhorse for
    both the APSS self-join (``Q is C``) and retrieval scoring
    (1 query × 10⁶ candidates: ``nq = 1`` padded to a block).

    ``use_kernel=True`` routes the whole join through the fused Pallas
    kernel (``kernels.apss_block.apss_fused``): score tiles stay VMEM-only,
    the output is the ``O(nq·k)`` match buffer, and the maxweight bound mask
    gates per-tile MXU work. Offsets stay dynamic, so this is the path the
    distributed ring/halfring schedules take. ``col_valid`` masks are not
    supported by the kernel (only contiguous-prefix validity, which the
    kernel derives from the unpadded corpus length).

    ``variant="auto"`` hands the whole self-join to the execution planner
    (``planner.plan_apss``): ``Q`` must be ``C`` (the same object) with
    ``exclude_self=True``, and ``mesh`` (optional) opens the distributed
    variants to the candidate set. Every other argument is chosen by the
    planner from sampled corpus statistics and the calibrated cost models.
    """
    if variant not in (None, "auto"):
        raise ValueError(f"unknown variant: {variant!r} (only 'auto')")
    if variant == "auto":
        if Q is not C:
            raise ValueError(
                "variant='auto' plans the APSS self-join: pass the same "
                "object as Q and C (rectangular retrieval is served by "
                "serving.query_topk against a prebuilt index)"
            )
        if not exclude_self:
            raise ValueError(
                "variant='auto' dispatches to self-join variants, which "
                "exclude self-pairs; pass exclude_self=True"
            )
        from repro.planner.plan import plan_apss

        return plan_apss(Q, threshold, k, mesh).run()
    if isinstance(Q, SparseCorpus) != isinstance(C, SparseCorpus):
        raise ValueError(
            "Q and C must use the same representation "
            "(both SparseCorpus or both dense arrays)"
        )
    if isinstance(Q, SparseCorpus):
        if use_kernel:
            raise ValueError(
                "sparse use_kernel is self-join only: call apss_blocked on a "
                "SparseCorpus (kernels.apss_block.sparse.apss_sparse_compacted)"
            )
        if col_valid is not None:
            raise ValueError("sparse similarity_topk derives col validity "
                             "from the unpadded corpus length")
        return sparse_similarity_topk(
            Q, C, threshold, k, block_rows=block_rows,
            exclude_self=exclude_self,
            row_offset=row_offset, col_offset=col_offset,
        )
    if use_kernel:
        if col_valid is not None:
            raise ValueError("use_kernel=True does not support col_valid")
        from repro.kernels.apss_block.ops import apss_fused

        bm = _kernel_tile(block_rows)
        return apss_fused(
            Q, C, float(threshold), k,
            block_m=bm, block_n=bm,
            row_offset=row_offset, col_offset=col_offset,
            exclude_self=exclude_self,
        )
    nq = Q.shape[0]
    Qp, _ = pad_rows(Q, block_rows)
    nblocks = Qp.shape[0] // block_rows
    Qb = Qp.reshape(nblocks, block_rows, Q.shape[1])

    def body(carry, inputs):
        blk_idx, q_blk = inputs
        s = jnp.einsum(
            "im,jm->ij", q_blk, C,
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        m = extract_matches(
            s,
            threshold,
            k,
            row_offset=row_offset + blk_idx * block_rows,
            col_offset=col_offset,
            exclude_self=exclude_self,
            col_valid=col_valid,
        )
        return carry, m

    _, ms = jax.lax.scan(body, 0, (jnp.arange(nblocks), Qb))
    out = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), ms)
    return jax.tree.map(lambda x: x[:nq], out)


def _kernel_tile(block_rows: int) -> int:
    """Clamp the user's row-block knob to an MXU-aligned kernel tile."""
    return min(max(128, block_rows), 256)


def apss_blocked(
    D: jax.Array,
    threshold: float,
    k: int = 32,
    *,
    block_rows: int = 512,
    with_prune_stats: bool = False,
    use_kernel: bool = False,
) -> Matches | tuple[Matches, PruneStats]:
    """Blocked APSS self-join with optional block-prune accounting.

    ``use_kernel=True`` routes the self-join through the fused streaming
    Pallas kernel (``kernels.apss_block.apss_fused``): matmul → threshold →
    top-k merge → count in one kernel, ``@pl.when`` tile skipping from the
    maxweight bound mask, and an ``O(n·k)`` ``Matches`` output — the ``n×n``
    score matrix is never materialized in HBM (TPU compiled; interpret mode
    on CPU). The XLA path computes every tile and uses the mask for
    accounting only. Exactness is independent of the mask; see
    ``core.pruning``.

    ``D`` may be a :class:`~repro.core.sparse.SparseCorpus`: the self-join
    then takes the sparse path — inverted-index worklist + CSR tile
    scoring (``use_kernel=True``; host-compacted, so not traceable) or the
    blocked gather-dot join (``use_kernel=False``, fully traceable). Both
    are exact on the densified corpus; see DESIGN.md §5.
    """
    if isinstance(D, SparseCorpus):
        return _apss_blocked_sparse(
            D, threshold, k, block_rows=block_rows,
            with_prune_stats=with_prune_stats, use_kernel=use_kernel,
        )
    if use_kernel:
        from repro.kernels.apss_block.ops import apss_fused

        bm = _kernel_tile(block_rows)
        m = apss_fused(
            D, D, float(threshold), k, block_m=bm, block_n=bm,
            exclude_self=True,
        )
    else:
        m = similarity_topk(
            D, D, threshold, k, block_rows=block_rows, exclude_self=True
        )
    mask = None
    if with_prune_stats:
        Dp, _ = pad_rows(D, block_rows)
        mask = block_prune_mask(Dp, Dp, threshold, block_rows)
    if telemetry.enabled():
        n, mdim = D.shape
        live, total, counts = _mask_counts(mask)
        flops = telemetry.dense_join_flops(n, n, mdim)
        if use_kernel and live is not None and total:
            flops *= live / total  # @pl.when skips dead tiles
        telemetry.record(telemetry.ApssStats(
            variant="blocked/dense-kernel" if use_kernel else "blocked/dense-xla",
            n=n, m=mdim, block_rows=block_rows, sparse=False, flops=flops,
            live_tiles=live, total_tiles=total, tile_counts=counts,
        ))
    if not with_prune_stats:
        return m
    return m, prune_stats(mask)


@functools.partial(jax.jit, static_argnames=("block_rows",))
def _sparse_self_bounds(D: SparseCorpus, threshold, *, block_rows: int):
    """The self-join's live tile mask and tile upper bounds, ``(nb, nb)``
    each over ``D`` row-padded to ``block_rows``: block stats and bounds in
    one program."""
    with jax.named_scope("mask"):
        Dp, _ = pad_rows_sparse(D, block_rows)
        stats = sparse_block_stats(Dp, block_rows)
        return live_tile_mask(stats, stats, threshold, return_ub=True)


def _apss_blocked_sparse(
    D: SparseCorpus,
    threshold: float,
    k: int,
    *,
    block_rows: int,
    with_prune_stats: bool,
    use_kernel: bool,
) -> Matches | tuple[Matches, PruneStats]:
    bs = _kernel_tile(block_rows) if use_kernel else block_rows
    with trace.span("apss/selfjoin", n=D.n, k=k, block_rows=bs):
        mask = ub = None
        if with_prune_stats or use_kernel:
            # Index-build half (block stats) separated from the scoring-time
            # mask so the bounds are computed exactly once here and shared by
            # the worklist AND the accounting (serving builds the same stats
            # once per corpus — see serving/index.py).
            with trace.span("apss/bounds"):
                mask, ub = _sparse_self_bounds(D, threshold, block_rows=bs)
                if use_kernel:  # host-compacted below: pull the mask here
                    mask, ub = np.asarray(mask), np.asarray(ub)
        if use_kernel:
            from repro.kernels.apss_block.sparse import apss_sparse_compacted

            m = apss_sparse_compacted(
                D, float(threshold), k,
                block_m=bs, block_mask=mask, block_ub=ub, use_kernel=True,
            )
        else:
            m = sparse_similarity_topk(
                D, D, threshold, k, block_rows=block_rows, exclude_self=True
            )
        if telemetry.enabled():
            live, total, counts = _mask_counts(mask)
            flops = telemetry.sparse_join_flops(D.n, D.n, D.cap)
            if use_kernel and live is not None and total:
                flops *= live / total  # worklist compaction skips dead tiles
            telemetry.record(telemetry.ApssStats(
                variant="blocked/sparse-kernel" if use_kernel else "blocked/sparse-xla",
                n=D.n, m=D.m, block_rows=bs, sparse=True, flops=flops,
                live_tiles=live, total_tiles=total, tile_counts=counts,
                extra={"cap": D.cap},
            ))
        if not with_prune_stats:
            return m
        return m, prune_stats(mask)
