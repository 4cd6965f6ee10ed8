"""query_topk: rectangular pruned scoring against a prebuilt APSSIndex.

The self-join scoring paths rebuild ``bdims``/``bx`` supports and pruning
bounds on every call; this module is the query-time half of the split:
per call it computes ONLY the query-side block stats (one cheap summary
pass over the padded batch), evaluates the paper's maxweight + minsize
bounds against the index's precomputed corpus block maxima — which is the
inverted-index candidacy test in weighted form, so candidates come from
the prebuilt posting-list supports — and scores exactly the live
``(query_block, corpus_block)`` tiles through a rectangular generalization
of the fused/compacted worklist path (Pallas kernel on TPU, XLA scan
fallback elsewhere). No n×n symmetry assumption anywhere: no mirror
packets, no self-pair exclusion, no triangular worklist cut.

Beyond pruning, the worklist's upper-bound-DESCENDING order is itself
exploitable (``early_exit=True``): the scan carries each query row's
running k-th value, and once every live row's k-th beats the next tile's
upper bound, no remaining tile can contribute — the ``lax.while_loop``
stops and the ordering becomes skipped FLOPs. Exact by construction for
top-k values and indices (a skipped tile's candidates are ≤ the bound ≤
every row's k-th, and ties lose to the buffer under the stable merge);
the only concession is that match COUNTS saturate at k — a row proven to
hold k matches stops counting the tail. DESIGN.md §12 has the full
soundness argument.

Retrace discipline (the server's hot loop must not recompile):

- every index structure enters the jit'd inners as pytree ARGUMENTS —
  nothing corpus-sized is rebuilt or re-traced per call,
- the live-tile worklist is bucket-padded to a power of two
  (``ops.pad_worklist``) so varying live-tile counts reuse compiled code,
- every jitted inner marks the public retrace registry
  (``repro.obs.compile``) at trace time only; ``tests/test_serving.py``
  asserts under an ``assert_no_retrace`` contract that a second query
  compiles nothing.

Sharded indexes (``build_index(mesh=...)``) take the per-shard path with
the SAME pruning as single-host serving: the corpus-side ``BlockStats``
are replicated, so the host evaluates the global live mask once, slices
each shard's block range out of it, and ships every device its own
compacted, bucket-padded worklist through one ``shard_map`` — a shard
scores only its live tiles (XLA scan or the rect Pallas kernel), emits
packets with GLOBAL column ids, and folds them locally. Per-shard top-k
partials come back stacked and the host merges them (``merge_matches``
over disjoint column ranges — exact).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.matches import (
    NEG_INF,
    SCORE_PRECISION,
    Matches,
    empty_matches,
    merge_matches,
)
from repro.core.pruning import dense_block_stats, live_tile_mask
from repro.core.sparse import SparseCorpus, gather_dot, to_dense
from repro.kernels.apss_block.fused import (
    NEG_LARGE,
    _rect_tile_packets,
    _topk_sort,
    rect_tile_candidates_early_exit_pallas,
    rect_tile_candidates_pallas,
)
from repro.kernels.apss_block.ops import (
    _merge_packet,
    _on_tpu,
    _pick_bk,
    compact_rect_worklist,
    fold_ranks,
    fold_rect_packets,
    pad_worklist,
)
from repro.kernels.apss_block.sparse import rect_sparse_tile_candidates_pallas
from repro.obs import compile as obs_compile
from repro.obs import metrics, trace
from repro.planner import telemetry
from repro.serving.index import APSSIndex

# Trace-time retrace counters live in the public registry
# (repro.obs.compile.MONITOR). The serving contract is "build once, query
# many": after the first call of a given shape these must not move —
# enforced by assert_no_retrace("serving.query") contracts in
# tests/test_serving.py.
obs_compile.register_entry_points(
    "serving.query",
    "query_mask", "dense_inner", "sparse_inner", "sharded_query",
    "dense_ee_inner", "sparse_ee_inner", "dense_ee_kernel",
)


def query_topk(
    index: APSSIndex,
    Q,
    threshold: float,
    k: int = 32,
    *,
    block_q: int = 128,
    use_kernel: bool = False,
    use_minsize: bool = True,
    early_exit: bool = False,
    plan=None,
    interpret: bool | None = None,
) -> Matches:
    """Top-k corpus neighbors ≥ ``threshold`` for a batch of queries.

    ``Q`` is ``(B, m)`` dense (a :class:`SparseCorpus` batch is densified —
    query batches are small) and is scored AS GIVEN (no normalization here;
    the server normalizes on ingest). Returns :class:`Matches` with global
    corpus row ids; exact vs the brute-force rectangular oracle
    (``extract_matches(Q @ Cᵀ, t, k, exclude_self=False)``) at every
    threshold, including ``t ≤ 0``, because pruned tiles are provably
    matchless (``core.pruning``).

    The live worklist is compacted host-side (same contract as
    ``apss_fused_compacted``), ordered by upper bound descending, and
    bucket-padded so repeat calls hit the jit cache. ``use_kernel`` routes
    tile scoring through the rectangular Pallas kernels (TPU; interpret
    off-TPU); the default XLA scan is the production path off-TPU.

    ``early_exit=True`` additionally stops the worklist scan once every
    query row's running k-th value beats the remaining upper bounds (see
    module doc): top-k values/indices stay bit-identical to
    ``early_exit=False``; counts saturate at ``min(count, k)``.

    ``plan="auto"`` (or an explicit ``planner.costmodel.QueryPlan``)
    delegates the ``block_q`` / kernel-vs-scan choice to the cost model,
    priced per batch from the index's exact ``BlockStats`` — the
    planner's decision overrides the ``block_q``/``use_kernel`` arguments.
    """
    with trace.span(
        "serving/query", use_kernel=use_kernel, early_exit=early_exit
    ):
        return _query_topk_impl(
            index, Q, threshold, k, block_q=block_q, use_kernel=use_kernel,
            use_minsize=use_minsize, early_exit=early_exit, plan=plan,
            interpret=interpret,
        )


def _query_topk_impl(
    index: APSSIndex,
    Q,
    threshold: float,
    k: int = 32,
    *,
    block_q: int = 128,
    use_kernel: bool = False,
    use_minsize: bool = True,
    early_exit: bool = False,
    plan=None,
    interpret: bool | None = None,
) -> Matches:
    if interpret is None:
        interpret = not _on_tpu()
    if isinstance(Q, SparseCorpus):
        if Q.m != index.m:
            raise ValueError(f"dimension mismatch: Q.m={Q.m} vs index m={index.m}")
        Q = to_dense(Q)
    Q = jnp.asarray(Q)
    if Q.ndim != 2 or Q.shape[1] != index.m:
        raise ValueError(f"Q must be (B, {index.m}); got {Q.shape}")
    B = Q.shape[0]
    if plan is not None:
        from repro.planner.costmodel import plan_query_topk

        if isinstance(plan, str):
            if plan != "auto":
                raise ValueError(f"plan must be 'auto' or a QueryPlan; got {plan!r}")
            plan = plan_query_topk(index, B, float(threshold), k)
        block_q = int(plan.block_q)
        use_kernel = bool(plan.use_kernel)
    if not index.is_sparse:
        # Dense corpora are lane-padded once at build time; match the
        # query batch (query-sized work) so the jitted inners see aligned
        # operands and never re-pad the corpus.
        remk = index.corpus.shape[1] - index.m
        if remk:
            Q = jnp.pad(Q, ((0, 0), (0, remk)))

    if index.mesh is not None:
        if early_exit:
            raise NotImplementedError(
                "early_exit is a single-host worklist optimization; the "
                "sharded path prunes per shard but scans its full live "
                "worklist"
            )
        return _sharded_query_pruned(
            index, Q, threshold, k, block_q=block_q, use_kernel=use_kernel,
            use_minsize=use_minsize, interpret=interpret,
        )

    rem = (-B) % block_q
    Qp = jnp.pad(Q, ((0, rem), (0, 0))) if rem else Q
    grid_q = Qp.shape[0] // block_q
    with trace.span("query/mask"):
        mask, ub = _query_mask(
            Qp, index.stats, threshold=float(threshold), block_q=block_q,
            use_minsize=use_minsize, normalized=index.normalized,
        )
        mk = np.asarray(mask)
        ubh = np.asarray(ub)
    with trace.span("query/worklist", batch=B):
        wl = compact_rect_worklist(mk, ubh)
        live = 0 if wl is None else int(wl.shape[1])
        if wl is not None:
            ij_np, tv_np = pad_worklist(wl)
        entries = 0 if wl is None else int(tv_np.shape[0])
        trace.annotate(
            live=live, total=int(mk.size), entries=entries,
            fold_slots=grid_q * fold_ranks(entries, mk.shape[1]),
        )
    if telemetry.enabled() or metrics.enabled():
        depth = (
            int(index.bdims.shape[1]) if index.is_sparse
            else int(index.corpus.shape[1])
        )
        if telemetry.enabled():
            telemetry.record(telemetry.ApssStats(
                variant="serving/query",
                n=index.n, m=index.m, block_rows=index.block_rows,
                sparse=index.is_sparse,
                flops=2.0 * live * block_q * index.block_rows * depth,
                live_tiles=live, total_tiles=int(mk.size),
                tile_counts=tuple(int(x) for x in mk.sum(axis=1)),
                extra={"batch": B, "use_kernel": use_kernel},
            ))
        if metrics.enabled():
            metrics.observe(
                "serving.live_tile_fraction", live / max(1, mk.size)
            )
    if wl is None:
        return empty_matches(B, k)
    with trace.span("query/dispatch", early_exit=early_exit):
        ij, tvalid = jnp.asarray(ij_np), jnp.asarray(tv_np)
        ubw = None
        if early_exit:
            # Per-worklist-entry upper bounds, in worklist (descending) order;
            # bucket-padding entries get NEG_LARGE so they are always skipped
            # and never gate the global stop.
            u = np.full((tv_np.shape[0],), NEG_LARGE, np.float32)
            u[: wl.shape[1]] = ubh[wl[0], wl[1]].astype(np.float32)
            ubw = jnp.asarray(u)

        scored = None
        if index.is_sparse:
            if early_exit:
                inner_kwargs = dict(
                    threshold=float(threshold), k=k, block_q=block_q,
                    block_c=index.block_rows, nc_valid=index.n, grid_q=grid_q,
                )
                nqv = jnp.asarray(B, jnp.int32)
                obs_compile.offer_capture(
                    "serving.sparse_ee_inner", _rect_sparse_ee_inner,
                    Qp, index.bdims, index.bx, ij, tvalid, ubw, nqv,
                    **inner_kwargs,
                )
                values, indices, counts, scored = _rect_sparse_ee_inner(
                    Qp, index.bdims, index.bx, ij, tvalid, ubw, nqv,
                    **inner_kwargs,
                )
            else:
                inner_kwargs = dict(
                    threshold=float(threshold), k=k, block_q=block_q,
                    block_c=index.block_rows, nc_valid=index.n, grid_q=grid_q,
                    use_kernel=use_kernel, interpret=interpret,
                )
                obs_compile.offer_capture(
                    "serving.sparse_inner", _rect_sparse_inner,
                    Qp, index.bdims, index.bx, ij, tvalid, **inner_kwargs,
                )
                values, indices, counts = _rect_sparse_inner(
                    Qp, index.bdims, index.bx, ij, tvalid, **inner_kwargs,
                )
        elif early_exit and use_kernel:
            inner_kwargs = dict(
                threshold=float(threshold), k=k, block_q=block_q,
                block_c=index.block_rows, nc_valid=index.n, grid_q=grid_q,
                nq_valid=B, interpret=interpret,
            )
            obs_compile.offer_capture(
                "serving.dense_ee_kernel", _rect_dense_ee_kernel,
                Qp, index.corpus, ij, tvalid, ubw, **inner_kwargs,
            )
            values, indices, counts, scored = _rect_dense_ee_kernel(
                Qp, index.corpus, ij, tvalid, ubw, **inner_kwargs,
            )
        elif early_exit:
            inner_kwargs = dict(
                threshold=float(threshold), k=k, block_q=block_q,
                block_c=index.block_rows, nc_valid=index.n, grid_q=grid_q,
            )
            nqv = jnp.asarray(B, jnp.int32)
            obs_compile.offer_capture(
                "serving.dense_ee_inner", _rect_dense_ee_inner,
                Qp, index.corpus, ij, tvalid, ubw, nqv, **inner_kwargs,
            )
            values, indices, counts, scored = _rect_dense_ee_inner(
                Qp, index.corpus, ij, tvalid, ubw, nqv, **inner_kwargs,
            )
        else:
            inner_kwargs = dict(
                threshold=float(threshold), k=k, block_q=block_q,
                block_c=index.block_rows, nc_valid=index.n, grid_q=grid_q,
                use_kernel=use_kernel, interpret=interpret,
            )
            obs_compile.offer_capture(
                "serving.dense_inner", _rect_dense_inner,
                Qp, index.corpus, ij, tvalid, **inner_kwargs,
            )
            values, indices, counts = _rect_dense_inner(
                Qp, index.corpus, ij, tvalid, **inner_kwargs,
            )

        if scored is not None and (telemetry.enabled() or metrics.enabled()):
            skipped = live - int(scored)
            if metrics.enabled():
                metrics.incr("serving.early_exit_skipped_tiles", skipped)
            if telemetry.enabled():
                telemetry.record(telemetry.ApssStats(
                    variant="serving/early-exit",
                    n=index.n, m=index.m, block_rows=index.block_rows,
                    sparse=index.is_sparse,
                    live_tiles=live, total_tiles=int(mk.size),
                    extra={"batch": B, "skipped_tiles": skipped},
                ))
            trace.annotate(early_exit_skipped_tiles=skipped)
        return Matches(values=values[:B], indices=indices[:B], counts=counts[:B])


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "block_q", "use_minsize", "normalized"),
)
def _query_mask(Qp, corpus_stats, *, threshold, block_q, use_minsize, normalized):
    """Query-side block stats + live mask vs PREBUILT corpus stats.

    The only per-call bound computation: ``O(B·m)`` for the query summary
    and one ``(B/bq × nb)`` matmul for the upper bounds. Corpus-side stats
    arrive as index leaves — never recomputed here.
    """
    obs_compile.mark("query_mask")
    with jax.named_scope("mask"):
        qstats = dense_block_stats(Qp.astype(jnp.float32), block_q)
        return live_tile_mask(
            qstats, corpus_stats, threshold,
            use_minsize=use_minsize, normalized=normalized, return_ub=True,
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_q", "block_c", "nc_valid", "grid_q",
        "use_kernel", "interpret",
    ),
)
def _rect_dense_inner(
    Qp, C, ij, tvalid, *,
    threshold, k, block_q, block_c, nc_valid, grid_q, use_kernel, interpret,
):
    """Score live rectangular tiles of a DENSE index; fold to Matches."""
    obs_compile.mark("dense_inner")
    m = Qp.shape[1]
    if use_kernel:
        bk = _pick_bk(m, 512)
        padk = (-m) % bk
        Qk = jnp.pad(Qp, ((0, 0), (0, padk))) if padk else Qp
        Ck = jnp.pad(C, ((0, 0), (0, padk))) if padk else C
        fv, fi, fc = rect_tile_candidates_pallas(
            Qk, Ck, ij, threshold, k,
            block_q=block_q, block_c=block_c, block_k=bk,
            nc_valid=nc_valid, interpret=interpret,
        )
    else:
        Qb = Qp.reshape(grid_q, block_q, m)
        Cb = C.reshape(-1, block_c, m)

        def tile(_, t):
            s = jnp.einsum(
                "qm,cm->qc", Qb[ij[0, t]], Cb[ij[1, t]],
                precision=SCORE_PRECISION,
                preferred_element_type=jnp.float32,
            )
            return _, _rect_tile_packets(
                s, ij[1, t], threshold=threshold, k=k,
                block_q=block_q, block_c=block_c, nc_valid=nc_valid,
                topk=_topk_sort,
            )

        _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij.shape[1]))
    return fold_rect_packets(
        ij, tvalid, fv, fi, fc[..., 0], grid_q=grid_q,
        grid_c=C.shape[0] // block_c, block_q=block_q, k=k,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_q", "block_c", "nc_valid", "grid_q",
        "use_kernel", "interpret",
    ),
)
def _rect_sparse_inner(
    Qp, bdims, bx, ij, tvalid, *,
    threshold, k, block_q, block_c, nc_valid, grid_q, use_kernel, interpret,
):
    """Score live rectangular tiles of a SPARSE index; fold to Matches.

    Per live tile ``(qi, cj)``: the query block's components at the corpus
    block's support dims (``bdims[cj]``) are gathered — the sentinel pad
    ``m`` hits an appended zero column — and contracted against the
    support-densified corpus block ``bx[cj]``. Exact, because every corpus
    nonzero lies inside its own block support (DESIGN.md §5/§6); MXU work
    is ``O(bq · bm · S)``, never ``O(bq · bm · m)``.
    """
    obs_compile.mark("sparse_inner")
    Qext = jnp.pad(Qp.astype(jnp.float32), ((0, 0), (0, 1)))
    Qb = Qext.reshape(grid_q, block_q, -1)

    def gather_t(t):
        return jnp.take(Qb[ij[0, t]], bdims[ij[1, t]], axis=1)  # (bq, S)

    if use_kernel:
        _, qg = lax.scan(
            lambda _, t: (_, gather_t(t)), 0, jnp.arange(ij.shape[1])
        )
        fv, fi, fc = rect_sparse_tile_candidates_pallas(
            qg, bx, ij, threshold, k,
            block_q=block_q, block_c=block_c, nc_valid=nc_valid,
            interpret=interpret,
        )
    else:

        def tile(_, t):
            s = jnp.einsum(
                "qs,cs->qc", gather_t(t), bx[ij[1, t]],
                precision=SCORE_PRECISION,
                preferred_element_type=jnp.float32,
            )
            return _, _rect_tile_packets(
                s, ij[1, t], threshold=threshold, k=k,
                block_q=block_q, block_c=block_c, nc_valid=nc_valid,
                topk=_topk_sort,
            )

        _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij.shape[1]))
    return fold_rect_packets(
        ij, tvalid, fv, fi, fc[..., 0], grid_q=grid_q,
        grid_c=bx.shape[0], block_q=block_q, k=k,
    )


# ---------------------------------------------------------------------------
# Early-exit scoring: fused while_loop over the ub-descending worklist
# ---------------------------------------------------------------------------


def _ee_fold(score_tile, ij, tvalid, ub, nq_valid, *, grid_q, block_q, k):
    """Fused score+fold with early exit (the traced half of ``early_exit``).

    Merges one packet at a time (``_merge_packet``, in worklist order),
    which ``fold_rect_packets`` matches bit for bit with every block at
    once, inside a ``lax.while_loop`` that carries the running top-k
    buffers, and adds two sound skips derived from the worklist's
    upper-bound-descending order:

    - tile skip — every live row of the tile's query block already holds
      k real values ≥ this tile's bound, so no candidate in it (value ≤
      bound) can displace a buffer entry (ties lose to the buffer under
      the stable merge): the tile's score/packet work is skipped;
    - global stop — the minimum k-th over ALL live rows beats this tile's
      bound, which bounds every remaining tile (descending order): the
      loop terminates.

    Padded query rows (flat id ≥ ``nq_valid``) are masked to +LARGE so an
    eternally-unfilled padding row can never pin the scan; bucket-padding
    worklist entries carry ``ub = NEG_LARGE`` and are always skipped.
    Returns ``(values, indices, counts, scored)`` with counts saturated at
    k (a skipped tile's matches beyond the k already held are not
    counted — deterministically ``min(exact_count, k)``).
    """
    T = ij.shape[1]
    ubv = jnp.where(tvalid, ub.astype(jnp.float32), jnp.float32(NEG_LARGE))
    rows = jnp.arange(grid_q * block_q, dtype=jnp.int32).reshape(
        grid_q, block_q
    )
    row_live = rows < nq_valid
    big = -jnp.float32(NEG_LARGE)

    def cond(state):
        t, done = state[0], state[1]
        return (t < T) & ~done

    def body(state):
        t, _done, cv, ci, cc, scored = state
        kth = jnp.where(row_live, cv[:, :, k - 1], big)
        u = ubv[t]
        qi = ij[0, t]
        blk_kth = lax.dynamic_index_in_dim(kth, qi, 0, keepdims=False)
        tile_skip = (~tvalid[t]) | (jnp.min(blk_kth) >= u)
        done = (~tvalid[t]) | (jnp.min(kth) >= u)

        def merge(args):
            cv, ci, cc = args
            fv, fi, fc = score_tile(t)
            return _merge_packet(cv, ci, cc, qi, fv, fi, fc[:, 0], k)

        cv, ci, cc = lax.cond(tile_skip, lambda a: a, merge, (cv, ci, cc))
        scored = scored + jnp.where(tile_skip, 0, 1).astype(jnp.int32)
        return (t + 1, done, cv, ci, cc, scored)

    state = (
        jnp.int32(0),
        jnp.zeros((), jnp.bool_),
        jnp.full((grid_q, block_q, k), -jnp.inf, jnp.float32),
        jnp.full((grid_q, block_q, k), -1, jnp.int32),
        jnp.zeros((grid_q, block_q), jnp.int32),
        jnp.int32(0),
    )
    _t, _d, cv, ci, cc, scored = lax.while_loop(cond, body, state)
    values = jnp.where(ci >= 0, cv, NEG_INF).reshape(grid_q * block_q, k)
    indices = ci.reshape(grid_q * block_q, k)
    counts = jnp.minimum(cc, k).reshape(grid_q * block_q)
    return values, indices, counts, scored


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_q", "block_c", "nc_valid", "grid_q",
    ),
)
def _rect_dense_ee_inner(
    Qp, C, ij, tvalid, ub, nq_valid, *,
    threshold, k, block_q, block_c, nc_valid, grid_q,
):
    """Early-exit dense scoring: while_loop over the ub-ordered worklist.

    ``nq_valid`` is a TRACED scalar (not static) so varying batch sizes
    inside one ``block_q`` bucket share a single compilation, exactly like
    the non-early-exit inners.
    """
    obs_compile.mark("dense_ee_inner")
    m = Qp.shape[1]
    Qb = Qp.reshape(grid_q, block_q, m)
    Cb = C.reshape(-1, block_c, m)

    def score_tile(t):
        s = jnp.einsum(
            "qm,cm->qc", Qb[ij[0, t]], Cb[ij[1, t]],
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        return _rect_tile_packets(
            s, ij[1, t], threshold=threshold, k=k,
            block_q=block_q, block_c=block_c, nc_valid=nc_valid,
            topk=_topk_sort,
        )

    return _ee_fold(
        score_tile, ij, tvalid, ub, nq_valid,
        grid_q=grid_q, block_q=block_q, k=k,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_q", "block_c", "nc_valid", "grid_q",
    ),
)
def _rect_sparse_ee_inner(
    Qp, bdims, bx, ij, tvalid, ub, nq_valid, *,
    threshold, k, block_q, block_c, nc_valid, grid_q,
):
    """Early-exit sparse scoring: a skipped tile skips its support gather
    AND its contraction (``use_kernel`` requests also land here — the
    gather/score loop is the early-exit seam for sparse indexes)."""
    obs_compile.mark("sparse_ee_inner")
    Qext = jnp.pad(Qp.astype(jnp.float32), ((0, 0), (0, 1)))
    Qb = Qext.reshape(grid_q, block_q, -1)

    def score_tile(t):
        qg = jnp.take(Qb[ij[0, t]], bdims[ij[1, t]], axis=1)  # (bq, S)
        s = jnp.einsum(
            "qs,cs->qc", qg, bx[ij[1, t]],
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        return _rect_tile_packets(
            s, ij[1, t], threshold=threshold, k=k,
            block_q=block_q, block_c=block_c, nc_valid=nc_valid,
            topk=_topk_sort,
        )

    return _ee_fold(
        score_tile, ij, tvalid, ub, nq_valid,
        grid_q=grid_q, block_q=block_q, k=k,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "threshold", "k", "block_q", "block_c", "nc_valid", "grid_q",
        "nq_valid", "interpret",
    ),
)
def _rect_dense_ee_kernel(
    Qp, C, ij, tvalid, ub, *,
    threshold, k, block_q, block_c, nc_valid, grid_q, nq_valid, interpret,
):
    """Early-exit dense scoring through the Pallas rect kernel.

    The kernel carries the running top-k VALUES in VMEM scratch and gates
    each tile's MXU work on the same skip test as :func:`_ee_fold`
    (``kernels.apss_block.fused``); the grid itself cannot stop early, so
    skipped tiles emit neutral packets plus a flag. ``nq_valid`` is static
    here (it is baked into the kernel) — TPU serving uses fixed batches.
    """
    obs_compile.mark("dense_ee_kernel")
    m = Qp.shape[1]
    bk = _pick_bk(m, 512)
    padk = (-m) % bk
    Qk = jnp.pad(Qp, ((0, 0), (0, padk))) if padk else Qp
    Ck = jnp.pad(C, ((0, 0), (0, padk))) if padk else C
    ubk = jnp.where(tvalid, ub.astype(jnp.float32), jnp.float32(NEG_LARGE))
    fv, fi, fc, sk = rect_tile_candidates_early_exit_pallas(
        Qk, Ck, ij, ubk, threshold, k,
        block_q=block_q, block_c=block_c, block_k=bk,
        nc_valid=nc_valid, nq_valid=nq_valid, interpret=interpret,
    )
    values, indices, counts = fold_rect_packets(
        ij, tvalid, fv, fi, fc[..., 0], grid_q=grid_q,
        grid_c=C.shape[0] // block_c, block_q=block_q, k=k,
    )
    counts = jnp.minimum(counts, k)
    scored = jnp.sum(jnp.where(tvalid, 1 - sk, 0).astype(jnp.int32))
    return values, indices, counts, scored


# ---------------------------------------------------------------------------
# Sharded per-shard scoring (mesh-placed indexes)
# ---------------------------------------------------------------------------


def _sharded_query_pruned(
    index, Q, threshold, k, *, block_q, use_kernel, use_minsize, interpret
):
    """Host half of sharded serving: per-shard worklists from global stats.

    One ``_query_mask`` against the replicated corpus stats yields the
    GLOBAL live mask + bounds; each shard's contiguous block range
    (``index.shard_block_range``) is sliced out and compacted into its own
    ub-descending worklist in LOCAL block coordinates. All shards pad to a
    COMMON power-of-two bucket (retrace discipline: one jit cache entry
    per bucket, shared by every shard), stack to ``(p, 2, T)`` /
    ``(p, T)``, and enter one ``shard_map``.
    """
    if index.is_sparse and use_kernel:
        raise NotImplementedError(
            "sharded sparse indexes score via the XLA gather path (no "
            "bdims/bx support compaction is built per shard); use_kernel "
            "applies to dense shards"
        )
    B = Q.shape[0]
    p = index.n_shards
    rem = (-B) % block_q
    Qp = jnp.pad(Q, ((0, rem), (0, 0))) if rem else Q
    grid_q = Qp.shape[0] // block_q
    with trace.span("query/mask"):
        mask, ub = _query_mask(
            Qp, index.stats, threshold=float(threshold), block_q=block_q,
            use_minsize=use_minsize, normalized=index.normalized,
        )
        mk = np.asarray(mask)
        ubh = np.asarray(ub)
    with trace.span("query/worklist", batch=B, shards=p):
        wls = []
        for s in range(p):
            lo, hi = index.shard_block_range(s)
            wls.append(compact_rect_worklist(mk[:, lo:hi], ubh[:, lo:hi]))
        live = sum(0 if w is None else int(w.shape[1]) for w in wls)
        # every shard pads to the longest shard's power-of-two bucket
        Tmax = max((int(w.shape[1]) for w in wls if w is not None), default=0)
        Tb = 1 << max(0, (Tmax - 1).bit_length())
        trace.annotate(
            live=live, total=int(mk.size), entries=p * Tb if live else 0,
            fold_slots=(
                p * grid_q * fold_ranks(Tb, index.nb_local) if live else 0
            ),
        )
    if telemetry.enabled() or metrics.enabled():
        depth = (
            int(index.corpus[0].shape[1]) if index.is_sparse
            else int(index.corpus.shape[1])
        )
        if telemetry.enabled():
            telemetry.record(telemetry.ApssStats(
                variant="serving/query-sharded",
                n=index.n, m=index.m, devices=p,
                block_rows=index.block_rows, sparse=index.is_sparse,
                flops=2.0 * live * block_q * index.block_rows * depth,
                live_tiles=live, total_tiles=int(mk.size),
                tile_counts=tuple(
                    0 if w is None else int(w.shape[1]) for w in wls
                ),
                extra={"batch": B, "use_kernel": use_kernel},
            ))
        if metrics.enabled():
            metrics.observe(
                "serving.live_tile_fraction", live / max(1, mk.size)
            )
    if live == 0:
        return empty_matches(B, k)
    ij_all = np.zeros((p, 2, Tb), np.int32)
    tv_all = np.zeros((p, Tb), bool)
    for s, w in enumerate(wls):
        if w is None:
            continue
        ij_all[s, :, : w.shape[1]] = w
        tv_all[s, : w.shape[1]] = True
    with trace.span("query/dispatch", early_exit=False):
        out = _sharded_query(
            Qp, index.corpus, jnp.asarray(ij_all), jnp.asarray(tv_all),
            mesh=index.mesh, axis_name=index.axis_name, kind=index.kind,
            threshold=float(threshold), k=k, block_q=block_q, grid_q=grid_q,
            block_rows=index.block_rows, nb_loc=index.nb_local,
            n_valid=index.n, use_kernel=use_kernel, interpret=interpret,
        )
    parts = [jax.tree.map(lambda x: x[i], out) for i in range(p)]
    mm = functools.reduce(merge_matches, parts)
    return Matches(mm.values[:B], mm.indices[:B], mm.counts[:B])


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axis_name", "kind", "threshold", "k", "block_q", "grid_q",
        "block_rows", "nb_loc", "n_valid", "use_kernel", "interpret",
    ),
)
def _sharded_query(
    Qp, corpus, ij, tvalid, *, mesh, axis_name, kind, threshold, k,
    block_q, grid_q, block_rows, nb_loc, n_valid, use_kernel, interpret,
):
    """One shard_map: replicated queries × per-device PRUNED worklist.

    Each device receives its own compacted worklist slice (local corpus
    block coordinates) and scores exactly those tiles — the XLA tile scan
    off-TPU, the rect Pallas kernel with ``use_kernel`` (the worklist rides
    a 3-row scalar-prefetch: rows 0–1 index local DMA blocks, row 2
    carries the GLOBAL block id so packet column ids and validity are
    global). Returns per-shard partial Matches STACKED on a leading
    ``(p,)`` axis — the caller merges them host-side (the partials' column
    ranges are disjoint by construction, so ``merge_matches`` is exact).
    Corpus padding rows (which live only in the last shard) never match:
    validity is evaluated against GLOBAL row ids.
    """
    obs_compile.mark("sharded_query")

    def dense_body(Qr, C_loc, ij_s, tv_s):
        ij_l = ij_s[0]
        tv = tv_s[0]
        nb_off = lax.axis_index(axis_name) * nb_loc
        mloc = C_loc.shape[1]
        if use_kernel:
            bk = _pick_bk(mloc, 512)
            padk = (-mloc) % bk
            Qk = jnp.pad(Qr, ((0, 0), (0, padk))) if padk else Qr
            Ck = jnp.pad(C_loc, ((0, 0), (0, padk))) if padk else C_loc
            ij3 = jnp.concatenate([ij_l, ij_l[1:2] + nb_off], axis=0)
            fv, fi, fc = rect_tile_candidates_pallas(
                Qk, Ck, ij3, threshold, k,
                block_q=block_q, block_c=block_rows, block_k=bk,
                nc_valid=n_valid, interpret=interpret,
            )
        else:
            Qb = Qr.reshape(grid_q, block_q, mloc)
            Cb = C_loc.reshape(nb_loc, block_rows, mloc)

            def tile(_, t):
                s = jnp.einsum(
                    "qm,cm->qc", Qb[ij_l[0, t]], Cb[ij_l[1, t]],
                    precision=SCORE_PRECISION,
                    preferred_element_type=jnp.float32,
                )
                return _, _rect_tile_packets(
                    s, ij_l[1, t] + nb_off, threshold=threshold, k=k,
                    block_q=block_q, block_c=block_rows, nc_valid=n_valid,
                    topk=_topk_sort,
                )

            _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij_l.shape[1]))
        v, i, c = fold_rect_packets(
            ij_l, tv, fv, fi, fc[..., 0],
            grid_q=grid_q, grid_c=nb_loc, block_q=block_q, k=k,
        )
        return Matches(v[None], i[None], c[None])

    def sparse_body(Qr, idxL, valL, nnzL, ij_s, tv_s):
        del nnzL  # scoring sums every (0-padded) slot; nnz not needed
        ij_l = ij_s[0]
        tv = tv_s[0]
        cap = idxL.shape[1]
        nb_off = lax.axis_index(axis_name) * nb_loc
        Ci = idxL.reshape(nb_loc, block_rows, cap)
        Cv = valL.reshape(nb_loc, block_rows, cap)
        Qb = Qr.astype(jnp.float32).reshape(grid_q, block_q, -1)

        def tile(_, t):
            s = gather_dot(Qb[ij_l[0, t]], Ci[ij_l[1, t]], Cv[ij_l[1, t]])
            return _, _rect_tile_packets(
                s, ij_l[1, t] + nb_off, threshold=threshold, k=k,
                block_q=block_q, block_c=block_rows, nc_valid=n_valid,
                topk=_topk_sort,
            )

        _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij_l.shape[1]))
        v, i, c = fold_rect_packets(
            ij_l, tv, fv, fi, fc[..., 0],
            grid_q=grid_q, grid_c=nb_loc, block_q=block_q, k=k,
        )
        return Matches(v[None], i[None], c[None])

    stacked = Matches(
        values=P(axis_name, None, None),
        indices=P(axis_name, None, None),
        counts=P(axis_name, None),
    )
    if kind == "dense":
        return shard_map(
            dense_body, mesh=mesh,
            in_specs=(
                P(None, None), P(axis_name, None),
                P(axis_name, None, None), P(axis_name, None),
            ),
            out_specs=stacked, check_vma=False,
        )(Qp, corpus, ij, tvalid)
    idx, val, nnz = corpus
    return shard_map(
        sparse_body, mesh=mesh,
        in_specs=(
            P(None, None), P(axis_name, None), P(axis_name, None),
            P(axis_name), P(axis_name, None, None), P(axis_name, None),
        ),
        out_specs=stacked, check_vma=False,
    )(Qp, idx, val, nnz, ij, tvalid)
