"""Retrieval serving tests (``repro.serving``): APSSIndex build-once/reuse
(trace counters prove the second query rebuilds nothing), rectangular exactness
vs the brute-force oracle across densities/thresholds/k, batched-server ==
one-shot calls, LRU cache behaviour, and sharded partial-merge correctness
on 8 virtual devices."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apss import normalize_rows
from repro.core.matches import extract_matches
from repro.core.sparse import from_dense
from repro.obs import compile as obs_compile
from repro.serving import (
    RetrievalServer,
    build_index,
    query_topk,
)


def _corpus_queries(n, m, density, nq, seed=0):
    rng = np.random.default_rng(seed)
    C = np.abs(rng.standard_normal((n, m))).astype(np.float32)
    C *= rng.random((n, m)) < density
    Q = np.abs(rng.standard_normal((nq, m))).astype(np.float32)
    Q *= rng.random((nq, m)) < density
    Cn = np.asarray(normalize_rows(jnp.asarray(C)))
    Qn = np.asarray(normalize_rows(jnp.asarray(Q)))
    return Cn, Qn


def _rect_oracle(Qn, Cn, t, k):
    """Brute-force rectangular oracle: dense Q·Cᵀ, threshold, top-k."""
    S = jnp.einsum("qm,cm->qc", jnp.asarray(Qn), jnp.asarray(Cn),
                   preferred_element_type=jnp.float32)
    return extract_matches(S, t, k, exclude_self=False)


def _check_rect(got, ref, nq):
    np.testing.assert_array_equal(
        np.asarray(got.counts), np.asarray(ref.counts)[:nq]
    )
    gv, gi = np.asarray(got.values), np.asarray(got.indices)
    rv, ri = np.asarray(ref.values), np.asarray(ref.indices)
    for r in range(nq):
        assert set(gi[r][gi[r] >= 0]) == set(ri[r][ri[r] >= 0]), r
        np.testing.assert_allclose(
            np.sort(gv[r]), np.sort(rv[r]), atol=1e-5
        )


@pytest.mark.parametrize("density", [0.02, 0.1, 0.3])
@pytest.mark.parametrize("threshold,k", [(0.2, 8), (0.5, 4)])
def test_query_topk_rect_exact_dense_and_sparse(density, threshold, k):
    Cn, Qn = _corpus_queries(220, 96, density, 11, seed=int(density * 100))
    ref = _rect_oracle(Qn, Cn, threshold, k)
    for corpus in (Cn, from_dense(Cn)):
        index = build_index(corpus, block_rows=64, normalize=False)
        got = query_topk(index, jnp.asarray(Qn), threshold, k, block_q=16)
        _check_rect(got, ref, 11)


def test_query_topk_negative_threshold_exact():
    """t ≤ 0: every tile is live (bounds are ≥ 0) and every pair matches —
    the pruning must degrade to full scoring, not drop zero-sim pairs."""
    Cn, Qn = _corpus_queries(96, 64, 0.1, 5, seed=3)
    ref = _rect_oracle(Qn, Cn, -0.5, 6)
    index = build_index(from_dense(Cn), block_rows=32, normalize=False)
    got = query_topk(index, jnp.asarray(Qn), -0.5, 6, block_q=8)
    _check_rect(got, ref, 5)


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_query_topk_kernel_matches_xla(kind):
    Cn, Qn = _corpus_queries(256, 128, 0.1, 8, seed=9)
    corpus = Cn if kind == "dense" else from_dense(Cn)
    index = build_index(corpus, block_rows=128, normalize=False)
    ref = _rect_oracle(Qn, Cn, 0.3, 8)
    got = query_topk(
        index, jnp.asarray(Qn), 0.3, 8, block_q=128, use_kernel=True
    )
    _check_rect(got, ref, 8)


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_index_nbytes_counts_every_leaf(kind):
    """Corpus, block stats and (sparse) the support lists and their
    densified blocks, at 4 bytes an entry. A dense corpus is lane-padded
    to the kernel tile, and its stats are taken at that width."""
    from repro.serving.index import index_nbytes

    Cn, _ = _corpus_queries(150, 96, 0.1, 1, seed=2)
    corpus = Cn if kind == "dense" else from_dense(Cn)
    index = build_index(corpus, block_rows=64, normalize=False)
    ncp, nb, m = index.n_padded, index.n_blocks, index.m
    assert (ncp, nb) == (192, 3)
    if kind == "dense":
        width = index.corpus.shape[1]
        assert width == 128  # 96 lane-padded
        want = 4 * ncp * width + 4 * (nb * width + nb + nb)
    else:
        stats = 4 * (nb * m + nb + nb)  # maxw, mw, max_nnz
        cap = corpus.cap
        S = index.bdims.shape[1]
        assert index.bx.shape == (nb, 64, S)
        want = 4 * (2 * ncp * cap + ncp) + stats + 4 * (nb * S + nb * 64 * S)
    assert index_nbytes(index) == want


def test_index_built_once_and_reused_no_retrace():
    """The serving contract: the SECOND query (same shapes) traces nothing
    and rebuilds no support structure — all index leaves enter the jitted
    inners as arguments, and the worklist bucket absorbs live-tile drift."""
    import repro.serving.index as sindex

    Cn, Qn = _corpus_queries(220, 96, 0.1, 8, seed=5)
    index = build_index(from_dense(Cn), block_rows=64, normalize=False)

    got0 = query_topk(index, jnp.asarray(Qn), 0.3, 8, block_q=16)
    # Support-structure builders must not run during queries at all, and
    # no serving entry point may re-trace (public contract API).
    orig = sindex.block_support_gather
    sindex.block_support_gather = None  # any call would TypeError
    try:
        with obs_compile.assert_no_retrace("serving.query"):
            got1 = query_topk(index, jnp.asarray(Qn * 0.7), 0.3, 8, block_q=16)
    finally:
        sindex.block_support_gather = orig
    # Scaled queries keep the same candidate structure admissible but must
    # rescore: results reflect the new values.
    assert np.all(np.asarray(got1.counts) <= np.asarray(got0.counts))
    ref = _rect_oracle(Qn * 0.7, Cn, 0.3, 8)
    _check_rect(got1, ref, 8)


def test_query_batches_hit_worklist_bucket_cache():
    """Different query batches with different live-tile counts land in the
    same power-of-two bucket → zero new traces after warm-up."""
    Cn, _ = _corpus_queries(220, 96, 0.1, 4, seed=6)
    index = build_index(Cn, block_rows=64, normalize=False)
    rng = np.random.default_rng(0)
    traced = []
    for i in range(4):
        Q = np.abs(rng.standard_normal((4, 96))).astype(np.float32)
        Q *= rng.random((4, 96)) < (0.05 + 0.1 * i)
        Qn = np.asarray(normalize_rows(jnp.asarray(Q)))
        before = sum(obs_compile.snapshot().values())
        query_topk(index, jnp.asarray(Qn), 0.25, 4, block_q=8)
        traced.append(sum(obs_compile.snapshot().values()) - before)
    # O(log tiles) buckets, not O(calls): at most the first two calls may
    # compile (distinct bucket sizes); later batches must all be cache hits.
    assert traced[-1] == 0 and traced[-2] == 0, traced


def test_retrieval_server_batched_equals_oneshot():
    Cn, Qn = _corpus_queries(220, 96, 0.12, 10, seed=7)
    index = build_index(Cn, block_rows=64, normalize=False)
    srv = RetrievalServer(
        index, threshold=0.3, k=8, max_batch=4, normalize=False, block_q=8
    )
    results = srv.serve([Qn[i] for i in range(10)])
    assert len(results) == 10 and srv.stats.steps == 3  # 4+4+2 in 3 batches
    for i, res in enumerate(results):
        one = query_topk(index, jnp.asarray(Qn[i][None]), 0.3, 8, block_q=8)
        assert res.count == int(np.asarray(one.counts)[0]), i
        oi = np.asarray(one.indices)[0]
        assert set(res.indices[res.indices >= 0]) == set(oi[oi >= 0]), i
        np.testing.assert_allclose(
            np.sort(res.values), np.sort(np.asarray(one.values)[0]), atol=1e-6
        )


def test_retrieval_server_lru_cache():
    Cn, Qn = _corpus_queries(96, 64, 0.15, 3, seed=8)
    index = build_index(Cn, block_rows=32, normalize=False)
    srv = RetrievalServer(
        index, threshold=0.2, k=4, max_batch=2, normalize=False,
        block_q=4, cache_size=2,
    )
    first = srv.serve([Qn[0], Qn[1]])
    again = srv.serve([Qn[0]])  # repeat → cache, no step
    assert not first[0].cached and again[0].cached
    assert srv.stats.cache_hits == 1
    np.testing.assert_array_equal(first[0].indices, again[0].indices)
    # Capacity 2: touching a third distinct query evicts the LRU entry.
    srv.serve([Qn[2]])
    assert not srv.serve([Qn[1]])[0].cached  # evicted → recomputed


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_sharded_index_partial_merge_exact(mesh8, kind):
    """8-way sharded placement: per-shard top-k partials merged host-side
    must equal the single-host result AND the oracle (global ids, corpus
    padding in the last shard masked)."""
    Cn, Qn = _corpus_queries(220, 96, 0.12, 9, seed=11)  # 220 % 8 ≠ 0 → pad
    corpus = Cn if kind == "dense" else from_dense(Cn)
    index = build_index(corpus, block_rows=16, mesh=mesh8, normalize=False)
    assert index.n_padded % (8 * 16) == 0
    ref = _rect_oracle(Qn, Cn, 0.3, 8)
    got = query_topk(index, jnp.asarray(Qn), 0.3, 8)
    _check_rect(got, ref, 9)


def test_sharded_index_second_query_no_retrace(mesh8):
    Cn, Qn = _corpus_queries(128, 64, 0.15, 4, seed=12)
    index = build_index(Cn, block_rows=16, mesh=mesh8, normalize=False)
    query_topk(index, jnp.asarray(Qn), 0.3, 4)
    with obs_compile.assert_no_retrace("serving.query"):
        query_topk(index, jnp.asarray(Qn * 0.5), 0.3, 4)


# ===========================================================================
# ISSUE 10: traced early-exit, sharded-pruning kernel, per-batch plans,
# continuous batching
# ===========================================================================


def _check_early_exit_bitexact(ref, got, k):
    """Early exit is bit-exact on values AND indices; its counts saturate
    at k (the while_loop stops counting once every row holds k)."""
    np.testing.assert_array_equal(np.asarray(ref.values), np.asarray(got.values))
    np.testing.assert_array_equal(
        np.asarray(ref.indices), np.asarray(got.indices)
    )
    np.testing.assert_array_equal(
        np.minimum(np.asarray(ref.counts), k), np.asarray(got.counts)
    )


@pytest.mark.parametrize("density", [0.02, 0.1, 0.3])
@pytest.mark.parametrize("threshold,k", [(0.2, 8), (0.5, 4), (-0.5, 6)])
def test_query_topk_early_exit_exact(density, threshold, k):
    """EE vs the full scan across densities × thresholds × both corpus
    representations: identical values and indices, counts saturated at k
    (the full scan itself is oracle-checked above)."""
    Cn, Qn = _corpus_queries(220, 96, density, 11, seed=int(density * 100))
    for corpus in (Cn, from_dense(Cn)):
        index = build_index(corpus, block_rows=64, normalize=False)
        ref = query_topk(index, jnp.asarray(Qn), threshold, k, block_q=16)
        got = query_topk(
            index, jnp.asarray(Qn), threshold, k, block_q=16, early_exit=True
        )
        _check_early_exit_bitexact(ref, got, k)


def test_query_topk_early_exit_kernel_exact():
    """The Pallas EE kernel (interpret mode off-TPU) matches the scan EE
    path and the full scan."""
    Cn, Qn = _corpus_queries(256, 128, 0.1, 8, seed=9)
    index = build_index(Cn, block_rows=128, normalize=False)
    ref = query_topk(index, jnp.asarray(Qn), 0.3, 8, block_q=128)
    got = query_topk(
        index, jnp.asarray(Qn), 0.3, 8, block_q=128,
        early_exit=True, use_kernel=True,
    )
    _check_early_exit_bitexact(ref, got, 8)


def test_early_exit_skips_tiles_on_overlap_clusters():
    """The regime EE is for (DESIGN.md §12): clustered corpus with a weak
    shared vocabulary — cross-cluster tiles stay live (mask can't drop
    them) but lose to within-cluster top-k, so the ub-ordered scan skips
    them. Skips must be > 0 AND the result bit-exact."""
    from repro.data.sparse import perturbed_queries, sparse_clustered_corpus
    from repro.obs.metrics import MetricsRegistry

    sp = sparse_clustered_corpus(
        2048, 1024, 16.0, n_clusters=16, seed=2, overlap_dims=8
    )
    index = build_index(sp, block_rows=64, normalize=False)
    Q = jnp.asarray(perturbed_queries(sp, 64, seed=3))
    with MetricsRegistry() as reg:
        ref = query_topk(index, Q, 0.01, 8)
        got = query_topk(index, Q, 0.01, 8, early_exit=True)
    skipped = int(reg.counters.get("serving.early_exit_skipped_tiles", 0))
    assert skipped > 0
    _check_early_exit_bitexact(ref, got, 8)


def test_early_exit_no_retrace_across_batches():
    """EE inners carry nq_valid as a traced scalar: different batch sizes
    within one block_q bucket must not re-trace."""
    Cn, Qn = _corpus_queries(220, 96, 0.1, 8, seed=5)
    index = build_index(Cn, block_rows=64, normalize=False)
    query_topk(index, jnp.asarray(Qn), 0.3, 8, block_q=16, early_exit=True)
    with obs_compile.assert_no_retrace("serving.query"):
        query_topk(
            index, jnp.asarray(Qn[:5] * 0.7), 0.3, 8, block_q=16,
            early_exit=True,
        )


def test_sharded_early_exit_not_implemented(mesh8):
    Cn, Qn = _corpus_queries(128, 64, 0.15, 4, seed=12)
    index = build_index(Cn, block_rows=16, mesh=mesh8, normalize=False)
    with pytest.raises(NotImplementedError):
        query_topk(index, jnp.asarray(Qn), 0.3, 4, early_exit=True)


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_sharded_query_pruned_parity(mesh8, kind):
    """Sharded-query pruning (tentpole a): per-shard compacted worklists
    through one shard_map must equal the unsharded path and the oracle."""
    Cn, Qn = _corpus_queries(220, 96, 0.12, 9, seed=11)
    corpus = Cn if kind == "dense" else from_dense(Cn)
    index = build_index(corpus, block_rows=16, mesh=mesh8, normalize=False)
    flat = build_index(corpus, block_rows=16, normalize=False)
    ref = _rect_oracle(Qn, Cn, 0.3, 8)
    got_sh = query_topk(index, jnp.asarray(Qn), 0.3, 8)
    got_fl = query_topk(flat, jnp.asarray(Qn), 0.3, 8)
    _check_rect(got_sh, ref, 9)
    np.testing.assert_array_equal(
        np.asarray(got_sh.counts), np.asarray(got_fl.counts)
    )


def test_sharded_query_kernel_parity(mesh8):
    """The sharded kernel path (3-row worklists carrying global packet
    ids) must match the sharded scan path exactly."""
    Cn, Qn = _corpus_queries(256, 96, 0.12, 8, seed=13)
    index = build_index(Cn, block_rows=32, mesh=mesh8, normalize=False)
    ref = _rect_oracle(Qn, Cn, 0.3, 8)
    got = query_topk(index, jnp.asarray(Qn), 0.3, 8, use_kernel=True)
    _check_rect(got, ref, 8)


def test_plan_query_topk_sanity():
    """Per-batch plans (tentpole c): exact BlockStats in, a concrete
    (block_q, use_kernel) out, telemetry record emitted, and the planned
    call stays exact."""
    from repro.planner import telemetry
    from repro.planner.costmodel import QueryPlan, plan_query_topk

    Cn, Qn = _corpus_queries(220, 96, 0.1, 8, seed=21)
    index = build_index(Cn, block_rows=64, normalize=False)
    with telemetry.CommLog() as log:
        plan = plan_query_topk(index, 8, 0.3, k=8)
    assert isinstance(plan, QueryPlan)
    assert plan.block_q in (8, 16, 32, 64, 128)
    assert plan.predicted_us > 0
    assert 0.0 <= plan.live_block_fraction <= 1.0
    assert not plan.use_kernel  # CPU: kernel tier not offered
    assert any(
        getattr(r, "variant", None) == "serving/plan" for r in log.records
    )
    ref = _rect_oracle(Qn, Cn, 0.3, 8)
    _check_rect(query_topk(index, jnp.asarray(Qn), 0.3, 8, plan=plan), ref, 8)
    _check_rect(
        query_topk(index, jnp.asarray(Qn), 0.3, 8, plan="auto"), ref, 8
    )


def test_continuous_server_equals_oneshot():
    """Continuous batching (tentpole d): slot-granularity admission changes
    scheduling, never results — every response equals the one-shot call."""
    from repro.serving import ContinuousRetrievalServer

    Cn, Qn = _corpus_queries(220, 96, 0.12, 10, seed=7)
    index = build_index(Cn, block_rows=64, normalize=False)
    with ContinuousRetrievalServer(
        index, workers=2, threshold=0.3, k=8, max_batch=4,
        normalize=False, block_q=8,
    ) as srv:
        results = srv.serve([Qn[i] for i in range(10)])
    assert len(results) == 10
    assert all(r.status == "ok" for r in results)
    for i, res in enumerate(results):
        one = query_topk(index, jnp.asarray(Qn[i][None]), 0.3, 8, block_q=8)
        assert res.count == int(np.asarray(one.counts)[0]), i
        oi = np.asarray(one.indices)[0]
        assert set(res.indices[res.indices >= 0]) == set(oi[oi >= 0]), i
        np.testing.assert_allclose(
            np.sort(res.values), np.sort(np.asarray(one.values)[0]), atol=1e-6
        )


def test_continuous_server_chaos_slow_slot_sheds_late_keeps_exact():
    """A FaultPlan-delayed slot stalls one batch; requests that expire in
    the queue behind it are shed, claimed requests complete exactly —
    shed-late-keep-exact, the degraded-tier contract under continuous
    admission."""
    from repro.robust.faults import Fault, FaultPlan
    from repro.serving import ContinuousRetrievalServer

    Cn, Qn = _corpus_queries(96, 64, 0.15, 12, seed=8)
    index = build_index(Cn, block_rows=32, normalize=False)
    plan = FaultPlan([Fault("delay", "serving", step=0, seconds=0.4)])
    with ContinuousRetrievalServer(
        index, workers=1, threshold=0.2, k=4, max_batch=2,
        normalize=False, block_q=4, deadline_s=0.15, fault_plan=plan,
        cache_size=0,
    ) as srv:
        rids = [srv.submit(Qn[i]) for i in range(12)]
        results = [srv.result(r) for r in rids]
    assert plan.fired.get("delay:serving", 0) == 1
    statuses = {r.status for r in results}
    assert "shed" in statuses and "ok" in statuses
    for i, res in enumerate(results):
        if res.status != "ok":
            assert res.count == 0
            continue
        one = query_topk(index, jnp.asarray(Qn[i][None]), 0.2, 4, block_q=4)
        assert res.count == int(np.asarray(one.counts)[0]), i


def test_continuous_server_result_unknown_rid_raises():
    from repro.serving import ContinuousRetrievalServer

    Cn, Qn = _corpus_queries(96, 64, 0.15, 2, seed=9)
    index = build_index(Cn, block_rows=32, normalize=False)
    with ContinuousRetrievalServer(
        index, workers=1, threshold=0.2, k=4, max_batch=2, normalize=False,
        block_q=4,
    ) as srv:
        rid = srv.submit(Qn[0])
        srv.result(rid)
        with pytest.raises(KeyError):
            srv.result(rid + 999)
