"""Degraded-mode serving + adversarial-input contract (ISSUE 6).

The acceptance bar: under injected slow-shard load the server sheds or
degrades past-deadline requests while in-budget requests still return EXACT
results, with the ``shed`` / ``degraded`` / ``retries`` / ``stale`` counters
asserted both on :class:`ServerStats` and in the telemetry log.
"""

import numpy as np
import pytest

from repro.core.apss import apss_reference
from repro.planner import telemetry
from repro.robust import Fault, FaultPlan
from repro.serving.index import build_index
from repro.serving.server import RetrievalServer

T, K = 0.35, 8


@pytest.fixture(scope="module")
def index(request):
    corpus = request.getfixturevalue("corpus")
    return build_index(corpus, block_rows=32, normalize=False)


@pytest.fixture(scope="module")
def corpus_np(request):
    return np.asarray(request.getfixturevalue("corpus"))


def _serve_one(srv, q):
    return srv.result(srv.submit(q))


# ---------------------------------------------------------------------------
# Adversarial input: the contract is reject-or-sanitize, never garbage
# ---------------------------------------------------------------------------


def test_nan_query_rejected(index):
    srv = RetrievalServer(index, threshold=T, k=K)
    q = np.zeros(index.m, np.float32)
    q[3] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        srv.submit(q)


def test_inf_query_rejected(index):
    srv = RetrievalServer(index, threshold=T, k=K)
    q = np.zeros(index.m, np.float32)
    q[0] = -np.inf
    with pytest.raises(ValueError, match="non-finite"):
        srv.submit(q)


def test_non_numeric_dtype_rejected(index):
    srv = RetrievalServer(index, threshold=T, k=K)
    with pytest.raises(ValueError, match="not numeric"):
        srv.submit(np.array(["x"] * index.m))
    with pytest.raises(ValueError, match="not numeric"):
        srv.submit(np.ones(index.m, np.complex64))


def test_integer_query_cast(index):
    """Numeric non-float dtypes are cast, not rejected."""
    srv = RetrievalServer(index, threshold=T, k=K)
    res = _serve_one(srv, np.zeros(index.m, np.int32))
    assert res.status == "ok"


def test_zero_vector_normalized_to_empty_result(index):
    """All-zero query + normalize=True: normalize_rows keeps it zero (eps
    floor — no divide-by-zero NaNs), it matches nothing, and the result is
    a well-formed empty."""
    srv = RetrievalServer(index, threshold=T, k=K, normalize=True)
    res = _serve_one(srv, np.zeros(index.m, np.float32))
    assert res.status == "ok"
    assert res.count == 0
    assert np.all(np.asarray(res.indices) == -1)
    assert not np.isnan(np.asarray(res.values)).any()


def test_wrong_dim_rejected(index):
    srv = RetrievalServer(index, threshold=T, k=K)
    with pytest.raises(ValueError, match="query dim"):
        srv.submit(np.zeros(index.m + 1, np.float32))


# ---------------------------------------------------------------------------
# Deadlines + admission control under injected slow-shard load
# ---------------------------------------------------------------------------


def test_slow_shard_sheds_late_keeps_exact(index, corpus_np):
    """Acceptance criterion 3: a delay fault stalls the step; the
    tight-deadline request is shed, the in-budget request's answer equals
    the oracle's top-k for that row."""
    plan = FaultPlan([Fault("delay", scope="serving", step=0, seconds=0.05)])
    srv = RetrievalServer(
        index, threshold=T, k=K, cache_size=0, fault_plan=plan,
    )
    with telemetry.CommLog() as log:
        rid_late = srv.submit(corpus_np[0], deadline_s=0.01)
        rid_ok = srv.submit(corpus_np[1])
        while srv._pending:
            srv.step()
    late, ok = srv.result(rid_late), srv.result(rid_ok)
    assert plan.fired["delay:serving"] == 1
    assert late.status == "shed"
    assert late.count == 0
    assert ok.status == "ok"
    # Retrieval semantics: the query is external, so its identical corpus
    # row is a legitimate (self-inclusive) match.
    ref = apss_reference(corpus_np, T, K, exclude_self=False)
    # The oracle sums each score in another order: indices and count
    # exactly, values to f32 rounding.
    assert np.array_equal(np.asarray(ok.indices), np.asarray(ref.indices[1]))
    assert ok.count == int(ref.counts[1])
    np.testing.assert_allclose(
        np.asarray(ok.values), np.asarray(ref.values[1]), rtol=1e-6, atol=1e-6
    )
    assert srv.stats.shed == 1
    assert log.counters["serving.shed"] == 1


def test_admission_budget_sheds_overflow(index, corpus_np):
    srv = RetrievalServer(
        index, threshold=T, k=K, cache_size=0, max_pending=2,
    )
    with telemetry.CommLog() as log:
        rids = [srv.submit(corpus_np[i]) for i in range(5)]
        statuses = [srv.result(r).status for r in rids]
    assert statuses == ["ok", "ok", "shed", "shed", "shed"]
    assert srv.stats.shed == 3
    assert log.counters["serving.shed"] == 3


# ---------------------------------------------------------------------------
# Degradation ladder: kernel → XLA → stale cache, with retries
# ---------------------------------------------------------------------------


def test_kernel_tier_down_degrades_to_xla_exact(index, corpus_np):
    """Persistent kernel-tier failure: one retry (counted), then degrade to
    the XLA tier — the answer is still exact."""
    plan = FaultPlan([Fault("error", scope="serving.kernel", times=-1)])
    srv = RetrievalServer(
        index, threshold=T, k=K, cache_size=0, use_kernel=True,
        max_retries=1, backoff_s=0.001, fault_plan=plan,
    )
    with telemetry.CommLog() as log:
        res = _serve_one(srv, corpus_np[2])
    assert res.status == "ok"
    ref = apss_reference(corpus_np, T, K, exclude_self=False)
    assert np.array_equal(np.asarray(res.indices), np.asarray(ref.indices[2]))
    assert srv.stats.retries == 1
    assert srv.stats.degraded == 1
    assert log.counters["serving.retries"] == 1
    assert log.counters["serving.degraded"] == 1


def test_transient_error_recovers_via_retry(index, corpus_np):
    """A once-off failure is absorbed by the retry, no degradation."""
    plan = FaultPlan([Fault("error", scope="serving.xla", times=1)])
    srv = RetrievalServer(
        index, threshold=T, k=K, cache_size=0,
        max_retries=2, backoff_s=0.001, fault_plan=plan,
    )
    res = _serve_one(srv, corpus_np[3])
    assert res.status == "ok"
    assert srv.stats.retries == 1
    assert srv.stats.degraded == 0


def test_all_tiers_down_serves_stale_then_fails_on_miss(index, corpus_np):
    """ttl_s=0 makes every cache entry stale immediately: fresh submits
    miss, but when scoring is down the stale entry still answers — and a
    query never seen before fails explicitly instead of hanging."""
    srv = RetrievalServer(
        index, threshold=T, k=K, ttl_s=0.0, max_retries=0,
    )
    warm = _serve_one(srv, corpus_np[4])
    assert warm.status == "ok"
    srv.fault_plan = FaultPlan(
        [Fault("error", scope="serving.xla", times=-1)]
    )
    with telemetry.CommLog() as log:
        stale = _serve_one(srv, corpus_np[4])
        miss = _serve_one(srv, corpus_np[5])
    assert stale.status == "stale"
    assert stale.cached
    assert np.array_equal(np.asarray(stale.indices), np.asarray(warm.indices))
    assert miss.status == "failed"
    assert miss.count == 0
    assert srv.stats.stale == 1
    assert log.counters["serving.stale"] == 1
    assert log.counters["serving.degraded"] == 2


def test_normalize_still_applied_on_unnormalized_queries(index, corpus_np):
    """Degraded-mode plumbing must not bypass the normalize contract."""
    srv = RetrievalServer(index, threshold=T, k=K, cache_size=0)
    scaled = _serve_one(srv, corpus_np[6] * 7.5)
    plain = _serve_one(srv, corpus_np[6])
    assert np.array_equal(np.asarray(scaled.indices), np.asarray(plain.indices))
    assert np.allclose(np.asarray(scaled.values), np.asarray(plain.values))
