"""Program spans on the profiler's clock, device scopes, and the server's
queue wait.

One ``trace.span`` call writes to the active ``Tracer`` and, while a JAX
profiler session is on, to the session as a ``TraceAnnotation`` with the
span's counts as metadata. A session alone enters no ``CommLog``, plants no
callback and changes no jaxpr; the jitted inners name their stages
(``support_gather``, ``fold``, ``mask``) for the device trace.
"""

import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.apss import apss_blocked
from repro.data.sparse import (
    perturbed_queries,
    sparse_clustered_corpus,
    sparse_zipfian_corpus,
)
from repro.kernels.apss_block import ops, sparse
from repro.obs import MetricsRegistry, Tracer, trace
from repro.planner import telemetry
from repro.serving import build_index, query
from repro.serving.server import ContinuousRetrievalServer, RetrievalServer

T, K = 0.2, 8
SELFJOIN_SPANS = (
    "apss/bounds", "apss/worklist", "apss/support_gather", "apss/dispatch",
)
QUERY_SPANS = ("query/mask", "query/worklist", "query/dispatch")


@pytest.fixture(scope="module")
def corpus():
    sp = sparse_clustered_corpus(300, 256, 8.0, n_clusters=4, seed=0)
    index = build_index(sp, block_rows=64)
    Q = jnp.asarray(perturbed_queries(sp, 40, seed=1))
    return sp, index, Q


def _session_events(tmp_path, fn):
    """Run ``fn`` inside a CPU profiler session; the program's host events
    as ``(name, start, end, thread, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        assert TraceAnnotation.is_enabled()
        fn()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.split("/")[0] in ("apss", "query", "serving"):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns), (plane.name, i),
                                dict(ev.stats)))
    return out


def _inside(child, parent) -> bool:
    return child[3] == parent[3] and parent[1] <= child[1] and child[2] <= parent[2]


# -- no sink ------------------------------------------------------------------


def test_span_with_no_sink_is_the_shared_noop():
    assert not TraceAnnotation.is_enabled() and not trace.enabled()
    assert trace.span("apss/selfjoin", n=1) is trace.NULL_SPAN
    with trace.span("query/worklist") as s:
        assert s is None
        trace.annotate(live=1, entries=2)  # nowhere to go: no error


def test_no_sink_opens_no_commlog_and_plants_no_callback(corpus, monkeypatch):
    sp, index, Q = corpus
    entered = []
    real = telemetry.CommLog.__enter__
    monkeypatch.setattr(
        telemetry.CommLog, "__enter__", lambda self: entered.append(self) or real(self)
    )
    jax.block_until_ready(apss_blocked(sp, T, K, use_kernel=True))
    jax.block_until_ready(query.query_topk(index, Q, T, K, block_q=16))
    assert entered == [] and not telemetry.enabled()
    jaxpr = str(jax.make_jaxpr(
        lambda D: apss_blocked(D, T, K, block_rows=64, with_prune_stats=True)
    )(sp))
    assert "callback" not in jaxpr


# -- profiler sink ------------------------------------------------------------


def test_profiler_session_records_program_spans_with_counts(
    corpus, tmp_path, monkeypatch
):
    sp, index, Q = corpus
    kept = {}

    def spy(module, name):
        real = getattr(module, name)

        def wrapped(*a, **kw):
            out = real(*a, **kw)
            kept.setdefault(name, []).append(out)
            return out

        monkeypatch.setattr(module, name, wrapped)

    spy(sparse, "compact_worklist")
    spy(query, "compact_rect_worklist")

    def run():
        jax.block_until_ready(apss_blocked(sp, T, K, use_kernel=True))
        jax.block_until_ready(query.query_topk(index, Q, T, K, block_q=16))

    events = _session_events(tmp_path, run)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    (join,) = by_name["apss/selfjoin"]
    assert join[4] == {"n": 300, "k": K, "block_rows": 256}
    for name in SELFJOIN_SPANS:
        (child,) = by_name[name]
        assert _inside(child, join), name
    (call,) = by_name["serving/query"]
    for name in QUERY_SPANS:
        (child,) = by_name[name]
        assert _inside(child, call), name

    (wl,) = kept["compact_worklist"]
    nb = -(-300 // 256)
    assert by_name["apss/worklist"][0][4] == {
        "live": wl.shape[1], "total": nb * (nb + 1) // 2, "entries": wl.shape[1],
        "fold_slots": nb * ops.fold_ranks(2 * wl.shape[1], nb + 1),
    }
    support = by_name["apss/support_gather"][0][4]
    assert support["blocks"] == nb and support["block_rows"] == 256
    assert support["support"] % 128 == 0 and support["support_chunk"] > 0
    # the device build reads back one int32 (the widest support), no corpus
    assert support["host_read_bytes"] == 4 and "apss/upload" not in by_name

    (rwl,) = kept["compact_rect_worklist"]
    _, valid = ops.pad_worklist(rwl)
    stats = by_name["query/worklist"][0][4]
    assert stats["live"] == rwl.shape[1] == valid.sum()
    assert stats["entries"] == valid.size and stats["batch"] == Q.shape[0]
    assert stats["total"] == (-(-Q.shape[0] // 16)) * (-(-index.n // 64))
    assert stats["fold_slots"] == (-(-Q.shape[0] // 16)) * ops.fold_ranks(
        valid.size, -(-index.n // 64)
    )


def test_support_gather_span_names_its_lookup(tmp_path):
    # 300 rows in two 256-row blocks: a 2 × (m + 1) dimension→slot table
    m = 256
    sp = sparse_zipfian_corpus(300, m, 8.0, seed=4)
    events = _session_events(
        tmp_path,
        lambda: jax.block_until_ready(sparse.apss_sparse_compacted(sp, T, K)),
    )
    (ev,) = [e for e in events if e[0] == "apss/support_gather"]
    assert ev[4]["support_lookup"] == "table"
    assert ev[4]["lookup_bytes"] == 2 * (m + 1) * 4


def test_profiler_alone_enters_no_commlog_and_changes_no_jaxpr(
    corpus, tmp_path, monkeypatch
):
    sp, index, Q = corpus
    entered = []
    real = telemetry.CommLog.__enter__
    monkeypatch.setattr(
        telemetry.CommLog, "__enter__", lambda self: entered.append(self) or real(self)
    )

    def fresh():
        return lambda D: apss_blocked(D, T, K, block_rows=64, with_prune_stats=True)

    off = str(jax.make_jaxpr(fresh())(sp))
    seen = {}

    def run():
        seen["telemetry"] = telemetry.enabled()
        seen["jaxpr"] = str(jax.make_jaxpr(fresh())(sp))
        jax.block_until_ready(apss_blocked(sp, T, K, use_kernel=True))
        jax.block_until_ready(query.query_topk(index, Q, T, K, block_q=16))
        seen["after"] = telemetry.enabled() or trace.enabled()

    events = _session_events(tmp_path, run)
    assert {e[0] for e in events} >= {"apss/selfjoin", "serving/query"}
    assert entered == [] and seen["telemetry"] is False and seen["after"] is False
    assert seen["jaxpr"] == off and "callback" not in off


def test_tracer_and_profiler_see_the_same_spans(corpus, tmp_path):
    sp, index, Q = corpus
    with Tracer() as tr:
        events = _session_events(
            tmp_path,
            lambda: jax.block_until_ready(query.query_topk(index, Q, T, K, block_q=16)),
        )
    (call,) = [s for s in tr.walk() if s.name == "serving/query"]
    assert [c.name for c in call.children] == list(QUERY_SPANS)
    wl = call.children[1].attrs
    (ev,) = [e for e in events if e[0] == "query/worklist"]
    assert {k: wl[k] for k in ("live", "total", "entries")} == {
        k: ev[4][k] for k in ("live", "total", "entries")
    }


# -- device scopes --------------------------------------------------------------


def _hlo(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


def test_inners_name_their_gather_fold_and_mask_scopes(corpus):
    sp, index, Q = corpus
    bm, S, T_ = 128, 256, 3
    text = _hlo(
        sparse._sparse_compacted_inner,
        jnp.zeros((2, bm, S)), jnp.zeros((2, S), jnp.int32),
        jnp.zeros((2, bm, sp.cap), jnp.int32), jnp.zeros((2, bm, sp.cap)),
        jnp.zeros((2, T_), jnp.int32),
        threshold=T, k=K, block_m=bm, n_valid=200, grid_m=2, m=sp.m,
        use_kernel=False, interpret=True,
    )
    assert "/support_gather/" in text and "/fold/" in text
    text = _hlo(
        query._rect_dense_inner,
        jnp.zeros((16, 128)), jnp.zeros((64, 128)), jnp.zeros((2, 4), jnp.int32),
        jnp.ones((4,), bool),
        threshold=T, k=K, block_q=16, block_c=32, nc_valid=64, grid_q=1,
        use_kernel=False, interpret=True,
    )
    assert "/fold/" in text
    text = _hlo(
        query._query_mask, jnp.zeros((16, index.m)), index.stats,
        threshold=T, block_q=16, use_minsize=True, normalized=True,
    )
    assert "/mask/" in text


# -- server ------------------------------------------------------------------------


def _requests(index, n):
    rng = np.random.default_rng(3)
    return [rng.standard_normal(index.m).astype(np.float32) for _ in range(n)]


def test_step_server_records_each_requests_queue_wait(corpus):
    _, index, _ = corpus
    srv = RetrievalServer(index, threshold=T, k=K, max_batch=4, block_q=4)
    qs = _requests(index, 6)
    with MetricsRegistry() as reg, Tracer() as tr:
        rids = [srv.submit(q) for q in qs]
        while srv.step():
            pass
    assert all(srv.result(r).status == "ok" for r in rids)
    assert reg.histogram("serving.queue_wait_s").count == len(qs)
    steps = [s for s in tr.walk() if s.name == "serving/step"]
    assert len(steps) == 2 and all(s.attrs["max_wait_ms"] >= 0 for s in steps)
    assert steps[1].attrs["max_wait_ms"] >= steps[0].attrs["max_wait_ms"]


def test_continuous_workers_open_their_own_step_spans(corpus):
    _, index, _ = corpus
    qs = _requests(index, 12)
    with MetricsRegistry() as reg, Tracer() as tr:
        with ContinuousRetrievalServer(
            index, workers=2, threshold=T, k=K, max_batch=4, block_q=4, cache_size=0,
        ) as srv:
            results = srv.serve(qs)
    assert all(r.status == "ok" for r in results)
    assert reg.histogram("serving.queue_wait_s").count == len(qs)
    steps = [s for s in tr.walk() if s.name == "serving/step"]
    assert steps and all(s.parent is tr.root for s in steps)
    assert all("max_wait_ms" in s.attrs for s in steps)
    for s in steps:
        (score,) = s.children
        assert score.name == "serving/score" and score.attrs["tier"] == "xla"
        assert [c.name for c in score.children] == ["serving/query"]


def test_tracer_keeps_one_open_span_stack_per_thread():
    both_open = threading.Barrier(2, timeout=10)

    def work(name):
        with trace.span(name):
            both_open.wait()
            with trace.span(name + "/child"):
                both_open.wait()

    with Tracer() as tr:
        threads = [threading.Thread(target=work, args=(n,)) for n in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    tops = {s.name: s for s in tr.root.children}
    assert set(tops) == {"a", "b"}
    for name, s in tops.items():
        assert [c.name for c in s.children] == [name + "/child"]
