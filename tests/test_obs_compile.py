"""Compile-time observability: retrace registry, no-retrace contracts,
AOT lower/compile records, call-site capture, metrics merge (ISSUE 9)."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import FlightRecorder, MetricsRegistry, RetraceError
from repro.obs import compile as obs_compile
from repro.obs.compile import CompileMonitor
from repro.obs.metrics import Histogram
from repro.obs.trace import Tracer


# -- registry ----------------------------------------------------------------


def test_mark_counts_and_snapshot_is_a_copy():
    mon = CompileMonitor()
    mon.mark("a")
    mon.mark("a")
    mon.mark("b")
    snap = mon.snapshot()
    assert snap == {"a": 2, "b": 1}
    snap["a"] = 99
    assert mon.counts["a"] == 2  # snapshot is detached


def test_registered_groups_resolve_to_entry_points():
    mon = CompileMonitor()
    mon.register_entry_points("grp", "x", "y")
    c = mon.assert_no_retrace("grp", "z")
    assert c.names == ("x", "y", "z")
    assert obs_compile.entry_points("serving.query")  # registered on import
    assert obs_compile.entry_points("serving.mutable")


# -- contracts ---------------------------------------------------------------


def test_contract_passes_when_nothing_retraces():
    mon = CompileMonitor()
    mon.mark("warm")
    with mon.assert_no_retrace("warm"):
        pass  # no marks inside


def test_contract_raises_at_mark_time():
    mon = CompileMonitor()
    with pytest.raises(RetraceError, match="'hot'"):
        with mon.assert_no_retrace("hot"):
            mon.mark("hot")


def test_contract_watches_everything_when_unnamed():
    mon = CompileMonitor()
    with pytest.raises(RetraceError):
        with mon.assert_no_retrace():
            mon.mark("anything-at-all")


def test_contract_ignores_unwatched_names():
    mon = CompileMonitor()
    with mon.assert_no_retrace("only-this"):
        mon.mark("something-else")


def test_contract_exit_catches_direct_counter_bumps():
    """A direct `counts[x] += 1` bypasses mark(); the exit check still
    catches it through the shared Counter object."""
    mon = CompileMonitor()
    with pytest.raises(RetraceError):
        with mon.assert_no_retrace("legacy"):
            mon.counts["legacy"] += 1


def test_shape_varying_call_trips_contract_and_dumps_flight_record(tmp_path):
    """The acceptance scenario: a jitted entry point warmed at one shape,
    then fed a new shape under an active contract — RetraceError at the
    call, with a flight record dumped for the post-mortem."""
    mon = CompileMonitor()

    @jax.jit
    def entry(x):
        mon.mark("entry")  # trace-time side effect == compilation count
        return x * 2.0

    entry(jnp.zeros((4,)))  # warm at shape (4,)
    with FlightRecorder(directory=str(tmp_path)) as fr:
        with mon.assert_no_retrace("entry"):
            entry(jnp.zeros((4,)))  # cache hit: fine
            with pytest.raises(RetraceError, match="entry"):
                entry(jnp.zeros((8,)))  # new shape: re-trace
    assert fr.dumps and fr.dumps[0][0] == "compile.retrace.entry"
    files = glob.glob(str(tmp_path / "flight_*compile*retrace*entry*.json"))
    assert files, "expected a flight_NNN_compile.retrace.entry dump on disk"


def test_query_topk_hot_path_contract_is_active():
    """Public-API version of the serving no-retrace discipline: warm
    query_topk, then assert the whole serving.query group under a
    contract — and show a shape-breaking query WOULD trip it."""
    from repro.data.sparse import perturbed_queries, sparse_clustered_corpus
    from repro.serving import build_index, query_topk

    sp = sparse_clustered_corpus(128, 64, 6.0, n_clusters=4, seed=0)
    index = build_index(sp, block_rows=32, normalize=False)
    Q = perturbed_queries(sp, 4, seed=1)
    query_topk(index, Q, 0.3, 4)
    with obs_compile.assert_no_retrace("serving.query"):
        query_topk(index, Q, 0.3, 4)  # repeat: no new traces
    with pytest.raises(RetraceError):
        with obs_compile.assert_no_retrace("serving.query"):
            # block_q is a static argument: a new value MUST re-trace
            query_topk(index, Q, 0.3, 4, block_q=16)


def test_mutable_append_delete_contract_is_active():
    from repro.serving.mutable import MutableAPSSIndex

    rng = np.random.default_rng(0)

    def rows(n):
        X = np.abs(rng.standard_normal((n, 32))).astype(np.float32)
        return X / np.linalg.norm(X, axis=1, keepdims=True)

    mi = MutableAPSSIndex(rows(16), threshold=0.2, k=4, block_rows=64)
    Q = rows(4)
    for _ in range(2):  # warm every delta-join/query/delete shape once
        mi.append(rows(8))
        mi.query(Q)
        mi.delete([int(mi.graph()[0][0])])
    with obs_compile.assert_no_retrace("serving.mutable"):
        mi.append(rows(8))
        mi.query(Q)
        mi.delete([int(mi.graph()[0][0])])


# -- AOT lower/compile -------------------------------------------------------


def test_lower_and_compile_records_times_and_memory():
    mon = CompileMonitor()

    @jax.jit
    def f(x):
        return (x @ x.T).sum()

    with Tracer() as tr:
        compiled, rec = mon.lower_and_compile(
            f, jnp.ones((16, 8)), name="matmul16x8"
        )
    assert rec.name == "matmul16x8"
    assert rec.t_lower_s >= 0 and rec.t_compile_s > 0
    assert rec.total_bytes == (
        rec.argument_bytes + rec.output_bytes + rec.temp_bytes
    )
    assert rec.argument_bytes >= 16 * 8 * 4  # the input buffer at least
    assert mon.records == [rec]
    assert float(compiled(jnp.ones((16, 8)))) == pytest.approx(16 * 16 * 8)
    spans = [s.name for s in tr.walk()]
    assert "compile/matmul16x8" in spans
    d = rec.as_dict()
    assert d["total_bytes"] == rec.total_bytes


# -- call-site capture -------------------------------------------------------


def test_capture_calls_first_offer_wins_and_nests():
    obs_compile.offer_capture("x", None)  # no context: dropped
    with obs_compile.capture_calls() as outer:
        obs_compile.offer_capture("x", "first", 1, a=2)
        obs_compile.offer_capture("x", "second")
        with obs_compile.capture_calls() as inner:
            obs_compile.offer_capture("x", "inner-first")
        obs_compile.offer_capture("y", "why")
    assert outer["x"].fn == "first"
    assert outer["x"].args == (1,) and outer["x"].kwargs == {"a": 2}
    assert outer["y"].fn == "why"
    assert inner["x"].fn == "inner-first"
    assert obs_compile._CAPTURE is None  # context fully unwound


def test_captured_serving_call_lowers_to_the_real_program():
    """The audit seam end-to-end: capture the query inner from a real
    query_topk call, AOT-compile it, and check the compiled program
    reproduces the hot path's scores."""
    from repro.data.sparse import perturbed_queries, sparse_clustered_corpus
    from repro.serving import build_index, query_topk

    sp = sparse_clustered_corpus(128, 64, 6.0, n_clusters=4, seed=3)
    index = build_index(sp, block_rows=32, normalize=False)
    Q = perturbed_queries(sp, 4, seed=4)
    with obs_compile.capture_calls() as calls:
        got = query_topk(index, Q, 0.3, 4)
    assert "serving.sparse_inner" in calls
    call = calls["serving.sparse_inner"]
    mon = CompileMonitor()
    compiled, rec = mon.lower_and_compile(
        call.fn, *call.args, name="cap", **call.kwargs
    )
    assert rec.t_compile_s > 0
    assert "dot" in compiled.as_text() or "convolution" in compiled.as_text()
    assert got.values.shape[0] == Q.shape[0]


# -- metrics merge (satellite: CI matrix-cell aggregation) -------------------


def test_histogram_merge_matches_combined_stream():
    a, b, ref = Histogram(), Histogram(), Histogram()
    rng = np.random.default_rng(0)
    xs = rng.lognormal(0.0, 2.0, 400)
    ys = rng.lognormal(1.0, 1.0, 300)
    for x in xs:
        a.observe(x)
        ref.observe(x)
    for y in ys:
        b.observe(y)
        ref.observe(y)
    b.observe(0.0)
    ref.observe(0.0)
    a.merge(b)
    assert a.count == ref.count and a.zeros == ref.zeros
    assert a.total == pytest.approx(ref.total)
    assert a.min == ref.min and a.max == ref.max
    assert a.buckets == ref.buckets
    for q in (0.5, 0.9, 0.99):
        assert a.quantile(q) == pytest.approx(ref.quantile(q))


def test_histogram_merge_rejects_mismatched_bases():
    with pytest.raises(ValueError, match="base"):
        Histogram().merge(Histogram(base=2.0))


def test_registry_merge_aggregates_matrix_cells():
    cell1, cell2 = MetricsRegistry(), MetricsRegistry()
    cell1.incr("serving.requests", 10)
    cell2.incr("serving.requests", 5)
    cell1.gauge("queue_depth", 3)
    cell2.gauge("queue_depth", 7)
    cell2.gauge("only2", 1)
    for v in (0.1, 0.2):
        cell1.observe("latency_s", v)
    for v in (0.4, 0.8):
        cell2.observe("latency_s", v)
    cell2.observe("only2_s", 1.0)
    cell1.merge(cell2)
    assert cell1.counters["serving.requests"] == 15
    assert cell1.gauges == {"queue_depth": 7, "only2": 1}  # last-wins
    h = cell1.histogram("latency_s")
    assert h.count == 4 and h.max == 0.8 and h.min == pytest.approx(0.1)
    assert cell1.histogram("only2_s").count == 1
