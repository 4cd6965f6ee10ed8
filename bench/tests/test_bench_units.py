"""The benchmark's yardstick on the CPU: data, reference, work, peaks, trace.

Run with ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import data, reference, spec, trace, work  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


# --- data ---------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40 + 3])
def test_sparse_corpus_is_seeded_and_exact(seed):
    a = data.sparse_zipf_csr(200, 5000, 200 * 25, 1.1, seed)
    b = data.sparse_zipf_csr(200, 5000, 200 * 25, 1.1, seed)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    idx, val, nnz = a
    assert nnz.sum() == 200 * 25 and nnz.min() >= 1
    valid = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    for r in range(200):
        row = idx[r, : nnz[r]]
        assert np.all(np.diff(row) > 0)  # sorted, unique
    assert np.all(val[~valid] == 0) and np.all(idx[~valid] == 0)
    np.testing.assert_allclose((val.astype(np.float64) ** 2).sum(1), 1.0, rtol=1e-6)


def test_sparse_corpus_differs_across_seeds_and_follows_zipf():
    a = data.sparse_zipf_csr(400, 2000, 400 * 30, 1.1, 1)
    b = data.sparse_zipf_csr(400, 2000, 400 * 30, 1.1, 2**32 + 1)
    assert not np.array_equal(a[0], b[0])
    idx, _, nnz = a
    valid = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    counts = np.bincount(idx[valid], minlength=2000)
    assert counts[0] > counts[10] > counts[1000]  # head dims are the popular ones


def test_gaussian_rows_and_gaps_are_seeded():
    x = np.asarray(data.gaussian_rows(2**33 + 5, 1, 16, 8))
    y = np.asarray(data.gaussian_rows(2**33 + 5, 1, 16, 8))
    z = np.asarray(data.gaussian_rows(5, 1, 16, 8))
    np.testing.assert_array_equal(x, y)
    assert not np.array_equal(x, z)
    g1 = data.poisson_gaps(1000, 200.0, 1)
    g2 = data.poisson_gaps(1000, 200.0, 2)
    np.testing.assert_allclose(np.sort(g1), np.sort(g2))  # same set, other order
    assert not np.array_equal(g1, g2)
    assert abs(g1.sum() - 1000 / 200.0) < 0.05


# --- reference and comparison -------------------------------------------


def _selfjoin_case(seed=3, n=160, m=900, k=6, t=0.2):
    idx, val, nnz = data.sparse_zipf_csr(n, m, n * 20, 1.1, seed)
    ref = reference.SparseSelfJoin(idx, val, nnz, m)
    scores = ref.scores(0, n)
    s32 = scores.astype(np.float32)
    ok = scores >= t
    masked = np.where(ok, s32, -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(masked, order, axis=1)
    ids = np.where(np.isfinite(vals), order, -1)
    return scores, vals, ids, ok.sum(1), t, k


def test_reference_scores_match_dense_float64():
    idx, val, nnz = data.sparse_zipf_csr(120, 700, 120 * 15, 1.1, 4)
    dense = np.zeros((120, 700))
    for r in range(120):
        dense[r, idx[r, : nnz[r]]] = val[r, : nnz[r]]
    want = dense @ dense.T
    np.fill_diagonal(want, -np.inf)
    np.testing.assert_allclose(
        reference.SparseSelfJoin(idx, val, nnz, 700).scores(0, 120), want, atol=1e-12
    )


def test_judge_accepts_the_reference_answer():
    scores, vals, ids, counts, t, k = _selfjoin_case()
    v = reference.judge(scores, vals, ids, counts, t, k, 1e-6)
    assert v.bad_rows == 0 and v.value_gap < 1e-6 and v.matches > 0


@pytest.mark.parametrize("fault", ["wrong_id", "dropped_id", "count_plus_one",
                                    "duplicate_id", "value_off", "self_pair"])
def test_judge_catches_a_planted_fault(fault):
    scores, vals, ids, counts, t, k = _selfjoin_case()
    vals, ids, counts = vals.copy(), ids.copy(), counts.copy()
    r = int(np.argmax(counts >= k))  # a row with a full top-k
    if fault == "wrong_id":
        worst = int(np.argmin(np.where(np.isfinite(scores[r]), scores[r], np.inf)))
        ids[r, 0] = worst
    elif fault == "dropped_id":
        ids[r, 0], vals[r, 0] = -1, -np.inf
    elif fault == "count_plus_one":
        counts[r] += 1
    elif fault == "duplicate_id":
        ids[r, 1] = ids[r, 0]
    elif fault == "value_off":
        vals[r, 0] += 1e-3
    elif fault == "self_pair":
        ids[r, 0] = r
    v = reference.judge(scores, vals, ids, counts, t, k, 1e-6)
    assert v.bad_rows > 0 or v.value_gap > 1e-6


def test_bfloat16_scores_fail_the_comparison():
    scores, vals, ids, counts, t, k = _selfjoin_case()
    idx, val, nnz = data.sparse_zipf_csr(160, 900, 160 * 20, 1.1, 3)
    dense = np.zeros((160, 900), np.float32)
    for r in range(160):
        dense[r, idx[r, : nnz[r]]] = val[r, : nnz[r]]
    import jax.numpy as jnp

    d16 = jnp.asarray(dense, jnp.bfloat16)
    s = np.array(jnp.einsum("rd,cd->rc", d16, d16, preferred_element_type=jnp.float32))
    np.fill_diagonal(s, -np.inf)
    masked = np.where(s >= t, s, -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")[:, :k]
    v16 = np.take_along_axis(masked, order, axis=1)
    i16 = np.where(np.isfinite(v16), order, -1)
    v = reference.judge(scores, v16, i16, (s >= t).sum(1), t, k, 1e-6)
    assert v.value_gap > 1e-6


def test_control_is_three_bfloat16_passes():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 100)).astype(np.float32)
    b = rng.standard_normal((80, 100)).astype(np.float32)
    exact = a.astype(np.float64) @ b.T.astype(np.float64)
    err3 = np.abs(np.asarray(reference.dot_bf16x3(a, b)) - exact).max()
    err32 = np.abs(a @ b.T - exact).max()
    assert err32 < err3 < 1e-2  # coarser than float32, far finer than bfloat16


# --- work counts and peaks ------------------------------------------------


def test_sparse_work_equals_a_brute_force_count():
    n, block, k = 300, 128, 8
    idx, val, nnz = data.sparse_zipf_csr(n, 700, n * 12, 1.1, 9)
    rows = work.block_rows(n, block)
    support, nonzeros = work.csr_blocks(idx, nnz, block)
    wl = np.array([[0, 0, 1, 0, 2], [0, 1, 1, 2, 2]])
    flops, nbytes = work.sparse_selfjoin(wl, rows, support, nonzeros, k)
    madds = 0
    for bi, bj in wl.T:
        ri = range(bi * block, min(n, (bi + 1) * block))
        rj = range(bj * block, min(n, (bj + 1) * block))
        dims = {int(d) for r in ri for d in idx[r, : nnz[r]]}
        for _ in ri:
            for _ in rj:
                madds += len(dims)
    assert flops == 2 * madds
    # every block is touched: the whole corpus read once, results once
    assert nbytes == int(nnz.sum()) * 8 + n * (k * 8 + 4)


@pytest.mark.parametrize("kind", ["sparse", "rect"])
def test_bytes_do_not_change_with_the_tiling(kind):
    """Compulsory bytes: every operand once, results once, whatever the
    block size and however often a tile reuses a block."""
    n, k = 700, 8
    idx, _, nnz = data.sparse_zipf_csr(n, 900, n * 12, 1.1, 5)
    counts = set()
    for block in (64, 128, 256):
        nb = -(-n // block)
        if kind == "sparse":
            full = np.array(np.triu_indices(nb))  # every upper tile live
            rows = work.block_rows(n, block)
            support, nonzeros = work.csr_blocks(idx, nnz, block)
            counts.add(work.sparse_selfjoin(full, rows, support, nonzeros, k)[1])
        else:
            q_rows = work.block_rows(300, block)
            c_rows = work.block_rows(n, block)
            full = np.array(np.meshgrid(np.arange(len(q_rows)), np.arange(nb),
                                        indexing="ij")).reshape(2, -1)
            counts.add(work.rect_dense(full, q_rows, c_rows, 100, k)[1])
    assert len(counts) == 1


def test_rect_work_equals_a_brute_force_count_and_ignores_padding():
    q_rows = work.block_rows(200, 128)       # 128 + 72 real queries
    c_rows = work.block_rows(1000, 256)      # 3 full blocks + 232
    wl = np.array([[0, 0, 1, 1], [0, 3, 1, 2]])
    flops, _ = work.rect_dense(wl, q_rows, c_rows, 100, 10)
    pairs = sum(q_rows[a] * c_rows[b] for a, b in wl.T)
    assert flops == 2 * pairs * 100
    # A bucket-padded worklist would repeat tile (0, 0): the spy records the
    # worklist before padding, and the count of a padded one differs.
    padded = np.concatenate([wl, np.zeros((2, 4), np.int64)], axis=1)
    assert work.rect_dense(padded, q_rows, c_rows, 100, 10)[0] > flops


def _program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def test_spy_records_the_unpadded_worklist_and_the_tiling():
    import jax

    _program()
    import repro.serving.query as query
    from repro.serving.index import build_index

    from bench.spy import CallSpy, query_block

    index = build_index(data.gaussian_rows(1, 1, 600, 16), block_rows=128)
    q = data.normalize_f32(data.gaussian_rows(1, 2, 40, 16))
    with CallSpy(query, "compact_rect_worklist") as spy, \
            CallSpy(query, "_query_mask", keep=query_block) as blocks:
        jax.block_until_ready(query.query_topk(index, q, 0.0, 5, block_q=32))
    (wl,) = spy.kept
    assert wl.shape[1] <= 2 * 5 and wl.shape[1] == len({tuple(c) for c in wl.T})
    assert blocks.kept == [32]
    assert query.compact_rect_worklist is spy._real


def test_spy_records_the_self_join_block():
    import jax
    import jax.numpy as jnp

    _program()
    import repro.kernels.apss_block.sparse as sparse_kernels
    from repro.core.apss import apss_blocked
    from repro.core.sparse import SparseCorpus

    from bench.spy import CallSpy, support_block

    idx, val, nnz = data.sparse_zipf_csr(300, 2000, 300 * 20, 1.1, 2)
    sp = SparseCorpus(jnp.asarray(idx), jnp.asarray(val), jnp.asarray(nnz), 2000)
    with CallSpy(sparse_kernels, "block_support_gather", keep=support_block) as b:
        jax.block_until_ready(apss_blocked(sp, 0.2, 4, block_rows=128, use_kernel=True))
    assert b.kept == [128]


def test_program_spy_reads_the_temporaries_of_what_ran():
    import types

    import jax
    import jax.numpy as jnp

    from bench.memory import ProgramSpy, peak_bytes

    @jax.jit
    def f(x):
        return (jnp.outer(x, x) + 1.0).sum()  # an (n, n) temporary

    mod = types.SimpleNamespace(f=f)
    with ProgramSpy(mod, "f") as spy:
        mod.f(jnp.ones(512, jnp.float32))
        mod.f(jnp.ones(512, jnp.float32))
    assert mod.f is f and len(spy.kept) == 2
    assert spy.temp_bytes() >= 512 * 512 * 4
    got = peak_bytes(jax.devices()[:1], [spy])
    assert got["memory_peak_bytes"] == got["peak_bytes_in_use"] + got["program_temp_bytes"]


def test_peaks_table_knows_the_v5e_and_refuses_others():
    p = spec.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")


def test_every_cell_names_files_that_exist():
    import json

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], root=__import__("pathlib").Path(ROOT))
        assert cell.traffic["kind"] in ("selfjoin", "closed_loop", "open_loop")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        from bench.run import load_reader

        for m in cell.per_layer:
            assert callable(load_reader(m["name"]))


# --- trace reduction --------------------------------------------------------


def _ops(intervals, names):
    texts = [n + (" " + trace.PALLAS_CALL if "kernel" in n else "") for n in names]
    labels = sorted(set(texts))
    return trace.Ops(
        np.array([s for s, _ in intervals], np.int64),
        np.array([e for _, e in intervals], np.int64),
        np.array([labels.index(t) for t in texts], np.int64),
        labels,
    )


def test_reduce_busy_union_kernels_and_gaps():
    ops = _ops([(10, 30), (20, 40), (60, 70), (80, 120)],
               ["fusion", "_sparse_tile_kernel", "fusion", "copy"])
    spans = [("window", 0, 100), ("join", 0, 55), ("score_call", 55, 100)]
    r = trace.reduce([ops], spans)
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx((30 + 10 + 20) * 1e-9)  # [10,40) [60,70) [80,100)
    assert r.kernel_seconds("_sparse_tile_kernel") == pytest.approx(20e-9)
    assert r.op_seconds["copy"] == pytest.approx(20e-9)  # clipped to the window
    # gaps [0,10) and [40,60) -> join (midpoints 5 and 50 are in 'join')...
    assert r.idle_by_span["join"] == pytest.approx(30e-9)
    # ...and [70,80) -> score_call
    assert r.idle_by_span["score_call"] == pytest.approx(10e-9)
    assert r.busy_s + sum(r.idle_by_span.values()) == pytest.approx(r.window_s)


def test_reduce_averages_devices_and_needs_a_window():
    a = _ops([(0, 50)], ["x"])
    b = _ops([(0, 100)], ["x"])
    r = trace.reduce([a, b], [("window", 0, 100)])
    assert r.busy_s == pytest.approx(75e-9) and r.devices == 2
    assert r.idle_by_span == {"no_benchmark_span": pytest.approx(25e-9)}
    with pytest.raises(ValueError):
        trace.reduce([a], [("join", 0, 10)])


def test_breakdown_is_bounded_and_sorted():
    names = [f"op{i}" for i in range(15)]
    ops = _ops([(i * 10, i * 10 + i + 1) for i in range(15)], names)
    r = trace.reduce([ops], [("window", 0, 200)])
    b = r.breakdown()
    assert len(b["device_ops"]) == 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True) and b["device_ops"][0][0] == "op14"


def test_breakdown_labels_drop_layouts_and_merge():
    text = ("%while.15 = (s32[]{:T(128)}, f32[378,256,13824]{2,1,0:T(8,128)}, "
            "/*index=2*/s32[2]{0}) while(%tuple.62), body=%wide.region_0")
    assert trace.label_of(text) == (
        "%while.15 = (s32[], f32[378,256,13824], s32[2]) while(%tuple.62), "
        "body=%wide.region_0"
    )
    assert len(trace.label_of("x" * 500)) == trace.LABEL_CHARS
    # Two programs' operations with one label are shown as one entry.
    ops = _ops([(0, 10), (20, 25)], ["a{1}", "a{2}"])
    r = trace.reduce([ops], [("window", 0, 100)])
    assert len(r.op_seconds) == 2
    assert r.breakdown()["device_ops"] == [["a", pytest.approx(15e-9)]]


def test_reduce_a_trace_recorded_on_the_chip():
    from bench.kinds.closed_loop import KERNEL_NAMES as RECT
    from bench.kinds.selfjoin import KERNEL_NAMES as CSR

    devices, spans = trace.read_xspace(FIXTURE)
    assert len(devices) == 1
    r = trace.reduce(devices, spans)
    assert 0 < r.busy_s < r.window_s
    csr, rect = r.kernel_seconds(CSR), r.kernel_seconds(RECT)
    assert csr > 0 and rect > 0 and csr + rect < r.busy_s
    assert r.kernel_seconds("no_such_kernel") == 0
    assert {"join", "score_call"} <= {n for n, _, _ in spans}
    assert r.busy_s + sum(r.idle_by_span.values()) == pytest.approx(r.window_s, rel=1e-9)
    # The host sleeps between the calls are idle time outside any call's span.
    assert r.idle_by_span["no_benchmark_span"] > 0.04
    assert len(r.breakdown()["device_ops"]) == 10
