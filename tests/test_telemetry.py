"""Telemetry-backed wire-volume tests (ISSUE 4 satellite).

The ROADMAP noted the schedules' wire-volume claims were "verified by
construction, not measured". These tests execute the instrumented variants
under a :class:`~repro.planner.telemetry.CommLog` and assert the recorded
per-hop byte counts — halfring moves ~half the ring's block bytes, the
sparse ring's per-hop bytes scale with ``cap/m`` — turning the claims into
executed assertions (the same formulas parameterize the planner's cost
models, so these tests pin the model too).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apss import apss_blocked, normalize_rows
from repro.core.distributed import (
    apss_2d,
    apss_horizontal,
    apss_horizontal_hierarchical,
)
from repro.core.sparse import from_dense
from repro.planner import CommLog
from repro.planner.telemetry import (
    csr_block_bytes,
    dense_block_bytes,
    enabled,
)

T, K = 0.35, 16
P8 = 8


def _dense(n=128, m=1024, dens=0.3, seed=0):
    rng = np.random.default_rng(seed)
    D = np.abs(rng.standard_normal((n, m))).astype(np.float32)
    D *= rng.random((n, m)) < dens
    return np.asarray(normalize_rows(jnp.asarray(D)))


def test_commlog_scoping():
    assert not enabled()
    with CommLog() as log:
        assert enabled()
        assert log.records == []
    assert not enabled()


def test_halfring_halves_ring_bytes(mesh8):
    """S = Sᵀ: the halfring's traveling blocks make p//2 hops vs the ring's
    p-1 — block bytes EXACTLY in that ratio; the caravan overhead keeps the
    total under 0.65× for m ≫ k."""
    D = jnp.asarray(_dense())
    with CommLog() as log:
        apss_horizontal(D, T, K, mesh8, schedule="ring", block_rows=16)
        apss_horizontal(D, T, K, mesh8, schedule="halfring", block_rows=16)
    ring, half = log.records
    rb = ring.bytes_by_payload()["dense_block"]
    hb = half.bytes_by_payload()["dense_block"]
    assert hb * (P8 - 1) == rb * (P8 // 2)
    ratio = half.wire_bytes / ring.wire_bytes
    assert 0.5 <= ratio < 0.65, ratio


def test_sparse_ring_bytes_scale_with_cap(mesh8):
    """The sparse ring's per-hop payload is the CSR triple: bytes/hop drop
    from O(n_loc·m) to O(n_loc·cap) — a factor ≈ (2·cap)/m vs dense."""
    m = 1024
    D = _dense(128, m, 0.05, seed=1)
    sp = from_dense(D)
    n_loc = 128 // P8
    with CommLog() as log:
        apss_horizontal(jnp.asarray(D), T, K, mesh8, schedule="ring", block_rows=16)
        apss_horizontal(sp, T, K, mesh8, "data", schedule="ring", block_rows=16)
    dense_r, sparse_r = log.records
    assert dense_r.hops[0].bytes_per_hop == dense_block_bytes(n_loc, m)
    assert sparse_r.hops[0].bytes_per_hop == csr_block_bytes(n_loc, sp.cap)
    ratio = sparse_r.wire_bytes / dense_r.wire_bytes
    assert ratio == pytest.approx(
        csr_block_bytes(n_loc, sp.cap) / dense_block_bytes(n_loc, m)
    )
    assert ratio < 0.25  # 5% density: cap ≪ m

    # Widening cap (inert padding slots) scales the slot payload linearly.
    sp2 = from_dense(D, cap=2 * sp.cap)
    with CommLog() as log2:
        apss_horizontal(sp2, T, K, mesh8, "data", schedule="ring", block_rows=16)
    b1 = sparse_r.hops[0].bytes_per_hop - n_loc * 4  # minus the nnz vector
    b2 = log2.last.hops[0].bytes_per_hop - n_loc * 4
    assert b2 == 2 * b1


def test_sparse_halfring_halves_csr_hops(mesh8):
    """The wire-halving is schedule-level: the CSR triple rides it too."""
    sp = from_dense(_dense(128, 1024, 0.05, seed=2))
    with CommLog() as log:
        apss_horizontal(sp, T, K, mesh8, "data", schedule="ring", block_rows=16)
        apss_horizontal(sp, T, K, mesh8, "data", schedule="halfring", block_rows=16)
    ring, half = log.records
    assert (
        half.bytes_by_payload()["csr_block"] * (P8 - 1)
        == ring.bytes_by_payload()["csr_block"] * (P8 // 2)
    )


def test_hierarchical_hop_economy():
    """Nested ring: same p-1 total block hops as a flat ring, but the slow
    (outer/pod) axis carries only s_outer - 1 of them."""
    from repro.compat import make_mesh

    D = jnp.asarray(_dense(128, 256, 0.3, seed=3))
    mesh = make_mesh((2, 4), ("pod", "data"))
    with CommLog() as log:
        apss_horizontal_hierarchical(
            D, T, K, mesh, ("pod", "data"), block_rows=16
        )
    rec = log.last
    by_axis = {h.axis: h.hops for h in rec.hops}
    assert by_axis == {"pod": 1, "data": 6}  # (2-1)·1 and (4-1)·2
    assert sum(by_axis.values()) == P8 - 1   # flat-ring total, redistributed


def test_raising_call_records_nothing():
    """Validation runs BEFORE the telemetry record: a rejected call must
    not log wire bytes for an execution that never happened."""
    from repro.compat import make_mesh

    sp = from_dense(_dense(128, 96, 0.2, seed=9))
    mesh = make_mesh((2, 4), ("pod", "data"))
    with CommLog() as log:
        with pytest.raises(ValueError):
            apss_horizontal_hierarchical(
                sp, T, K, mesh, ("pod", "data"), use_kernel=True
            )
    assert log.records == []


def test_blocked_live_fraction_recorded():
    """The sparse worklist path reports its live-tile fraction + histogram
    (no extra device work: the worklist is already host-materialized)."""
    sp = from_dense(_dense(1024, 512, 0.02, seed=4))
    with CommLog() as log:
        apss_blocked(sp, 0.5, K, block_rows=128, use_kernel=True)
    rec = log.last
    assert rec.variant == "blocked/sparse-kernel"
    assert rec.live_tiles is not None and rec.total_tiles == 8 * 8
    assert 0.0 <= rec.live_fraction <= 1.0
    assert len(rec.tile_counts) == 8
    assert rec.imbalance >= 1.0


def test_serving_query_records_live_fraction():
    from repro.serving import build_index, query_topk

    sp = from_dense(_dense(256, 512, 0.05, seed=5))
    index = build_index(sp, block_rows=64, normalize=False)
    Q = _dense(8, 512, 0.05, seed=6)
    with CommLog() as log:
        query_topk(index, jnp.asarray(Q), 0.4, K)
    rec = log.last
    assert rec.variant == "serving/query"
    assert rec.total_tiles == 1 * (256 // 64)  # one query block × 4 corpus blocks
    assert rec.live_tiles is not None and 0 <= rec.live_tiles <= rec.total_tiles
    assert rec.extra["batch"] == 8


def test_sparse_2d_composed_wire_volume():
    """Composed 2-D schedule: the row-axis ring ships the per-cell CSR pair
    and its spill list exactly ``q-1`` times at ``csr_cell_bytes(n_loc,
    cap_loc, spill)`` per hop (``cap_loc`` the split's cut width, a multiple
    of the gather chunk, not the global cap); the inner accumulation hops
    are representation-agnostic — identical to the dense record's — and run
    once per ring step (hop counts ×q)."""
    from repro.compat import make_mesh
    from repro.core import distributed as dist
    from repro.core.distributed import apss_2d
    from repro.core.sparse import shard_dims
    from repro.planner.telemetry import csr_cell_bytes

    D = _dense(128, 1024, 0.05, seed=8)
    sp = from_dense(D)
    mesh = make_mesh((4, 2), ("data", "model"))
    q, r, n_loc = 4, 2, 32
    cap_loc, spill = dist._cut_width(shard_dims(sp, r)[2].T, q, q * n_loc)
    with CommLog() as log:
        apss_2d(
            jnp.asarray(D), T, K, mesh, accumulation="compressed",
            block_rows=16,
        )
        apss_2d(sp, T, K, mesh, accumulation="compressed", block_rows=16)
    dense_rec, sparse_rec = log.records
    assert sparse_rec.sparse and sparse_rec.extra["cap_loc"] == cap_loc

    (ring,) = [h for h in sparse_rec.hops if h.payload == "csr_cell"]
    assert ring.op == "ppermute" and ring.axis == "data"
    assert ring.hops == q - 1
    assert ring.bytes_per_hop == csr_cell_bytes(n_loc, cap_loc, spill)

    inner_s = [h for h in sparse_rec.hops if h.payload != "csr_cell"]
    inner_d = [h for h in dense_rec.hops if h.payload != "dense_block"]
    assert inner_s == inner_d  # candidate ids/scores never carry the corpus
    assert inner_s and all(h.hops % q == 0 for h in inner_s)

    # The sparse cell undercuts the dense cell's (n_loc, m/r) payload: at 5%
    # density its width is 64 (the realized 39 rounded up to the chunk).
    db = dense_rec.bytes_by_payload()["dense_block"]
    sb = sparse_rec.bytes_by_payload()["csr_cell"]
    assert sb < 0.3 * db  # 8·cap_loc + 12·spill/n_loc ≪ 4·m/r


def test_vertical_compressed_vs_allreduce_volume(mesh8_model):
    """Lemma-1 compaction: the compressed accumulation's collective volume
    is O(p·C) per row vs the allreduce's O(n) — the paper's 10-100× score
    volume reduction, visible in the recorded bytes."""
    D = jnp.asarray(_dense(128, 96, 0.3, seed=7))
    from repro.core.distributed import apss_vertical

    with CommLog() as log:
        apss_vertical(
            D, T, K, mesh8_model, accumulation="allreduce", block_rows=32
        )
        apss_vertical(
            D, T, K, mesh8_model, accumulation="compressed", block_rows=32,
            candidate_capacity=8,
        )
    allred, comp = log.records
    assert comp.wire_bytes < allred.wire_bytes


def test_2d_step_times_one_entry_per_ring_step(mesh4x2):
    """ISSUE 6 satellite: the checkerboard sweep's StepTicker lands one
    per-step wall time in the telemetry record — q entries for a q-step
    row-axis ring — and every (row, col) rank ticks every step."""
    import jax

    D = jnp.asarray(_dense(128, 96, 0.3, seed=11))
    q = mesh4x2.shape["data"]
    with CommLog() as log:
        m = apss_2d(D, T, K, mesh4x2, block_rows=16)
        jax.block_until_ready(m.values)
    rec = log.last
    times = rec.step_times
    assert times is not None and len(times) == q
    assert all(t > 0 for t in times[1:])  # step 0 absorbs compile time
    ticker = rec.step_ticker
    assert ticker.n_steps == q
    ranks = {r for r, _, _ in ticker.ticks}
    assert ranks == set(range(8))
    # the ledger bridge: per-rank deltas feed StragglerReport
    report = ticker.to_step_timer().report()
    assert set(report.rank_ema) == set(range(8))


def test_hierarchical_step_times_count_all_computes():
    """Nested (2, 4) ring: ∏sizes = 8 computes per rank, numbered by the
    traced step counter riding the carry."""
    import jax

    from repro.compat import make_mesh

    D = jnp.asarray(_dense(128, 96, 0.3, seed=12))
    mesh = make_mesh((2, 4), ("pod", "data"))
    with CommLog() as log:
        m = apss_horizontal_hierarchical(
            D, T, K, mesh, ("pod", "data"), block_rows=16
        )
        jax.block_until_ready(m.values)
    times = log.last.step_times
    assert times is not None and len(times) == 8


def test_step_times_none_without_ticker():
    """Variants that don't wire a ticker report None, not garbage."""
    D = jnp.asarray(_dense(64, 96, 0.3, seed=13))
    with CommLog() as log:
        apss_blocked(D, T, K, block_rows=32)
    assert log.last.step_times is None


def test_counter_seam_scopes_to_active_logs():
    from repro.planner import telemetry as tm

    tm.incr("x.y")  # no active log: no-op, no error
    with CommLog() as outer:
        with CommLog() as inner:
            tm.incr("x.y", 2)
        tm.incr("x.y")
    assert inner.counters["x.y"] == 2
    assert outer.counters["x.y"] == 3
