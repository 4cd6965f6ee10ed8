"""Fault-injection suite (ISSUE 6): resumable sweeps + checkpoint integrity.

Recovery is proven for the three fault classes the acceptance criteria
name — kill-mid-sweep (resume on the same AND a reshaped mesh, bit-identical
to an uninterrupted run), checkpoint corruption (detected at load, restore
falls back one kept step), and straggler eviction (StepTimer report → a
smaller mesh → resumed sweep still exact). Every fault comes from a seeded
``FaultPlan`` so each failure is deterministic and each test asserts the
fault actually fired.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.checkpoint import (
    CheckpointCorruptionError,
    CheckpointManager,
    load_checkpoint,
    save_checkpoint,
)
from repro.core.apss import apss_reference
from repro.core.graph import match_set
from repro.distributed.straggler import StepTimer
from repro.planner import telemetry
from repro.robust import (
    Fault,
    FaultPlan,
    InjectedFault,
    ResumableSweep,
    SweepKilled,
    mesh_after_eviction,
)

T, K, BN = 0.35, 16, 32


def _matches_equal(a, b):
    return (
        np.array_equal(np.asarray(a.values), np.asarray(b.values))
        and np.array_equal(np.asarray(a.indices), np.asarray(b.indices))
        and np.array_equal(np.asarray(a.counts), np.asarray(b.counts))
    )


def _agrees_with_oracle(got, ref):
    """Against ``apss_reference``, which sums each score in another order:
    the same match pairs, indices and counts exactly, values to f32
    rounding. (A sweep against its own uninterrupted run stays
    bit-identical: ``_matches_equal``.)"""
    return (
        match_set(got) == match_set(ref)
        and np.array_equal(np.asarray(got.indices), np.asarray(ref.indices))
        and np.array_equal(np.asarray(got.counts), np.asarray(ref.counts))
        and np.allclose(
            np.asarray(got.values), np.asarray(ref.values),
            rtol=1e-6, atol=1e-6,
        )
    )


# ---------------------------------------------------------------------------
# FaultPlan determinism + semantics
# ---------------------------------------------------------------------------


def test_chaos_plan_is_deterministic():
    a = FaultPlan.chaos(7, steps=16, kill=True)
    b = FaultPlan.chaos(7, steps=16, kill=True)
    assert a.faults == b.faults
    c = FaultPlan.chaos(8, steps=16, kill=True)
    assert a.faults != c.faults


def test_fault_times_are_consumed():
    plan = FaultPlan([Fault("error", scope="s", times=2)])
    for _ in range(2):
        with pytest.raises(InjectedFault):
            plan.fail_point("s")
    plan.fail_point("s")  # exhausted: no-op
    assert plan.fired["error:s"] == 2
    assert not plan.armed("error", "s")


def test_unmatched_hooks_are_noops():
    plan = FaultPlan([Fault("kill", step=3)])
    plan.kill_point(2)
    plan.fail_point("anything")
    assert plan.delay("sweep", step=0) == 0.0
    x = np.ones(4)
    assert plan.corrupt_array(x, step=0) is x
    assert plan.total_fired == 0


def test_corrupt_array_is_seeded():
    mk = lambda: FaultPlan([Fault("corrupt", scope="sweep.caravan")], seed=5)
    x = np.linspace(0, 1, 32, dtype=np.float32)
    a = mk().corrupt_array(x.copy(), step=3)
    b = mk().corrupt_array(x.copy(), step=3)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, x)


# ---------------------------------------------------------------------------
# Resumable sweep: exactness
# ---------------------------------------------------------------------------


def test_sweep_matches_oracle(corpus, tmp_path):
    ref = apss_reference(corpus, T, K)
    got = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=str(tmp_path)
    ).run()
    assert _agrees_with_oracle(got, ref)


def test_sweep_mesh_bit_identical_to_single_device(corpus, tmp_path, mesh8):
    """The mesh only changes placement — same bits as the 1-device run."""
    solo = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=str(tmp_path / "s")
    ).run()
    dist = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN,
        directory=str(tmp_path / "d"), mesh=mesh8,
    ).run()
    assert _matches_equal(solo, dist)


def test_sweep_meta_mismatch_refuses_resume(corpus, tmp_path):
    ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=str(tmp_path)
    ).run()
    with pytest.raises(ValueError, match="meta mismatch"):
        ResumableSweep(
            corpus, threshold=0.5, k=K, block_rows=BN,
            directory=str(tmp_path),
        )


# ---------------------------------------------------------------------------
# Fault type 1: kill mid-sweep → resume (same mesh, reshaped mesh)
# ---------------------------------------------------------------------------


def test_kill_then_resume_same_mesh(corpus, tmp_path):
    ref = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=str(tmp_path / "r")
    ).run()
    plan = FaultPlan([Fault("kill", step=2)])
    d = str(tmp_path / "k")
    with pytest.raises(SweepKilled):
        ResumableSweep(
            corpus, threshold=T, k=K, block_rows=BN, directory=d,
            fault_plan=plan,
        ).run()
    assert plan.fired["kill:sweep"] == 1
    with telemetry.CommLog() as log:
        got = ResumableSweep(
            corpus, threshold=T, k=K, block_rows=BN, directory=d
        ).run()
    assert _matches_equal(got, ref)
    # the fault suite's headline counter: steps recovered from disk
    assert log.counters["sweep.resumed_steps"] == 2
    assert log.counters["sweep.checkpoints"] > 0


def test_kill_then_resume_reshaped_mesh(corpus, tmp_path, mesh8):
    """Kill on 8 devices, resume on 4 — Matches identical to uninterrupted."""
    ref = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=str(tmp_path / "r")
    ).run()
    d = str(tmp_path / "k")
    killer = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=d, mesh=mesh8,
        fault_plan=FaultPlan([Fault("kill", step=3)]),
    )
    with pytest.raises(SweepKilled):
        killer.run()
    smaller = Mesh(np.array(jax.devices()[:4]), ("data",))
    resumed = killer.resume_on(smaller)
    got = resumed.run()
    assert resumed.resumed_from == 3
    assert _matches_equal(got, ref)


# ---------------------------------------------------------------------------
# Fault type 2: corruption — traveling packet + checkpoint leaf
# ---------------------------------------------------------------------------


def test_corrupted_caravan_changes_result(corpus, tmp_path):
    """The harness really damages in-flight partials: a corrupted caravan
    survives merging and the final Matches differ from the oracle (at-rest
    checksums cannot see in-flight damage — that is exactness-check
    territory, pinned here)."""
    ref = apss_reference(corpus, T, K)
    plan = FaultPlan([Fault("corrupt", scope="sweep.caravan", step=1)])
    got = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN,
        directory=str(tmp_path), fault_plan=plan,
    ).run()
    assert plan.fired["corrupt:sweep.caravan"] == 1
    assert not _matches_equal(got, ref)


def test_checksum_detects_bitflip(tmp_path):
    state = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    final = save_checkpoint(state, str(tmp_path), 1)
    leaf = os.path.join(final, "w.npy")
    FaultPlan(seed=3).corrupt_file(leaf)
    with pytest.raises(CheckpointCorruptionError, match="checksum mismatch"):
        load_checkpoint(str(tmp_path), 1)


def test_restore_falls_back_past_corrupt_step(corpus, tmp_path):
    """Newest checkpoint corrupt → restore(fallback=True) walks back one
    kept step and the resumed sweep still matches the oracle exactly."""
    ref = apss_reference(corpus, T, K)
    d = str(tmp_path)
    killer = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=d,
        fault_plan=FaultPlan([Fault("kill", step=3)]),
    )
    with pytest.raises(SweepKilled):
        killer.run()
    latest = killer.manager.latest_step()
    assert latest == 3
    step_dir = os.path.join(d, f"step_{latest:010d}")
    leaf = [f for f in os.listdir(step_dir) if f.endswith(".npy")][0]
    FaultPlan(seed=1).corrupt_file(os.path.join(step_dir, leaf))
    with pytest.raises(CheckpointCorruptionError):
        killer.manager.restore(step=latest)
    with pytest.warns(UserWarning, match="falling back"):
        resumed = ResumableSweep(
            corpus, threshold=T, k=K, block_rows=BN, directory=d
        )
        got = resumed.run()
    assert resumed.resumed_from == 2  # one checkpoint window lost, not the job
    assert _agrees_with_oracle(got, ref)


def test_restore_raises_when_all_corrupt(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save({"x": np.ones(4, np.float32)}, 1)
    for s in mgr.all_steps():
        step_dir = os.path.join(str(tmp_path), f"step_{s:010d}")
        leaf = [f for f in os.listdir(step_dir) if f.endswith(".npy")][0]
        FaultPlan(seed=s).corrupt_file(os.path.join(step_dir, leaf))
    with pytest.warns(UserWarning):
        with pytest.raises(CheckpointCorruptionError, match="every kept"):
            mgr.restore(fallback=True)


# ---------------------------------------------------------------------------
# Satellite: async checkpoint writes must never fail silently
# ---------------------------------------------------------------------------


def test_async_write_error_surfaces_on_wait(tmp_path, monkeypatch):
    from repro.checkpoint import checkpointer

    mgr = CheckpointManager(str(tmp_path), keep=2)

    def boom(state, directory, step):
        raise OSError("disk full")

    monkeypatch.setattr(checkpointer, "save_checkpoint", boom)
    mgr.save({"x": np.ones(4, np.float32)}, 1, blocking=False)
    with pytest.raises(OSError, match="disk full"):
        mgr.wait()
    mgr.wait()  # error is raised once, then cleared


def test_async_write_error_surfaces_on_next_save(tmp_path, monkeypatch):
    from repro.checkpoint import checkpointer

    mgr = CheckpointManager(str(tmp_path), keep=2)
    real = checkpointer.save_checkpoint

    def boom(state, directory, step):
        raise OSError("quota exceeded")

    monkeypatch.setattr(checkpointer, "save_checkpoint", boom)
    mgr.save({"x": np.ones(4, np.float32)}, 1, blocking=False)
    monkeypatch.setattr(checkpointer, "save_checkpoint", real)
    with pytest.raises(OSError, match="quota exceeded"):
        mgr.save({"x": np.ones(4, np.float32)}, 2)


# ---------------------------------------------------------------------------
# Fault type 3 (sweep side): straggler eviction feeds a smaller mesh
# ---------------------------------------------------------------------------


def test_evict_report_shrinks_mesh_and_resume_is_exact(corpus, tmp_path, mesh8):
    ref = apss_reference(corpus, T, K)
    d = str(tmp_path)
    killer = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=d, mesh=mesh8,
        fault_plan=FaultPlan([Fault("kill", step=2)]),
    )
    with pytest.raises(SweepKilled):
        killer.run()
    # Synthetic straggler ledger: rank 5 is 10x the median step time.
    timer = StepTimer(tolerance=1.5)
    for rank in range(8):
        for _ in range(4):
            timer.record(rank, 1.0 if rank == 5 else 0.1)
    report = timer.report()
    assert report.evict == [5]
    smaller = mesh_after_eviction(mesh8, report)
    assert smaller.devices.size == 7
    got = killer.resume_on(smaller).run()
    assert _agrees_with_oracle(got, ref)


def test_mesh_after_eviction_noop_without_stragglers(mesh8):
    timer = StepTimer()
    for rank in range(8):
        timer.record(rank, 0.1)
    assert mesh_after_eviction(mesh8, timer.report()) is mesh8


def test_sweep_records_step_times(corpus, tmp_path):
    timer = StepTimer()
    sweep = ResumableSweep(
        corpus, threshold=T, k=K, block_rows=BN, directory=str(tmp_path),
        timer=timer,
    )
    sweep.run()
    assert len(timer.history[0]) == sweep.B  # one wall time per ring step


# ---------------------------------------------------------------------------
# Fault type 4 (ISSUE 7): mutation durability — kill mid-append, WAL rot
# ---------------------------------------------------------------------------


def _mutable_graph_equal(a, b):
    ga, gb = a.graph(), b.graph()
    return np.array_equal(ga[0], gb[0]) and _matches_equal(ga[1], gb[1])


@pytest.mark.parametrize("seam", ["mutable.append", "mutable.commit"])
def test_mutable_kill_mid_append_resumes_bit_identical(tmp_path, seam):
    """Kill between WAL write and apply ('mutable.append') or between
    apply and snapshot ('mutable.commit'): reopening replays the logged
    op and lands bit-identical to the uninterrupted run."""
    from repro.serving import MutableAPSSIndex

    rng = np.random.default_rng(20)
    D = rng.normal(size=(64, 16)).astype(np.float32)
    ref = MutableAPSSIndex(D, threshold=T, k=K)
    plan = FaultPlan([Fault("kill", scope=seam, step=2)])
    d = str(tmp_path / "kill")
    mi = MutableAPSSIndex(
        D[:48], threshold=T, k=K, directory=d, fault_plan=plan
    )
    with pytest.raises(SweepKilled):
        mi.append(D[48:])
    assert plan.fired[f"kill:{seam}"] == 1
    with telemetry.CommLog() as log:
        resumed = MutableAPSSIndex(corpus=None, threshold=T, k=K, directory=d)
    assert log.counters["mutable.replayed_ops"] == 1
    assert _mutable_graph_equal(resumed, ref)
    # and the resumed index keeps working: next op takes the next WAL seq
    resumed.delete([0])
    ref.delete([0])
    assert _mutable_graph_equal(resumed, ref)


def test_mutable_corrupt_log_walks_back_one_op(tmp_path):
    """Bit-rot in the newest WAL entry: reopening detects the digest
    mismatch, warns, walks back exactly that op, and stays serviceable."""
    from repro.serving import MutableAPSSIndex

    rng = np.random.default_rng(21)
    D = rng.normal(size=(64, 16)).astype(np.float32)
    d = str(tmp_path / "rot")
    # kill before the snapshot so op 2 exists ONLY in the log...
    plan = FaultPlan([Fault("kill", scope="mutable.commit", step=2)])
    mi = MutableAPSSIndex(
        D[:48], threshold=T, k=K, directory=d, fault_plan=plan
    )
    with pytest.raises(SweepKilled):
        mi.append(D[48:])
    # ...then rot one byte of its payload on disk
    step_dir = os.path.join(d, "log", "step_%010d" % 2)
    leaf = [f for f in os.listdir(step_dir) if f.endswith(".npy")][0]
    FaultPlan(seed=1).corrupt_file(os.path.join(step_dir, leaf))
    with telemetry.CommLog() as log:
        with pytest.warns(UserWarning, match="walking back"):
            walked = MutableAPSSIndex(
                corpus=None, threshold=T, k=K, directory=d
            )
    assert log.counters["mutable.log_walkback"] == 1
    assert "mutable.replayed_ops" not in log.counters
    # state equals the pre-op oracle — the corrupt append never happened
    assert _mutable_graph_equal(
        walked, MutableAPSSIndex(D[:48], threshold=T, k=K)
    )
    # the walked-back seq is reusable: redoing the append works and
    # matches the uninterrupted end state
    walked.append(D[48:])
    assert _mutable_graph_equal(
        walked, MutableAPSSIndex(D, threshold=T, k=K)
    )


def test_mutable_snapshot_fallback_counts(tmp_path):
    """Corrupting the newest SNAPSHOT (not the log) falls back one kept
    snapshot and replays the op gap from the WAL."""
    from repro.serving import MutableAPSSIndex

    rng = np.random.default_rng(22)
    D = rng.normal(size=(64, 16)).astype(np.float32)
    d = str(tmp_path / "snaprot")
    mi = MutableAPSSIndex(D[:48], threshold=T, k=K, directory=d)
    mi.append(D[48:])
    state_dir = os.path.join(d, "state")
    step_dir = os.path.join(state_dir, "step_%010d" % 2)
    leaf = [f for f in os.listdir(step_dir) if f.endswith(".npy")][0]
    FaultPlan(seed=2).corrupt_file(os.path.join(step_dir, leaf))
    with telemetry.CommLog() as log:
        with pytest.warns(UserWarning, match="falling back"):
            resumed = MutableAPSSIndex(
                corpus=None, threshold=T, k=K, directory=d
            )
    assert log.counters["mutable.restore_fallback"] == 1
    assert log.counters["mutable.replayed_ops"] == 1  # op 2 redone from WAL
    assert _mutable_graph_equal(resumed, mi)
