"""Substrate tests: checkpoint/fault-tolerance, elastic re-mesh, synthetic
corpora and their statistics, dedup, straggler ledger."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.core.pruning import block_prune_mask
from repro.data import (
    PAPER_DATASETS,
    corpus_stats,
    dedup_corpus,
    paper_like_corpus,
    synthetic_corpus,
)
from repro.data.synthetic import clustered_corpus
from repro.distributed import StepTimer
from repro.distributed.elastic import make_elastic_mesh, reshard_tree


# -- checkpoint / fault tolerance ----------------------------------------------


def test_checkpoint_roundtrip_all_dtypes():
    state = {
        "bf16": jnp.ones((3, 4), jnp.bfloat16) * 1.5,
        "f32": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "i32": jnp.asarray([1, 2, 3], jnp.int32),
        "nested": {"scalar": jnp.asarray(7, jnp.int32)},
    }
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(state, d, 5)
        back = load_checkpoint(d, 5, like=state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
            assert str(a.dtype) == str(b.dtype)
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)
            )


def test_checkpoint_manager_keep_k_and_resume():
    state = {"x": jnp.ones((2,))}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save({"x": state["x"] * s}, s)
        assert mgr.all_steps() == [3, 4]
        restored, step = mgr.restore(like=state)
        assert step == 4
        np.testing.assert_allclose(restored["x"], 4.0)


def test_checkpoint_atomicity_no_tmp_left():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=3)
        mgr.save({"x": jnp.zeros(3)}, 1, blocking=False)
        mgr.wait()
        assert not any(p.endswith(".tmp") for p in os.listdir(d))
        assert mgr.latest_step() == 1


# -- elastic ---------------------------------------------------------------------


def test_elastic_reshard_to_smaller_mesh():
    state = {"w": np.arange(32, dtype=np.float32).reshape(8, 4)}
    specs = {"w": P("data", "model")}
    big = make_elastic_mesh(8, model_parallel=2)
    small = make_elastic_mesh(4, model_parallel=2)
    on_big = reshard_tree(state, specs, big)
    on_small = reshard_tree(state, specs, small)
    np.testing.assert_array_equal(np.asarray(on_big["w"]), state["w"])
    np.testing.assert_array_equal(np.asarray(on_small["w"]), state["w"])
    assert len(on_small["w"].sharding.device_set) == 4


def test_elastic_mesh_keeps_model_parallel():
    m = make_elastic_mesh(7, model_parallel=2)
    assert m.shape["model"] == 2 and m.shape["data"] == 3


# -- data ------------------------------------------------------------------------


def test_corpus_stats_match_construction():
    D = synthetic_corpus(100, 400, 12.0, seed=1)
    st = corpus_stats(D)
    assert st.n == 100 and st.m == 400
    assert 6 <= st.avg_vector_size <= 20
    norms = np.linalg.norm(D, axis=1)
    np.testing.assert_allclose(norms[norms > 0], 1.0, rtol=1e-5)


def test_dedup_finds_planted_duplicates():
    D = synthetic_corpus(64, 256, 10.0, seed=2)
    dup = np.concatenate([D, D[:8]])
    keep, dup_of = dedup_corpus(dup, threshold=0.999)
    assert keep[:64].all()
    assert not keep[64:].any()
    np.testing.assert_array_equal(dup_of[64:], np.arange(8))


@pytest.mark.parametrize("name", sorted(PAPER_DATASETS))
def test_paper_like_corpus_scales_a_table_1_dataset(name):
    spec = PAPER_DATASETS[name]
    scale = 0.002
    D, t = paper_like_corpus(name, scale=scale, seed=5)
    assert t == spec["t"]
    assert D.shape == (
        max(64, int(spec["n"] * scale)), max(128, int(spec["m"] * scale))
    )
    assert D.dtype == np.float32 and (D >= 0).all()
    np.testing.assert_allclose(np.linalg.norm(D, axis=1), 1.0, rtol=1e-5)
    D2, _ = paper_like_corpus(name, scale=scale, seed=5)
    np.testing.assert_array_equal(D, D2)  # a seed gives one corpus


@pytest.mark.parametrize("n_clusters", [2, 4, 8])
def test_clustered_corpus_bands_share_no_dimension(n_clusters):
    n, m = 64, 128
    D = clustered_corpus(n, m, 5.0, n_clusters=n_clusters, seed=1)
    np.testing.assert_allclose(np.linalg.norm(D, axis=1), 1.0, rtol=1e-5)
    rows_per, band = n // n_clusters, m // n_clusters
    cluster = np.arange(n) // rows_per
    # each row lives in its cluster's band of dimensions ...
    for i in range(n):
        dims = np.flatnonzero(D[i])
        assert (dims // band == cluster[i]).all(), i
    # ... so rows of different clusters never meet, and block bounds at
    # the cluster size prove every off-diagonal tile dead for any t > 0
    S = D @ D.T
    assert (S[cluster[:, None] != cluster[None, :]] == 0).all()
    Dj = jnp.asarray(D)
    live = np.asarray(block_prune_mask(Dj, Dj, 1e-3, rows_per))
    np.testing.assert_array_equal(live, np.eye(n_clusters, dtype=bool))


# -- straggler --------------------------------------------------------------------


def test_straggler_detection():
    t = StepTimer(tolerance=1.5)
    for r in range(8):
        for _ in range(5):
            t.record(r, 0.1 if r != 5 else 0.25)
    rep = t.report()
    assert rep.evict == [5]
