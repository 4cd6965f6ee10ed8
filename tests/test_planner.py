"""Execution planner: cost-model-driven auto-selection (ISSUE 4 acceptance).

The load-bearing assertions: ``variant="auto"`` picks the sparse path on a
low-density corpus and a dense configuration on a dense one, and whatever
the planner picks executes exactly (every variant is exact, so planning can
never change results — only cost).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.apss import apss_reference, normalize_rows, similarity_topk
from repro.core.graph import match_set
from repro.core.sparse import to_dense
from repro.data.sparse import sparse_zipfian_corpus
from repro.planner import (
    VariantConfig,
    default_profile,
    estimate_cost,
    plan_apss,
)
from repro.planner import calibrate as calibrate_mod
from repro.planner.plan import candidate_configs, execute, summarize_corpus

T, K = 0.5, 16


@pytest.fixture(scope="module")
def lowdens():
    """Paper-regime corpus: density ≈ 0.6%."""
    return sparse_zipfian_corpus(512, 2048, 12.0, seed=0)


@pytest.fixture(scope="module")
def middens():
    """20% density: sparse representation offered, but gather-dot loses."""
    rng = np.random.default_rng(1)
    D = np.abs(rng.standard_normal((256, 128))).astype(np.float32)
    D *= rng.random((256, 128)) < 0.2
    return np.asarray(normalize_rows(jnp.asarray(D)))


def _check_exact(got, ref):
    assert match_set(got) == match_set(ref)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(ref.counts))


# -- the acceptance assertions ------------------------------------------------


def test_auto_picks_sparse_on_low_density(lowdens):
    plan = plan_apss(lowdens, T, K, profile=default_profile(), include_kernel=False)
    assert plan.config.sparse, plan.describe()
    # and the model's reasoning is visible: sparse flops ≪ dense flops
    sparse_best = next(e for e in plan.estimates if e.config.sparse)
    dense_best = next(e for e in plan.estimates if not e.config.sparse)
    assert sparse_best.flops < dense_best.flops


def test_auto_picks_dense_on_dense(middens):
    plan = plan_apss(middens, T, K, profile=default_profile(), include_kernel=False)
    # both representations were candidates; the dense one won on cost
    assert any(e.config.sparse for e in plan.estimates)
    assert not plan.config.sparse, plan.describe()


def test_auto_picks_dense_schedule_on_dense_mesh(middens, mesh8):
    plan = plan_apss(
        middens, T, K, mesh8, profile=default_profile(), include_kernel=False
    )
    assert not plan.config.sparse, plan.describe()
    got = plan.run()
    _check_exact(got, apss_reference(jnp.asarray(middens), T, K))


def test_variant_auto_dispatch_exact_sparse(lowdens):
    got = similarity_topk(lowdens, lowdens, T, K, exclude_self=True, variant="auto")
    ref = apss_reference(to_dense(lowdens), T, K)
    _check_exact(got, ref)


def test_variant_auto_dispatch_exact_dense(middens):
    D = jnp.asarray(middens)
    got = similarity_topk(D, D, T, K, exclude_self=True, variant="auto")
    _check_exact(got, apss_reference(D, T, K))


def test_variant_auto_guards():
    rng = np.random.default_rng(2)
    D = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    Q = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    with pytest.raises(ValueError, match="self-join"):
        similarity_topk(Q, D, T, K, exclude_self=True, variant="auto")
    with pytest.raises(ValueError, match="exclude_self"):
        similarity_topk(D, D, T, K, variant="auto")
    with pytest.raises(ValueError, match="variant"):
        similarity_topk(D, D, T, K, variant="ring")


def test_apss_distribution_auto(corpus, mesh8):
    from repro.core.distributed import apss

    D = jnp.asarray(corpus)
    got = apss(
        D, 0.35, K, mesh8, distribution="auto",
        profile=default_profile(), include_kernel=False,
    )
    _check_exact(got, apss_reference(D, 0.35, K))


# -- corpus summary -----------------------------------------------------------


def test_summarize_sparse_never_densifies(lowdens):
    s = summarize_corpus(lowdens, T)
    assert s.sparse_input
    assert s.n == 512 and s.m == 2048
    assert 0.004 < s.density < 0.01
    assert s.cap == lowdens.cap
    assert s.zipf_alpha > 0.4          # the generator's skew is visible
    assert 0.0 < s.live_fraction <= 1.0
    assert len(s.tile_counts) >= 1


def test_summarize_dense(middens):
    s = summarize_corpus(middens, T)
    assert not s.sparse_input
    assert s.density == pytest.approx(0.2, abs=0.05)
    assert s.cap <= 128
    assert s.imbalance(4) >= 1.0


def test_summarize_index_uses_exact_stats(lowdens):
    from repro.serving import build_index

    index = build_index(lowdens, block_rows=64, normalize=False)
    s = summarize_corpus(index, T)
    assert s.sparse_input and s.n == 512
    assert 0.0 <= s.live_fraction <= 1.0


# -- candidate enumeration / cost model ---------------------------------------


def test_candidates_respect_constraints(lowdens, mesh8):
    s = summarize_corpus(lowdens, T)
    cfgs = candidate_configs(s, mesh8, K, include_kernel=False)
    kinds = {c.kind for c in cfgs}
    assert {"blocked", "horizontal", "vertical"} <= kinds
    for c in cfgs:
        if c.kind == "vertical":
            assert s.n % c.block_rows == 0
            if c.accumulation == "scatter":
                assert c.block_rows % 8 == 0
        assert not c.use_kernel  # include_kernel=False


def test_every_candidate_priced_finite(lowdens, mesh8):
    s = summarize_corpus(lowdens, T)
    prof = default_profile()
    for c in candidate_configs(s, mesh8, K, include_kernel=False):
        e = estimate_cost(c, s, dict(mesh8.shape), prof, K)
        assert np.isfinite(e.total_s) and e.total_s > 0, c.name
        assert e.wire_bytes >= 0 and e.flops > 0


def test_blocked_priced_single_device_under_mesh(lowdens, mesh8):
    """A blocked config runs all rows on one device regardless of the mesh:
    its estimate must not shrink by p (which would bias the planner against
    every distributed variant)."""
    s = summarize_corpus(lowdens, T)
    prof = default_profile()
    cfg = VariantConfig("blocked", True, 128)
    with_mesh = estimate_cost(cfg, s, dict(mesh8.shape), prof, K)
    without = estimate_cost(cfg, s, None, prof, K)
    assert with_mesh.flops == without.flops
    assert with_mesh.total_s == without.total_s


def test_vertical_requires_m_divisible(mesh8):
    """Dense vertical shards columns P(None, axis): m % p != 0 must be
    filtered at enumeration, not crash at dispatch."""
    rng = np.random.default_rng(5)
    raw = np.abs(rng.standard_normal((128, 100))).astype(np.float32)
    D = np.asarray(normalize_rows(jnp.asarray(raw)))
    s = summarize_corpus(D, T)
    cfgs = candidate_configs(s, mesh8, K, include_kernel=False)
    assert cfgs and not any(c.kind == "vertical" for c in cfgs)


def test_plan_on_index_returns_valid_rows_only():
    """Indexes pad rows to the block multiple (and lane-pad dense feature
    axes); planning from an index must run on the VALID corpus, not the
    padded one."""
    from repro.data.sparse import sparse_zipfian_corpus as szc
    from repro.serving import build_index

    sp = szc(200, 512, 8.0, seed=6)
    index = build_index(sp, block_rows=64, normalize=False)  # pads 200 → 256
    assert index.n_padded == 256
    plan = plan_apss(index, T, K, profile=default_profile(), include_kernel=False)
    got = plan.run()
    assert got.counts.shape[0] == 200
    _check_exact(got, apss_reference(to_dense(sp), T, K))


def test_costmodel_wire_matches_telemetry(corpus, mesh8):
    """The model's wire prediction IS the telemetry formula: predicted bytes
    for the ring equal the instrumented record of an actual run."""
    from repro.core.distributed import apss_horizontal
    from repro.planner import CommLog

    D = jnp.asarray(corpus)
    s = summarize_corpus(D, 0.35)
    cfg = VariantConfig("horizontal", False, 128, schedule="ring")
    est = estimate_cost(cfg, s, dict(mesh8.shape), default_profile(), K)
    with CommLog() as log:
        apss_horizontal(D, 0.35, K, mesh8, schedule="ring", block_rows=16)
    assert est.wire_bytes == log.last.wire_bytes
    assert est.hop_count == log.last.hop_count


def test_profile_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CALIB_DIR", str(tmp_path))
    calibrate_mod._MEMO.clear()
    prof = calibrate_mod.calibrate(n=64, m=128, cap=8, iters=1, save=True)
    assert prof.matmul_gflops > 0 and prof.gather_gflops > 0
    assert calibrate_mod.profile_path().exists()
    calibrate_mod._MEMO.clear()
    loaded = calibrate_mod.get_profile()
    assert loaded.matmul_gflops == pytest.approx(prof.matmul_gflops)
    assert loaded.device_kind == prof.device_kind
    calibrate_mod._MEMO.clear()
    monkeypatch.delenv("REPRO_CALIB_DIR")


def test_get_profile_defaults_without_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_CALIB_DIR", str(tmp_path / "empty"))
    calibrate_mod._MEMO.clear()
    prof = calibrate_mod.get_profile()
    ref = default_profile()
    assert prof.matmul_gflops == ref.matmul_gflops  # deterministic defaults
    calibrate_mod._MEMO.clear()
    monkeypatch.delenv("REPRO_CALIB_DIR")


# -- plan execution & integration ---------------------------------------------


def test_execute_representation_conversion(middens):
    """A sparse config on a dense input converts host-side and stays exact."""
    cfg = VariantConfig("blocked", True, 64)
    got = execute(cfg, middens, T, K)
    _check_exact(got, apss_reference(jnp.asarray(middens), T, K))


def test_autotune_promotes_measured_winner():
    sp = sparse_zipfian_corpus(256, 1024, 8.0, seed=3)
    plan = plan_apss(
        sp, T, K, profile=default_profile(), include_kernel=False,
        autotune=True,
    )
    assert plan.autotuned
    measured = [e for e in plan.estimates if e.measured_s is not None]
    assert len(measured) >= 2
    assert plan.estimates[0].measured_s == min(e.measured_s for e in measured)
    _check_exact(plan.run(), apss_reference(to_dense(sp), T, K))


def test_build_index_with_plan(lowdens):
    from repro.serving import build_index, query_topk

    plan = plan_apss(lowdens, T, K, profile=default_profile(), include_kernel=False)
    index = build_index(lowdens, normalize=False, plan=plan)
    assert index.block_rows == plan.config.block_rows
    assert index.is_sparse == plan.config.sparse
    # plan on a dense view converts the corpus to the planned representation
    D = to_dense(lowdens)
    index2 = build_index(D, normalize=False, plan=plan)
    assert index2.is_sparse == plan.config.sparse
    Q = np.asarray(D[:4])
    got = query_topk(index2, jnp.asarray(Q), T, K)
    ref = query_topk(index, jnp.asarray(Q), T, K)
    assert match_set(got) == match_set(ref)


def test_plan_describe_and_dict(lowdens):
    plan = plan_apss(lowdens, T, K, profile=default_profile(), include_kernel=False)
    text = plan.describe()
    assert plan.config.name in text
    assert "density" in text
    d = plan.as_dict()
    assert d["chosen"] == plan.config.name
    assert d["estimates"] and {"config", "predicted_s", "wire_bytes"} <= d[
        "estimates"
    ][0].keys()
    assert d["summary"]["n"] == 512
