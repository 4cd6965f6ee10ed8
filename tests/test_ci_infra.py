"""CI infrastructure as code (ISSUE 5 satellites): the BENCH schema gate
(``benchmarks/check_schema.py``, formerly an inline workflow heredoc) and
the tier-1 shard partition (``tests/conftest.py``) are real, unit-tested
modules — a schema or sharding bug fails tier-1 locally, not just a CI run
three pushes later.
"""

import json
import pathlib

import pytest

import conftest
from benchmarks import check_schema
from benchmarks.check_schema import SchemaError, check


# -- a minimal valid BENCH artifact ------------------------------------------


def _entry(config="blocked[sparse,b=128]", us=10.0):
    return {
        "config": config, "predicted_s": 0.01, "measured_us": us,
        "wire_bytes": 0, "flops": 1.0, "compute_s": 0.01, "comm_s": 0.0,
    }


def _corpus_rec(entries, within=True):
    return {
        "summary": {"density": 0.005},
        "chosen": entries[0]["config"],
        "chosen_predicted": entries[0]["config"],
        "entries": entries,
        "best_measured": entries[0]["config"],
        "chosen_over_best": 1.0 if within else 3.0,
        "chosen_within_2x": within,
    }


def _valid_doc():
    return {
        "density": 0.01, "live_tile_fraction": 0.5, "variants": {},
        "sparse_sweep": {"entries": [{
            "density": 0.001, "live_tile_fraction_sparse": 0.1,
            "live_tile_fraction_dense": 0.2, "total_matches": 5,
            "variants": {"dense-fused": 1.0, "sparse-xla": 2.0},
        }]},
        "serving": {
            "index_build_us": 1.0, "index_bytes": 10, "rebuild": {},
            "amortized_speedup_batch64": 3.0,
            "batches": {
                b: {"us_per_call": 1, "us_per_query": 1, "qps": 1,
                    "total_matches": 1,
                    "latency_us": {"p50": 900.0, "p95": 1200.0,
                                   "p99": 1500.0, "samples": 20}}
                for b in ("1", "8", "64")
            },
            "servers": {
                regime: {
                    "step": {"qps": 5000.0, "p50_us": 3000.0,
                             "p95_us": 9000.0, "p99_us": 20000.0,
                             "requests": 192},
                    "continuous": {"qps": 6000.0, "p50_us": 2000.0,
                                   "p95_us": 6000.0, "p99_us": 9000.0,
                                   "requests": 192},
                }
                for regime in ("8", "64")
            },
            "early_exit": {
                "n": 2048, "m": 1024, "threshold": 0.01, "k": 8,
                "skipped_tiles": 28, "bit_exact": True,
            },
            "qps_batch64": 6000.0,
            "p99_us": 9000.0,
        },
        "planner": {
            "profile": {"matmul_gflops": 1, "gather_gflops": 1,
                        "score_cost_ns": 1, "device_kind": "cpu"},
            "corpora": {
                "sparse_lowdens": _corpus_rec([_entry()]),
                "dense": _corpus_rec([_entry("blocked[dense,b=128]")]),
            },
            "mesh2d": {
                "mesh": {"data": 4, "model": 2},
                "corpora": {"sparse_lowdens": _corpus_rec([
                    _entry("2d/compressed[sparse,b=128]"),
                    _entry("2d/allreduce[dense,b=128]"),
                ])},
            },
        },
        "mutable": {
            "n": 1024, "m": 128, "threshold": 0.2, "k": 16, "block": 64,
            "deltas": [
                {"delta": 16, "delta_fraction": 1 / 64, "append_s": 0.01,
                 "rebuild_s": 0.08, "speedup": 8.0},
                {"delta": 256, "delta_fraction": 1 / 4, "append_s": 0.03,
                 "rebuild_s": 0.06, "speedup": 2.0},
            ],
        },
        "provenance": {
            "git_sha": "deadbeef" * 5, "timestamp": "2026-08-09T00:00:00Z",
            "device_kind": "cpu", "device_count": 8, "jax_version": "0.4.37",
        },
    }


def _audit_lane(gated_ok=True):
    def _e(family, ratio=1.0):
        return {
            "family": family, "predicted_flops": 1e6,
            "hlo_flops": ratio * 1e6, "flop_ratio": ratio,
            "predicted_link_bytes": 0.0, "hlo_link_bytes": 0.0,
            "predicted_hbm_bytes": 1e4, "hlo_hbm_bytes": 2e4,
            "compile": {"t_compile_s": 0.1, "total_bytes": 4096},
        }

    return {
        "gated_ok": gated_ok,
        "gated_families": ["blocked[dense]", "horizontal/ring[dense]"],
        "entries": [_e("blocked[dense]"), _e("horizontal/ring[dense]")],
    }


def test_valid_doc_passes():
    check(_valid_doc())


@pytest.mark.parametrize("path", [
    ("sparse_sweep",),
    ("serving", "batches", "64"),
    ("serving", "batches", "8", "latency_us"),
    ("serving", "batches", "1", "latency_us", "p99"),
    ("planner", "profile", "gather_gflops"),
    ("planner", "mesh2d"),
    ("planner", "corpora", "sparse_lowdens", "entries", 0, "measured_us"),
    ("mutable",),
    ("mutable", "deltas", 0, "speedup"),
    ("provenance",),
    ("provenance", "git_sha"),
    ("provenance", "jax_version"),
])
def test_missing_key_fails_with_path(path):
    doc = _valid_doc()
    node = doc
    for k in path[:-1]:
        node = node[k]
    del node[path[-1]]
    with pytest.raises(SchemaError):
        check(doc)


def test_serving_latency_histogram_lane():
    """The serving lane must carry a per-call latency distribution with
    ordered quantiles — a mean alone can't regress on tail latency."""
    doc = _valid_doc()
    doc["serving"]["batches"]["64"]["latency_us"]["p50"] = 2000.0  # > p99
    with pytest.raises(SchemaError, match=r"p50 .* exceeds p99"):
        check(doc)
    doc = _valid_doc()
    doc["serving"]["batches"]["8"]["latency_us"]["p50"] = 0.0
    with pytest.raises(SchemaError, match="p50 must be positive"):
        check(doc)


def test_serving_server_curve_lane():
    """The QPS/p99 curve (ISSUE 10): both regimes × both servers present,
    percentiles ordered, and continuous ≤ step on p99 at the largest
    regime — the tentpole's headline claim, gated."""
    doc = _valid_doc()
    del doc["serving"]["servers"]["64"]["continuous"]
    with pytest.raises(SchemaError, match="continuous"):
        check(doc)
    doc = _valid_doc()
    srv = doc["serving"]["servers"]["8"]["step"]
    srv["p95_us"] = srv["p99_us"] + 1.0  # unordered
    with pytest.raises(SchemaError, match="unordered"):
        check(doc)
    doc = _valid_doc()
    doc["serving"]["servers"]["64"]["continuous"]["p99_us"] = 1e9
    with pytest.raises(SchemaError, match="exceeds"):
        check(doc)
    # at the SMALL regime step may legitimately win — not gated
    doc = _valid_doc()
    doc["serving"]["servers"]["8"]["continuous"]["p99_us"] = 1e9
    check(doc)


def test_serving_early_exit_lane():
    """Early exit must skip live tiles AND stay bit-exact."""
    doc = _valid_doc()
    doc["serving"]["early_exit"]["skipped_tiles"] = 0
    with pytest.raises(SchemaError, match="skipped no live tiles"):
        check(doc)
    doc = _valid_doc()
    doc["serving"]["early_exit"]["bit_exact"] = False
    with pytest.raises(SchemaError, match="diverged"):
        check(doc)
    doc = _valid_doc()
    del doc["serving"]["early_exit"]["skipped_tiles"]
    with pytest.raises(SchemaError, match="early_exit"):
        check(doc)


def test_within_2x_gate_applies_to_single_device_lanes_only():
    """The corpora lanes hard-gate chosen_within_2x; the mesh2d lane records
    it but doesn't gate (8 virtual devices share one socket — collective
    timings there are pathological by construction)."""
    doc = _valid_doc()
    doc["planner"]["mesh2d"]["corpora"]["sparse_lowdens"] = _corpus_rec(
        [_entry("2d/compressed[sparse,b=128]"),
         _entry("2d/allreduce[dense,b=128]")],
        within=False,
    )
    check(doc)  # mesh2d miss: recorded, not fatal
    doc = _valid_doc()
    doc["planner"]["corpora"]["dense"] = _corpus_rec(
        [_entry("blocked[dense,b=128]")], within=False
    )
    with pytest.raises(SchemaError, match=r"chosen plan"):
        check(doc)


def test_mesh2d_requires_both_2d_representations():
    """The mesh lane must measure the 2-D family in BOTH representations —
    a missing sparse entry means the planner gate regressed."""
    for drop in ("sparse", "dense"):
        doc = _valid_doc()
        rec = doc["planner"]["mesh2d"]["corpora"]["sparse_lowdens"]
        rec["entries"] = [e for e in rec["entries"] if drop not in e["config"]]
        with pytest.raises(SchemaError, match=f"2d-{drop}"):
            check(doc)


def test_sparse_regime_gate():
    doc = _valid_doc()
    doc["planner"]["corpora"]["sparse_lowdens"]["summary"]["density"] = 0.2
    with pytest.raises(SchemaError, match="sparse regime"):
        check(doc)


def test_mutable_lane_gates_small_delta_speedup():
    """The live-corpus acceptance bar (ISSUE 7): some delta <= n/16 must
    show append+delta-join >= 5x faster than a full rebuild."""
    doc = _valid_doc()
    doc["mutable"]["deltas"][0]["speedup"] = 3.0
    with pytest.raises(SchemaError, match=">= 5x"):
        check(doc)
    # a big-delta lane alone can't satisfy the gate either
    doc = _valid_doc()
    doc["mutable"]["deltas"] = [doc["mutable"]["deltas"][1]]
    with pytest.raises(SchemaError, match="no delta <= n/16"):
        check(doc)
    # the n/4 lane is informational: its speedup is not gated
    doc = _valid_doc()
    doc["mutable"]["deltas"][1]["speedup"] = 0.9
    check(doc)


def test_audit_lane_is_optional_but_checked_when_present():
    doc = _valid_doc()
    check(doc)  # no audit lane: fine
    doc["audit"] = _audit_lane()
    check(doc)
    doc["audit"] = _audit_lane(gated_ok=False)
    with pytest.raises(SchemaError, match="FLOP ratio gate"):
        check(doc)
    doc["audit"] = _audit_lane()
    doc["audit"]["entries"] = doc["audit"]["entries"][:1]  # ring missing
    with pytest.raises(SchemaError, match="gated families missing"):
        check(doc)
    doc["audit"] = _audit_lane()
    del doc["audit"]["entries"][0]["hlo_flops"]
    with pytest.raises(SchemaError, match=r"audit\.entries\[0\]"):
        check(doc)


def test_history_record_schema():
    from benchmarks.check_schema import check_history_record

    rec = {
        "git_sha": "abc123", "timestamp": "2026-08-09T00:00:00Z",
        "device_kind": "cpu", "jax_version": "0.4.37",
        "metrics": {"variants.fused.us_per_call": 10.0},
    }
    check_history_record(rec)
    with pytest.raises(SchemaError, match="empty metric"):
        check_history_record({**rec, "metrics": {}})
    with pytest.raises(SchemaError, match="non-negative"):
        check_history_record({**rec, "metrics": {"x": -1.0}})
    with pytest.raises(SchemaError, match="missing keys"):
        check_history_record({k: v for k, v in rec.items() if k != "git_sha"})


# -- perf-regression sentinel -------------------------------------------------


def _bench_doc(scale=1.0, sha="sha0", qps=5000.0):
    """A minimal artifact with the lanes the sentinel extracts."""
    return {
        "variants": {
            "fused": {"us_per_call": 100.0 * scale},
            "fused-compacted": {"us_per_call": 40.0 * scale},
        },
        "sparse_sweep": {"entries": [{
            "density_requested": 0.01,
            "variants": {"sparse-xla": {"us_per_call": 50.0 * scale}},
        }]},
        "serving": {
            "index_build_us": 500.0 * scale,
            "batches": {"8": {"us_per_query": 20.0 * scale}},
            "qps_batch64": qps,
            "p99_us": 9000.0 * scale,
        },
        "mutable": {"deltas": [{"delta": 16, "append_s": 0.01 * scale}]},
        "provenance": {
            "git_sha": sha, "timestamp": "t", "device_kind": "cpu",
            "jax_version": "0.4.37",
        },
    }


def test_sentinel_extracts_stable_metrics():
    from benchmarks.check_schema import check_history_record
    from benchmarks.sentinel import extract_metrics, record

    m = extract_metrics(_bench_doc())
    assert m["variants.fused.us_per_call"] == 100.0
    assert m["sparse_sweep.d=0.01.sparse-xla.us_per_call"] == 50.0
    assert m["serving.batch=8.us_per_query"] == 20.0
    assert m["serving.qps_batch64"] == 5000.0
    assert m["serving.p99_us"] == 9000.0
    assert m["mutable.delta=16.append_s"] == 0.01
    # the record the sentinel appends satisfies the history schema
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        rec = record(_bench_doc(), f"{d}/h.jsonl")
    check_history_record(rec)


def test_sentinel_passes_without_baseline_and_flags_2x_slowdown(tmp_path):
    """The acceptance scenario: seed a history, re-check unchanged (PASS),
    then check a synthetic 2x slowdown (FAIL naming the metrics)."""
    from benchmarks import sentinel

    hist = str(tmp_path / "BENCH_history.jsonl")
    # no history at all: check passes (nothing to regress from)
    assert sentinel.check(_bench_doc(), hist)["ok"]
    for i in range(3):  # seed three baseline runs
        sentinel.record(_bench_doc(sha=f"base{i}"), hist)
    ok = sentinel.check(_bench_doc(scale=1.1, sha="pr"), hist)
    assert ok["ok"] and ok["checked"] >= 5  # 10% drift: inside tolerance
    bad = sentinel.check(_bench_doc(scale=2.0, sha="pr"), hist)
    assert not bad["ok"]
    flagged = {r["metric"] for r in bad["regressions"]}
    assert "variants.fused.us_per_call" in flagged
    assert all(r["ratio"] == pytest.approx(2.0) for r in bad["regressions"])


def test_sentinel_qps_is_gated_higher_is_better(tmp_path):
    """``serving.qps_batch64`` inverts: a throughput DROP below
    baseline/tolerance is the regression; latency drift on the same run
    still gates the usual way."""
    from benchmarks import sentinel

    assert "serving.qps_batch64" in sentinel.HIGHER_IS_BETTER
    hist = str(tmp_path / "h.jsonl")
    for i in range(3):
        sentinel.record(_bench_doc(sha=f"base{i}"), hist)
    # QPS doubled: an improvement, not a regression
    assert sentinel.check(_bench_doc(sha="pr", qps=10000.0), hist)["ok"]
    # QPS halved: flagged, with the inverted ratio
    bad = sentinel.check(_bench_doc(sha="pr", qps=2500.0), hist)
    assert not bad["ok"]
    flagged = {r["metric"]: r for r in bad["regressions"]}
    assert set(flagged) == {"serving.qps_batch64"}
    assert flagged["serving.qps_batch64"]["ratio"] == pytest.approx(2.0)


def test_sentinel_rerecord_same_sha_replaces_not_duplicates(tmp_path):
    from benchmarks import sentinel

    hist = str(tmp_path / "h.jsonl")
    sentinel.record(_bench_doc(sha="a"), hist)
    sentinel.record(_bench_doc(scale=3.0, sha="a"), hist)  # supersedes
    records = sentinel.load_history(hist)
    assert len(records) == 1
    assert records[0]["metrics"]["variants.fused.us_per_call"] == 300.0


def test_sentinel_baseline_excludes_own_sha_and_other_devices(tmp_path):
    from benchmarks import sentinel

    hist = str(tmp_path / "h.jsonl")
    sentinel.record(_bench_doc(sha="mine"), hist)  # own prior run
    other = _bench_doc(scale=0.1, sha="gpu-run")
    other["provenance"]["device_kind"] = "gpu"
    sentinel.record(other, hist)
    # only baselines: own sha (excluded) + gpu (excluded) → no baseline
    res = sentinel.check(_bench_doc(scale=5.0, sha="mine"), hist)
    assert res["ok"] and res["baseline_records"] == 0


def test_sentinel_cli(tmp_path, capsys):
    from benchmarks import sentinel

    art = tmp_path / "bench.json"
    hist = str(tmp_path / "h.jsonl")
    art.write_text(json.dumps(_bench_doc(sha="base")))
    assert sentinel.main(["record", "--artifact", str(art),
                          "--history", hist]) == 0
    slow = tmp_path / "slow.json"
    slow.write_text(json.dumps(_bench_doc(scale=2.0, sha="pr")))
    assert sentinel.main(["check", "--artifact", str(slow),
                          "--history", hist]) == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "variants.fused.us_per_call" in err
    # unchanged re-run passes
    same = tmp_path / "same.json"
    same.write_text(json.dumps(_bench_doc(sha="pr2")))
    assert sentinel.main(["check", "--artifact", str(same),
                          "--history", hist]) == 0


def test_cli_roundtrip(tmp_path, capsys):
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_valid_doc()))
    assert check_schema.main([str(good)]) == 0
    bad_doc = _valid_doc()
    del bad_doc["serving"]
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(bad_doc))
    assert check_schema.main([str(bad)]) == 1
    err = capsys.readouterr().err
    assert "$.serving" in err or "serving" in err


def test_repo_bench_artifact_is_valid():
    """The committed BENCH_apss.json must satisfy the same gate CI applies
    to the smoke artifact — schema changes ship with a regenerated
    artifact, never ahead of it."""
    path = pathlib.Path(__file__).resolve().parent.parent / "BENCH_apss.json"
    check(json.loads(path.read_text()))


def test_error_messages_are_path_qualified():
    doc = _valid_doc()
    del doc["planner"]["mesh2d"]["corpora"]["sparse_lowdens"]["entries"][1][
        "wire_bytes"
    ]
    with pytest.raises(SchemaError, match=r"mesh2d\.corpora\.sparse_lowdens"):
        check(doc)


# -- tier-1 sharding ----------------------------------------------------------


def test_shard_assignment_is_a_partition():
    """Every test file lands in exactly one shard, for any shard count."""
    files = [f"tests/test_{name}.py" for name in (
        "apss_core", "apss_distributed", "sparse", "sparse_2d", "planner",
        "telemetry", "serving", "kernels", "ci_infra",
    )]
    for num in (2, 3, 5):
        buckets = [[] for _ in range(num)]
        for f in files:
            buckets[conftest.shard_of(f, num)].append(f)
        assert sorted(sum(buckets, [])) == sorted(files)  # exhaustive
        assert all(
            conftest.shard_of(f, num) == conftest.shard_of(f, num)
            for f in files
        )  # deterministic


def test_shard_of_is_stable():
    """Pinned values: the assignment must never drift across Python or
    pytest versions (a silent re-partition would un-run half the suite
    until every matrix cell is green again)."""
    import zlib

    f = "tests/test_sparse_2d.py"
    assert conftest.shard_of(f, 2) == zlib.crc32(f.encode()) % 2


def test_modifyitems_respects_env(monkeypatch):
    class Item:
        def __init__(self, nodeid):
            self.nodeid = nodeid

    class Hook:
        def __init__(self):
            self.deselected = []

        def pytest_deselected(self, items):
            self.deselected.extend(items)

    class Config:
        def __init__(self):
            self.hook = Hook()

    all_items = [Item(f"tests/test_{i}.py::test_x") for i in range(10)]
    # no env → untouched
    monkeypatch.delenv("PYTEST_NUM_SHARDS", raising=False)
    items = list(all_items)
    conftest.pytest_collection_modifyitems(Config(), items)
    assert items == all_items
    # 2 shards → disjoint + exhaustive, deselected reported
    kept = []
    for shard in ("1", "2"):
        monkeypatch.setenv("PYTEST_NUM_SHARDS", "2")
        monkeypatch.setenv("PYTEST_SHARD", shard)
        items = list(all_items)
        cfg = Config()
        conftest.pytest_collection_modifyitems(cfg, items)
        assert len(items) + len(cfg.hook.deselected) == len(all_items)
        kept.extend(i.nodeid for i in items)
    assert sorted(kept) == sorted(i.nodeid for i in all_items)
    # out-of-range shard id fails loudly
    monkeypatch.setenv("PYTEST_SHARD", "3")
    with pytest.raises(pytest.UsageError):
        conftest.pytest_collection_modifyitems(Config(), list(all_items))


def test_ci_workflow_wires_the_gate():
    """The workflow must call the schema module (not a heredoc), set the
    virtual-device count job-wide, pin the one JAX the code targets, and
    fan the matrix out over python versions with sharded tier-1."""
    wf = (
        pathlib.Path(__file__).resolve().parent.parent
        / ".github" / "workflows" / "ci.yml"
    ).read_text()
    assert "benchmarks.check_schema" in wf
    assert "benchmarks.bench_mutable" in wf  # the live-corpus lane feeds the gate
    assert "xla_force_host_platform_device_count=8" in wf
    assert "fail-fast: false" in wf
    assert "PYTEST_NUM_SHARDS" in wf
    assert '"3.11"' in wf and '"3.12"' in wf
    assert "jax[cpu]==${JAX_VERSION}" in wf and "JAX_VERSION: 0.9.0" in wf
    assert "upload-artifact" in wf
    assert "ruff check" in wf and "ruff format --check" in wf
    assert "python - <<" not in wf  # the heredoc is gone for good
    # observability artifacts: the bench/chaos lanes emit a Chrome trace +
    # metrics snapshot and upload them per matrix cell
    assert "--trace-out" in wf and "--metrics-out" in wf
    # compile audit + perf-regression sentinel (ISSUE 9): the bench smoke
    # carries --audit (gated by check_schema), and the sentinel checks then
    # records against a history persisted across runs via actions/cache
    assert "--audit" in wf
    assert "benchmarks.sentinel check" in wf
    assert "benchmarks.sentinel record" in wf
    assert "actions/cache" in wf
    assert "BENCH_history" in wf
    assert wf.index("sentinel check") < wf.index("sentinel record")
    # serving-load lane (ISSUE 10): the bench_serve smoke feeds the
    # QPS/p99 curve + early-exit gates; one live run per ref; manual runs
    assert "benchmarks.bench_serve" in wf
    assert "--smoke" in wf
    assert "concurrency:" in wf
    assert "cancel-in-progress: true" in wf
    assert "workflow_dispatch" in wf
    # format drift blocks: no advisory escape hatch left in the lint job
    assert "continue-on-error" not in wf.split("tier1:")[0]
