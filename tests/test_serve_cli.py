"""``python -m repro.launch.serve`` end to end at tiny sizes: retrieval is
the default mode, both servers report the oracle's match count, the chaos
lane writes its trace and metrics, and ``--mode auto`` calibrates, plans
and runs. The compile cache is left off, and calibration writes to a
temporary directory.
"""

import functools
import json
import re
import sys

import numpy as np
import pytest

import repro.launch.serve as serve
from repro.core.sparse import to_dense
from repro.data.sparse import perturbed_queries, sparse_clustered_corpus
from repro.planner import calibrate as calibrate_mod

TINY = ["--corpus-n", "256", "--corpus-m", "512", "--block", "128"]


def _run(monkeypatch, capsys, argv):
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", ["serve", *argv])
    serve.main()
    return capsys.readouterr().out


def _oracle_count(n, m, avg_nnz, nq, t):
    """Matches the retrieval demo's queries have in its corpus, by a dense
    float64 product (same generators, same seeds)."""
    sp = sparse_clustered_corpus(n, m, avg_nnz, n_clusters=16, seed=0)
    Q = np.asarray(perturbed_queries(sp, nq, seed=1), np.float64)
    C = np.asarray(to_dense(sp), np.float64)
    return int(((Q @ C.T) >= t).sum())


@pytest.mark.parametrize("server", ["step", "continuous"])
def test_default_mode_serves_retrieval_with_the_oracle_count(
    monkeypatch, capsys, server
):
    out = _run(
        monkeypatch, capsys,
        TINY + ["--requests", "8", "--batch", "4", "--server", server],
    )
    line = next(x for x in out.splitlines() if f"{server} server:" in x)
    got = int(re.search(r"(\d+) matches", line).group(1))
    assert got == _oracle_count(256, 512, 16.0, 8, 0.5)
    assert "8 queries" in line and "8 exact" in line
    assert "shed=0, degraded=0, retries=0, stale=0" in line


def test_chaos_lane_writes_its_trace_and_metrics(monkeypatch, capsys, tmp_path):
    trace, metrics = tmp_path / "trace.json", tmp_path / "metrics.json"
    out = _run(
        monkeypatch, capsys,
        TINY + [
            "--requests", "16", "--batch", "4", "--chaos",
            "--deadline-ms", "250",
            "--trace-out", str(trace), "--metrics-out", str(metrics),
        ],
    )
    assert "chaos seed=0" in out
    assert json.loads(trace.read_text())["traceEvents"]
    assert json.loads(metrics.read_text())


def test_auto_mode_calibrates_plans_and_runs(monkeypatch, capsys, tmp_path):
    monkeypatch.setenv("REPRO_CALIB_DIR", str(tmp_path))
    monkeypatch.setattr(calibrate_mod, "_MEMO", {})
    monkeypatch.setattr(
        calibrate_mod, "calibrate",
        functools.partial(
            calibrate_mod.calibrate, n=64, m=128, cap=8, m_sparse=256, iters=1
        ),
    )
    out = _run(monkeypatch, capsys, TINY + ["--mode", "auto", "--k", "8"])
    assert "[auto] calibrated" in out and "Plan:" in out
    ran = re.search(r"\[auto\] ran (\S+): .* (\d+) matches", out)
    assert ran, out
    assert list(tmp_path.glob("*.json"))  # the profile was saved


def test_help_lists_retrieval_as_default_and_auto_only(monkeypatch, capsys):
    with pytest.raises(SystemExit) as e:
        _run(monkeypatch, capsys, ["--help"])
    assert e.value.code == 0
    out = capsys.readouterr().out
    assert "--mode {retrieval,auto}" in out
    for gone in ("--arch", "--gen-tokens", "lm"):
        assert gone not in out.split("options:")[0]


def test_unknown_mode_is_refused(monkeypatch, capsys):
    with pytest.raises(SystemExit) as e:
        _run(monkeypatch, capsys, ["--mode", "lm"])
    assert e.value.code == 2
    assert "invalid choice: 'lm'" in capsys.readouterr().err
