"""The news20 vertical cell on the CPU: its data, readers, work count and a tiny run.

Run with ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import data, reference, replies, spec, trace, work  # noqa: E402
from bench import work_vertical  # noqa: E402
from bench.kinds import Observed  # noqa: E402
from bench.kinds.vertical_selfjoin import VerticalObserved  # noqa: E402
from bench.run import Context, load_reader  # noqa: E402

SEED = 2**31 + 29
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _corpus(seed, n=400, m=60001, per_row=60):
    base = data.sparse_zipf_csr(n, m, n * per_row, 1.1, seed)
    return base, replies.quoted_replies(*base, seed)


@pytest.mark.parametrize("seed", [0, SEED, 2**40 + 3])
def test_quoted_replies_keep_nnz_unit_rows_and_seed(seed):
    base, (idx, val, nnz) = _corpus(seed)
    _, again = _corpus(seed)
    for x, y in zip((idx, val, nnz), again):
        np.testing.assert_array_equal(x, y)
    np.testing.assert_array_equal(nnz, base[2])  # every row keeps its length
    assert nnz.sum() == 400 * 60
    valid = np.arange(idx.shape[1])[None, :] < nnz[:, None]
    for r in range(idx.shape[0]):
        assert np.all(np.diff(idx[r, : nnz[r]]) > 0)  # sorted, unique
    assert np.all(val[~valid] == 0) and np.all(idx[~valid] == 0)
    np.testing.assert_allclose((val.astype(np.float64) ** 2).sum(1), 1.0, rtol=1e-6)
    changed = (idx != base[0]).any(axis=1)
    assert changed.sum() <= 100 and changed.sum() > 80  # a quarter of 400 rows


def test_quoted_replies_differ_by_seed_and_make_matches():
    (_, (i1, _, _)), (_, (i2, _, _)) = _corpus(1), _corpus(2)
    assert not np.array_equal(i1, i2)
    base, quoted = _corpus(1)

    def rows_with_match(host):
        s = reference.SparseSelfJoin(*host, 60001).scores(0, 400)
        return float((s >= 0.4).any(axis=1).mean())

    assert rows_with_match(base) < 0.05 < 0.2 < rows_with_match(quoted)


def _reduced(op_seconds, window_s=2.0, busy_s=1.0):
    return trace.Reduced(
        window_s=window_s, busy_s=busy_s, devices=4,
        op_seconds=op_seconds, idle_by_span={},
    )


def test_collective_ms_sums_collective_ops_per_join():
    ops = {
        "%all-reduce.3 = f32[512,20480]{1,0} all-reduce(f32[512,20480]{1,0} %x)": 0.010,
        "%all-gather-start = (s32[512,256]) all-gather-start(s32[512,256] %c)": 0.002,
        "%all-gather-done = s32[512,1024] all-gather-done((s32[512,256]) %a)": 0.003,
        "%collective-permute-start.1 = (f32[4]) collective-permute-start(f32[4] %x)":
            0.001,
        "%all-reduce-scatter-fusion.1 = f32[128] fusion(f32[512] %p), kind=kOutput":
            0.004,
        "%fusion.3 = f32[512]{0} fusion(f32[512]{0} %all-reduce.1), kind=kLoop": 0.5,
        "%dot.1 = f32[512,20480] dot(f32[512,5760] %a, f32[20480,5760] %b)": 0.7,
    }
    ctx = Context(Observed(units=2, unit="join"), _reduced(ops), PEAKS)
    got = load_reader("collective_ms.vertical")(ctx)
    assert got == pytest.approx(1e3 * 0.020 / 2)
    assert load_reader("collective_ms.vertical")(
        Context(Observed(units=0), _reduced(ops), PEAKS)) is None


def test_shard_imbalance_is_max_over_mean_in_percent():
    read = load_reader("shard_imbalance.vertical")
    obs = VerticalObserved(units=1, shard_nnz=(110, 90, 100, 100))
    assert read(Context(obs, None, None)) == pytest.approx(110.0)
    # the parent's program records no shards: nothing to read, no error
    assert read(Context(Observed(units=1), None, None)) is None


def test_mfu_vertical_counts_one_chip():
    n, nnz, chips, k = 1100, 40_000, 4, 64
    flops, nbytes = work_vertical.partial_tiles(n, nnz, chips, k)
    assert flops == pytest.approx(2 * n * 10_000)  # each row meets each nonzero
    assert nbytes == pytest.approx(10_000 * work.CSR_ENTRY + work.result_bytes(n, k))
    obs = VerticalObserved(units=3, work={"vertical_partials": (3 * flops, 3 * nbytes)})
    got = load_reader("mfu.vertical")(Context(obs, _reduced({}, window_s=0.5), PEAKS))
    assert got == pytest.approx(100 * 3 * flops / (0.5 * 197e12))


def test_news20_cell_loads_on_four_chips_with_its_metrics():
    cell = spec.load_cell("news20.vertical")
    assert cell.chips == 4 and cell.traffic["kind"] == "vertical_selfjoin"
    assert {m["name"] for m in cell.end_to_end} == {"selfjoin_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "idle_share.vertical", "collective_ms.vertical",
        "shard_imbalance.vertical", "mfu.vertical",
    }
    for m in cell.per_layer:
        assert callable(load_reader(m["name"]))
    c = cell.config
    assert (c["n"], c["m"], c["nnz"]) == (20001, 313389, 2984809)
    assert (c["threshold"], c["k"], c["shards"]) == (0.4, 64, 4)
    assert c["reduced"] == [] and c["distribution"] == "vertical"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cfg,) = [x for x in bench["configs"] if x["name"] == "news20"]
    assert cfg["file"] == "bench/configs/news20.json" and cfg["reduced"] == []


TINY_RUN = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
from bench import reference, spec
from bench.kinds import selfjoin
from bench.kinds.vertical_selfjoin import Driver
from bench.run import run_cell
cell = spec.load_cell("news20.vertical")
cell.config.update(n=700, m=5003, nnz=700 * 30, k=8, block_rows=128)
out = {{}}
for trace in (False, True):
    r = run_cell(cell, {seed}, 0.3, trace, None, log=lambda s: None)
    out[str(trace)] = {{k: r[k] for k in ("correct", "attempted", "metrics", "checks")}}
d = Driver(cell, {seed}, 0.3)
d.setup(); d.window(0.3)
c = cell.config
answers = reference.control_selfjoin(*d.host, c["m"], c["threshold"], c["k"])
(v,) = selfjoin.judge_joins(c, d.host, [answers])
out["control_value_gap"] = v.value_gap
out["program_value_gap"] = d.check().numbers["value_gap"][0]
print(json.dumps(out))
"""


def test_tiny_run_on_four_cpu_devices_is_correct_and_the_control_is_not():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", TINY_RUN.format(root=ROOT, seed=SEED)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    plain, traced = out["False"], out["True"]
    assert plain["correct"] and plain["attempted"] > 0
    assert set(plain["metrics"]) == {"selfjoin_s", "setup_s"}
    assert traced["correct"]
    assert 100.0 <= traced["metrics"]["shard_imbalance.vertical"]["value"] <= 106.0
    tol = spec.load_cell("news20.vertical").config["score_tol"]
    assert out["program_value_gap"] <= tol < out["control_value_gap"]
