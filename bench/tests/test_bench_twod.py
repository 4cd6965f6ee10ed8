"""The enron 2-D cell on the CPU: its metrics, readers and a tiny run.

Run with ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import spec, trace  # noqa: E402
from bench.kinds import Observed  # noqa: E402
from bench.kinds.vertical_selfjoin import VerticalObserved  # noqa: E402
from bench.run import Context, load_reader  # noqa: E402

SEED = 2**31 + 31


def test_enron_cell_loads_on_four_chips_with_its_eight_metrics():
    cell = spec.load_cell("enron.twod")
    assert cell.chips == 4 and cell.traffic["kind"] == "twod_selfjoin"
    assert {m["name"] for m in cell.end_to_end} == {"selfjoin_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "idle_share.twod", "collective_ms.twod", "mfu.twod",
        "shard_imbalance.twod", "ring_ms.twod", "exact_block_share.twod",
    }
    for m in cell.per_layer:
        assert callable(load_reader(m["name"]))
    c = cell.config
    assert (c["n"], c["m"], c["nnz"]) == (39861, 28102, 3710420)
    assert (c["threshold"], c["k"], c["block_rows"]) == (0.5, 64, 512)
    assert c["distribution"] == "2d" and c["mesh"] == {"data": 2, "model": 2}
    assert c["reduced"] == [] and c["score_tol"] == 7e-7
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (cfg,) = [x for x in bench["configs"] if x["name"] == "enron"]
    assert cfg["file"] == "bench/configs/enron.json" and cfg["reduced"] == []
    assert cfg["source"] == "https://archive.ics.uci.edu/dataset/164/bag+of+words"


def _reduced(op_seconds, window_s=2.0, busy_s=1.0):
    return trace.Reduced(
        window_s=window_s, busy_s=busy_s, devices=4,
        op_seconds=op_seconds, idle_by_span={},
    )


def test_ring_ms_counts_collective_permutes_only():
    ops = {
        "%collective-permute-start.1 = (s32[19968,96]) collective-permute-start"
        "(s32[19968,96] %c), source_target_pairs={{0,2},{2,0}}": 0.004,
        "%collective-permute-done.1 = s32[19968,96] collective-permute-done"
        "((s32[19968,96]) %s)": 0.002,
        "%collective-permute.2 = f32[128] collective-permute(f32[128] %v)": 0.001,
        "%all-reduce.3 = f32[512,19968]{1,0} all-reduce(f32[512,19968]{1,0} %x)": 0.010,
        "%all-gather-start = (s32[512,256]) all-gather-start(s32[512,256] %c)": 0.002,
        "%fusion.3 = f32[512]{0} fusion(f32[512]{0} %collective-permute.1)": 0.5,
    }
    ctx = Context(Observed(units=2, unit="join"), _reduced(ops), None)
    read = load_reader("ring_ms.twod")
    assert read(ctx) == pytest.approx(1e3 * 0.007 / 2)
    assert load_reader("collective_ms.twod")(ctx) == pytest.approx(1e3 * 0.019 / 2)
    assert read(Context(Observed(units=0), _reduced(ops), None)) is None
    assert read(Context(Observed(units=1), None, None)) is None


def test_exact_block_share_is_the_exact_routes_share_in_percent():
    read = load_reader("exact_block_share.twod")
    obs = VerticalObserved(units=3, blocks_pruned=117, blocks_exact=39)
    assert read(Context(obs, None, None)) == pytest.approx(25.0)
    assert read(Context(VerticalObserved(units=1, blocks_exact=4), None, None)) == 100.0
    # the parent's program counts no 2-D routes: nothing to read, no error
    assert read(Context(Observed(units=1), None, None)) is None
    assert read(Context(VerticalObserved(units=1), None, None)) is None


TINY_RUN = """
import json, os, sys
sys.path[:0] = [{root!r}, os.path.join({root!r}, "src")]
from bench import reference, spec
from bench.kinds import selfjoin
from bench.kinds.twod_selfjoin import Driver
from bench.run import run_cell
cell = spec.load_cell("enron.twod")
cell.config.update(n=1019, m=3001, nnz=1019 * 40, k=8, block_rows=128, threshold=0.3)
out = {{}}
for trace in (False, True):
    r = run_cell(cell, {seed}, 0.3, trace, None, log=lambda s: None)
    out[str(trace)] = {{k: r[k] for k in ("correct", "attempted", "metrics", "checks")}}
d = Driver(cell, {seed}, 0.3)
d.setup(); d.window(0.3)
out["mesh"] = dict(d.mesh.shape)
out["routes"] = d.info()
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
    assert out["mesh"] == {"data": 2, "model": 2}
    assert plain["correct"] and plain["attempted"] > 0
    assert set(plain["metrics"]) == {"selfjoin_s", "setup_s"}
    assert traced["correct"]
    m = traced["metrics"]
    assert 100.0 <= m["shard_imbalance.twod"]["value"] <= 110.0
    assert 0.0 <= m["exact_block_share.twod"]["value"] <= 100.0
    routes = out["routes"]
    assert len(routes["shard_nnz"]) == 4 and sum(routes["shard_nnz"]) == 1019 * 40
    assert routes["blocks_pruned"] + routes["blocks_exact"] > 0
    tol = spec.load_cell("enron.twod").config["score_tol"]
    assert out["program_value_gap"] <= tol < out["control_value_gap"]
