"""The reduction of the program's spans and device scopes, on the CPU.

``bench/program_trace.py`` reads the program's host spans and each device
operation's ``tf_op`` path from a profiler trace; the readers of
``host_plan_ms``, ``support_gather_ms``, ``fold_ms`` and ``worklist_fill``
turn them into per-layer numbers. Checked here on synthetic traces, on the
chip traces in ``data/`` and on a tiny whole traced run.
"""

from __future__ import annotations

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import program_trace as pt  # noqa: E402
from bench import spec, trace  # noqa: E402
from bench.kinds import Observed  # noqa: E402
from bench.run import load_reader  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
PARENT = os.path.join(DATA, "small_trace.xplane.pb")  # no program spans
PROGRAM = os.path.join(DATA, "program_trace.xplane.pb")  # with them


def _ops(intervals, paths):
    names = sorted(set(paths) | {""})
    return pt.DeviceOps(
        np.array([s for s, _ in intervals], np.int64),
        np.array([e for _, e in intervals], np.int64),
        np.array([names.index(p) for p in paths], np.int64),
        names,
    )


def _program(spans=(), ops=None, window=(0, 1000), bench=()):
    return pt.ProgramTrace(
        window, [pt.Span(n, s, e, "python3", dict(st)) for n, s, e, st in spans],
        [("window", *window), *bench], [] if ops is None else [ops],
    )


def _ctx(program=None, units=2):
    ctx = types.SimpleNamespace(observed=Observed(units=units), trace=None, peaks=None)
    if program is not None:
        ctx.program = program
    return ctx


# --- synthetic ----------------------------------------------------------------


def test_scope_time_is_the_union_inside_the_window():
    gather = "jit(_sparse_compacted_inner)/support_gather/while/body/gather:"
    fold = "jit(_sparse_compacted_inner)/fold/while/body/select_n:"
    ops = _ops(
        [(-50, 100), (50, 150), (200, 300), (300, 400), (900, 1200), (500, 600)],
        [gather, gather, fold, fold, gather, "jit(f)/folded/add:"],
    )
    p = _program(ops=ops)
    assert p.scope_seconds("support_gather") == pytest.approx((150 + 100) * 1e-9)
    assert p.scope_seconds("fold") == pytest.approx(200e-9)  # not "folded"
    assert p.scope_seconds("mask") == 0


def test_idle_gaps_go_to_the_innermost_span_and_keep_the_total():
    ops = _ops([(100, 200), (600, 700)], ["a", "a"])
    p = _program(
        spans=[("apss/selfjoin", 0, 900, {}), ("apss/support_gather", 250, 550, {})],
        ops=ops, bench=[("join", 0, 950)],
    )
    gaps = dict(p.idle_gaps())
    # [0,100) and [700,1000): midpoints 50 and 850 in apss/selfjoin;
    # [200,600) has its middle in apss/support_gather
    assert gaps == {
        "apss/selfjoin": pytest.approx(400e-9),
        "apss/support_gather": pytest.approx(400e-9),
    }
    assert sum(gaps.values()) == pytest.approx(1e-6 - 200e-9)
    assert p.longest_gaps(1) == [(200, 600)]


def test_readers_on_a_synthetic_context():
    ops = _ops(
        [(0, 300), (300, 400), (400, 450)],
        ["j/support_gather/while:", "j/fold/while:", "j/kernel:"],
    )
    spans = [
        ("apss/bounds", 0, 10, {}),
        ("apss/worklist", 10, 20, {"live": 3, "entries": 3}),
        ("apss/support_gather", 20, 60, {}), ("apss/upload", 60, 100, {}),
        ("apss/dispatch", 100, 500, {}),
        ("query/mask", 500, 600, {}),
        ("query/worklist", 600, 620, {"live": 5, "entries": 8}),
        ("query/worklist", 700, 720, {"live": 4, "entries": 8}),
    ]
    ctx = _ctx(_program(spans, ops))
    read = {n: load_reader(n)(ctx) for name in pt.METRICS.values() for n in name}
    # bounds, worklist, support gather, upload, both query worklists; not
    # the dispatch or the mask
    assert read["host_plan_ms.selfjoin"] == pytest.approx(1e3 * 140e-9 / 2)
    assert read["host_plan_ms.batch"] == read["host_plan_ms.selfjoin"]
    assert read["support_gather_ms.selfjoin"] == pytest.approx(1e3 * 300e-9 / 2)
    assert read["fold_ms.selfjoin"] == pytest.approx(1e3 * 100e-9 / 2)
    assert read["worklist_fill.batch"] == pytest.approx(100 * 12 / 19)


def test_readers_return_nothing_without_the_program_reduction():
    """A run of a program without spans and scopes (or a harness that does
    not attach the reduction) leaves the metrics out instead of raising."""
    bare = _ctx()
    empty = _ctx(_program(ops=_ops([(0, 10)], ["jit(f)/add:"])))
    for names in pt.METRICS.values():
        for n in names:
            assert load_reader(n)(bare) is None
            assert load_reader(n)(empty) is None


# --- chip traces ------------------------------------------------------------------


def test_wire_reader_matches_profile_data_and_reads_tf_op():
    devices, _ = trace.read_xspace(PARENT)
    with open(PARENT, "rb") as f:
        (ops,) = pt.device_ops(f.read())
    np.testing.assert_array_equal(ops.start, devices[0].start)
    np.testing.assert_array_equal(ops.end, devices[0].end)
    paths = [ops.paths[i] for i in ops.path]
    csr = [p for p in paths if p.startswith("jit(_sparse_compacted_inner)/while/body/")]
    rect = [p for p in paths if p.startswith("jit(_rect_dense_inner)/")]
    assert csr and rect
    assert "" in paths  # a while loop's own event carries no tf_op


def test_parent_trace_has_no_program_spans_and_the_same_idle_time():
    devices, spans = trace.read_xspace(PARENT)
    r = trace.reduce(devices, spans)
    p = pt.read(PARENT)
    assert p.spans == [] and p.scope_seconds("fold") == 0
    assert dict(p.idle_gaps()) == pytest.approx(r.idle_by_span)


def test_program_trace_recorded_on_the_chip():
    """A small self-join and ``query_topk`` batches with the program's spans
    and scopes (``record_trace.py`` on the v5e)."""
    devices, spans = trace.read_xspace(PROGRAM)
    r = trace.reduce(devices, spans)
    p = pt.read(PROGRAM)
    for scope in ("support_gather", "fold", "mask"):
        assert 0 < p.scope_seconds(scope) <= r.busy_s
    assert p.scope_seconds("support_gather") + p.scope_seconds("fold") <= r.busy_s
    names = {s.name for s in p.spans}
    assert {"apss/selfjoin", "apss/worklist", "apss/support_gather", "apss/upload",
            "serving/query", "query/mask", "query/worklist"} <= names
    (wl, *_) = [s for s in p.spans if s.name == "query/worklist"]
    assert 0 < wl.stats["live"] <= wl.stats["entries"] <= wl.stats["total"] * 2
    (gather, *_) = [s for s in p.spans if s.name == "apss/support_gather"]
    assert gather.stats["support"] % 128 == 0 and gather.stats["support_chunk"] >= 128
    gaps = dict(p.idle_gaps(top=100))
    assert sum(gaps.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-9)
    under_apss = sum(v for k, v in gaps.items() if k.startswith("apss/"))
    assert gaps.get("join", 0) < under_apss


# --- a whole traced run -----------------------------------------------------------


def test_tiny_traced_run_reads_the_program_spans():
    from bench.trace_run import traced_run

    cell = spec.load_cell("glove100.batch")
    cell.config.update(n=3000, m=100)
    cell.traffic.update(batch=64, pool_batches=4, check_queries=40, trace_seconds=0.5)
    r = traced_run(cell, 2**31 + 11, 0.5, None)
    assert r["correct"] and r["units"] > 0
    spans = r["spans"]
    calls = spans["serving/query"]["count"]
    assert spans["query/worklist"]["count"] == calls == r["units"]
    wl = spans["query/worklist"]["last"]
    assert r["metrics"]["worklist_fill.batch"] == pytest.approx(
        100 * wl["live"] / wl["entries"]
    )
    assert r["metrics"]["host_plan_ms.batch"] > 0
    assert "fold_ms.batch" not in r["metrics"]  # no device operations on the CPU
