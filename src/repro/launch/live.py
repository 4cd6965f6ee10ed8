"""Live-corpus demo: a MutableAPSSIndex under continuous mutation.

Walks the whole ISSUE-7 surface on a synthetic corpus — build, streamed
appends (delta join), deletes (tombstones + exact graph repair),
compaction, queries through a version-cache-invalidating
:class:`~repro.serving.server.RetrievalServer`, and a WAL kill/replay
round-trip — printing per-op latency and the telemetry counters. The
closing check rebuilds the final corpus from scratch and asserts the
standing graph is bit-identical (the metamorphic invariant, live).

CPU-scale demo:
    PYTHONPATH=src python -m repro.launch.live --n 2048 --m 512 --deltas 32
"""

from __future__ import annotations

import argparse
import contextlib
import os
import tempfile
import time

import numpy as np

from repro.cache import enable_compile_cache
from repro.obs import MetricsRegistry, Tracer, export
from repro.planner import telemetry
from repro.serving import MutableAPSSIndex, RetrievalServer


def _tick(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    print(f"  {label:<38} {1e3 * (time.perf_counter() - t0):8.1f} ms")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--deltas", type=int, default=32,
                    help="rows per append batch")
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--threshold", type=float, default=0.2)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the"
                         " mutation/serve loop to PATH")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot to PATH (.prom/.txt ->"
                         " Prometheus text, otherwise JSON)")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = Tracer() if args.trace_out else None
    registry = MetricsRegistry() if args.metrics_out else None
    with contextlib.ExitStack() as stack:
        if registry is not None:
            stack.enter_context(registry)
        if tracer is not None:
            stack.enter_context(tracer)
        _run(args)
    if tracer is not None:
        export.write_chrome_trace(args.trace_out, tracer, registry)
        print(f"[obs] trace -> {args.trace_out}")
    if registry is not None:
        export.write_metrics(args.metrics_out, registry)
        print(f"[obs] metrics -> {args.metrics_out}")


def _run(args) -> None:
    rng = np.random.default_rng(args.seed)
    D = rng.normal(size=(args.n, args.m)).astype(np.float32)
    kept: list[tuple[int, np.ndarray]] = []

    with tempfile.TemporaryDirectory() as td, telemetry.CommLog() as log:
        wal = os.path.join(td, "live")
        print(f"live corpus: n={args.n} m={args.m} t={args.threshold} "
              f"k={args.k} (WAL at {wal})")
        idx = _tick(
            f"build ({args.n} rows)",
            lambda: MutableAPSSIndex(
                D, threshold=args.threshold, k=args.k,
                block_rows=args.block, directory=wal,
            ),
        )
        kept += [(g, D[g]) for g in range(args.n)]
        srv = RetrievalServer(idx, threshold=args.threshold, k=args.k,
                              max_batch=8)
        Q = rng.normal(size=(8, args.m)).astype(np.float32)

        for r in range(args.rounds):
            new = rng.normal(size=(args.deltas, args.m)).astype(np.float32)
            gids = _tick(
                f"round {r}: append {args.deltas} (delta join)",
                lambda: idx.append(new),
            )
            kept += list(zip(gids, new))
            live = [g for g, _ in kept]
            victims = sorted(
                int(g)
                for g in rng.choice(live, size=args.deltas // 2,
                                    replace=False)
            )
            _tick(
                f"round {r}: delete {len(victims)} (graph repair)",
                lambda: idx.delete(victims),
            )
            kept = [(g, row) for g, row in kept if g not in set(victims)]
            res = _tick(
                f"round {r}: serve 8 queries (cache ver {idx.version})",
                lambda: srv.serve(list(Q)),
            )
            assert all(x.status == "ok" for x in res)

        _tick("compact (tombstone rewrite)", idx.compact)
        before = idx.graph()

        # durability round-trip: reopen from WAL + snapshots
        reopened = _tick(
            "reopen from WAL (restore + replay)",
            lambda: MutableAPSSIndex(
                corpus=None, threshold=args.threshold, k=args.k,
                block_rows=args.block, directory=wal,
            ),
        )
        after = reopened.graph()
        assert np.array_equal(before[0], after[0])
        assert np.array_equal(before[1].values, after[1].values)
        assert np.array_equal(before[1].indices, after[1].indices)

        # the metamorphic invariant, live: fresh rebuild == mutated index
        surv = np.asarray([g for g, _ in kept], np.int64)
        fresh = _tick(
            f"oracle rebuild ({len(kept)} surviving rows)",
            lambda: MutableAPSSIndex(
                np.stack([row for _, row in kept]),
                threshold=args.threshold, k=args.k, block_rows=args.block,
            ),
        )
        _, og = fresh.graph()
        ti = np.where(og.indices >= 0, surv[np.maximum(og.indices, 0)], -1)
        assert np.array_equal(before[1].values, og.values)
        assert np.array_equal(before[1].indices, ti)
        print(f"graph bit-identical to fresh rebuild over {idx.n} live rows "
              f"(version {idx.version})")
        counters = {k: v for k, v in sorted(log.counters.items())}
        print(f"counters: {counters}")
        joins = log.by_variant("serving/delta-join")
        if joins:
            lf = [j.live_fraction for j in joins if j.live_fraction]
            print(f"delta joins: {len(joins)} recorded, "
                  f"mean live-tile fraction "
                  f"{np.mean(lf):.2f}" if lf else "delta joins: recorded")


if __name__ == "__main__":
    main()
