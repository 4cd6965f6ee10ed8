"""Serving entry point: the APSS retrieval server and the execution planner.

Two modes:

- ``--mode retrieval`` (default): the APSS serving path — build a
  :class:`~repro.serving.index.APSSIndex` ONCE over a synthetic sparse
  corpus, then stream query batches through a
  :class:`~repro.serving.server.RetrievalServer` (one jit'd ``query_topk``
  per step boundary, LRU cache, per-query latency/QPS report).
- ``--mode auto``: the execution planner end-to-end — calibrate the
  hardware profile (one-shot, cached), plan the APSS self-join over the
  synthetic corpus (``planner.plan_apss``: every variant priced by the
  cost models), print the chosen Plan + the ranked predictions, then run
  it and report predicted vs measured.

CPU-scale demos (reduced configs):
    PYTHONPATH=src python -m repro.launch.serve \\
        --corpus-n 4096 --corpus-m 2048 --requests 64 --batch 8
    PYTHONPATH=src python -m repro.launch.serve --mode auto \\
        --corpus-n 2048 --corpus-m 8192 --threshold 0.5
"""

from __future__ import annotations

import argparse
import contextlib
import time

import jax

from repro.cache import enable_compile_cache


def run_retrieval(args) -> None:
    """Retrieval mode: index once, serve query batches, report QPS."""
    from repro.data.sparse import perturbed_queries, sparse_clustered_corpus
    from repro.serving import (
        ContinuousRetrievalServer,
        RetrievalServer,
        build_index,
    )

    t0 = time.time()
    sp = sparse_clustered_corpus(
        args.corpus_n, args.corpus_m, args.avg_nnz, n_clusters=16, seed=0
    )
    t_gen = time.time() - t0

    t0 = time.time()
    index = build_index(sp, block_rows=args.block, normalize=False)
    t_build = time.time() - t0

    # Perturbed corpus rows: realistic near-duplicate, topical traffic.
    qs = list(perturbed_queries(sp, args.requests, seed=1))

    deadline_s = args.deadline_ms / 1e3 if args.deadline_ms else None

    def make_server(chaos: bool = False):
        # Chaos lane: a fresh seeded FaultPlan per server (plans are
        # consumable) — injected step delays + transient scoring-tier
        # errors exercise the shed/degrade/retry machinery under the same
        # traffic the clean lane measures.
        plan = None
        if chaos:
            from repro.robust import FaultPlan

            steps = max(1, args.requests // args.batch)
            # Delays hit the serving step loop; transient errors hit the
            # XLA scoring tier (always present — the kernel tier is
            # TPU-only) so the retry/degrade machinery actually exercises.
            plan = FaultPlan.chaos(args.chaos_seed, steps=steps,
                                   kernel_errors=2, scope="serving",
                                   error_scope="serving.xla")
        kwargs = dict(
            threshold=args.threshold, k=args.k, max_batch=args.batch,
            deadline_s=deadline_s, fault_plan=plan,
            max_retries=2, backoff_s=0.001,
        )
        if args.server == "continuous":
            return ContinuousRetrievalServer(
                index, workers=args.workers, **kwargs
            )
        return RetrievalServer(index, **kwargs)

    # Warm up compile caches on a THROWAWAY server (the jitted scoring
    # paths are module-level, so compilation carries over), then time a
    # fresh one — otherwise the warmup batch sits in the LRU cache and
    # inflates the measured QPS.
    with contextlib.closing(make_server()) as warm:
        warm.serve(qs[: args.batch])
    srv = make_server(chaos=args.chaos)
    with contextlib.closing(srv):
        t0 = time.time()
        results = srv.serve(qs)
        dt = time.time() - t0
    n_match = sum(r.count for r in results)
    served = [r for r in results if r.status == "ok"]
    print(
        f"[serve] corpus n={sp.n} m={sp.m} (gen {t_gen:.1f}s) "
        f"index build {t_build:.2f}s"
        + (f" chaos seed={args.chaos_seed}" if args.chaos else "")
    )
    print(
        f"[serve] {args.server} server: {len(results)} queries in {dt:.3f}s "
        f"({len(results)/dt:.1f} QPS, batch {args.batch}, "
        f"{1e3*dt/len(results):.2f} ms/query), {n_match} matches, "
        f"{len(served)} exact, stats={srv.stats}"
    )


def run_auto(args) -> None:
    """Auto mode: calibrate → plan (print it) → run the chosen variant."""
    import numpy as np

    from repro.compat import make_mesh
    from repro.data.sparse import sparse_clustered_corpus
    from repro.planner.calibrate import calibrate, profile_path
    from repro.planner.plan import plan_apss

    t0 = time.time()
    sp = sparse_clustered_corpus(
        args.corpus_n, args.corpus_m, args.avg_nnz, n_clusters=16, seed=0
    )
    print(f"[auto] corpus n={sp.n} m={sp.m} cap={sp.cap} "
          f"(gen {time.time() - t0:.1f}s)")

    t0 = time.time()
    profile = calibrate(save=True)
    print(
        f"[auto] calibrated {profile.device_kind} in {time.time() - t0:.1f}s "
        f"(matmul {profile.matmul_gflops:.1f} GF/s, gather "
        f"{profile.gather_gflops:.2f} GF/s, wire "
        f"{profile.collective_gbps:.2f} GB/s) -> {profile_path()}"
    )

    mesh = (
        make_mesh((jax.device_count(),), ("data",))
        if jax.device_count() > 1
        else None
    )
    t0 = time.time()
    plan = plan_apss(
        sp, args.threshold, args.k, mesh, profile=profile,
        autotune=args.autotune,
    )
    print(f"[auto] planned in {time.time() - t0:.2f}s")
    print(plan.describe())

    t0 = time.time()
    res = jax.block_until_ready(plan.run())
    cold = time.time() - t0
    t0 = time.time()
    res = jax.block_until_ready(plan.run())
    warm = time.time() - t0
    n_match = int(np.asarray(res.counts).sum())
    print(
        f"[auto] ran {plan.config.name}: {warm * 1e3:.1f}ms warm "
        f"({cold * 1e3:.0f}ms cold), {n_match} matches; predicted "
        f"{plan.cost.total_s * 1e3:.1f}ms "
        f"({plan.cost.total_s / max(warm, 1e-9):.2f}x of measured)"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["retrieval", "auto"], default="retrieval")
    ap.add_argument("--requests", type=int, default=64,
                    help="retrieval mode: number of queries served")
    ap.add_argument("--corpus-n", type=int, default=4096)
    ap.add_argument("--corpus-m", type=int, default=2048)
    ap.add_argument("--avg-nnz", type=float, default=16.0)
    ap.add_argument("--block", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--server", choices=["step", "continuous"],
                    default="continuous",
                    help="retrieval mode: step-boundary batching or"
                         " slot-granularity continuous batching")
    ap.add_argument("--workers", type=int, default=2,
                    help="continuous server: concurrent scoring workers")
    ap.add_argument("--threshold", type=float, default=0.5)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--autotune", action="store_true",
                    help="auto mode: microbenchmark the top-3 plans")
    ap.add_argument("--chaos", action="store_true",
                    help="retrieval mode: inject seeded faults (step delays"
                         " + transient scoring errors) and report the"
                         " shed/degraded/retries counters")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="retrieval mode: per-request deadline; late"
                         " requests are shed, not served")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace-event JSON of the"
                         " run (plan/execute/serving spans) to PATH")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a metrics snapshot to PATH (.prom/.txt ->"
                         " Prometheus text, otherwise JSON)")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.obs import MetricsRegistry, Tracer, export

    tracer = Tracer() if args.trace_out else None
    registry = MetricsRegistry() if args.metrics_out else None
    with contextlib.ExitStack() as stack:
        # Registry first so finalize() (tracer exit) can observe sweep
        # step-time/skew histograms into it.
        if registry is not None:
            stack.enter_context(registry)
        if tracer is not None:
            stack.enter_context(tracer)
        _run_mode(args)
    if tracer is not None:
        export.write_chrome_trace(args.trace_out, tracer, registry)
        print(f"[obs] trace -> {args.trace_out}")
    if registry is not None:
        export.write_metrics(args.metrics_out, registry)
        print(f"[obs] metrics -> {args.metrics_out}")


def _run_mode(args) -> None:
    if args.mode == "auto":
        run_auto(args)
    else:
        run_retrieval(args)


if __name__ == "__main__":
    main()
