import os

# 8 virtual devices for the distribution benchmarks (paper Figs 3-6);
# NOT the dry-run's 512 (that runs only via launch/dryrun.py).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Benchmark driver — one module per paper table. Prints
``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--only sequential,pruning,...]
    PYTHONPATH=src python -m benchmarks.run --json [PATH] [--n 4096] \
        [--sweep-n 1024] [--sweep-m 8192]

``--json`` writes the perf-trajectory artifact (default ``BENCH_apss.json``):
the streaming-extraction comparison (dense-kernel vs fused vs
fused-compacted) at ``--n`` plus the sparse density sweep
(``bench_sparse``: dense fused paths vs the inverted-index CSR paths at
densities 0.1%/1%/10%), each entry carrying corpus density and live-tile
fractions so the trajectory stays interpretable across workloads.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from repro.cache import enable_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: sequential,pruning,blocksize,parallel,"
                         "apss_stream,sparse,roofline")
    ap.add_argument("--json", nargs="?", const="BENCH_apss.json", default=None,
                    metavar="PATH",
                    help="write the APSS perf artifact to PATH and exit")
    ap.add_argument("--n", type=int, default=4096,
                    help="corpus rows for the --json streaming comparison")
    ap.add_argument("--sweep-n", type=int, default=1024,
                    help="corpus rows for the --json sparse density sweep")
    ap.add_argument("--sweep-m", type=int, default=8192,
                    help="corpus dims for the --json sparse density sweep")
    ap.add_argument("--audit", action="store_true",
                    help="with --json: append the model-vs-HLO compile "
                         "audit lane (repro.obs.audit) to the artifact")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import (
        bench_apss_stream,
        bench_blocksize,
        bench_parallel,
        bench_pruning,
        bench_sequential,
        bench_sparse,
        roofline,
    )

    if args.json:
        def persist(r):
            with open(args.json, "w") as f:
                json.dump(r, f, indent=2)
                f.write("\n")

        from benchmarks.common import provenance

        r = bench_apss_stream.measure(n=args.n)
        r["provenance"] = provenance()
        persist(r)  # minutes of streaming data survive a sweep failure
        for name, v in r["variants"].items():
            print(f"{name}: {v['us_per_call']:.0f} us")
        print(
            f"live tiles {r['live_tiles']}/{r['total_tiles']} "
            f"({r['live_tile_fraction']:.3f})"
        )
        block = min(256, max(64, args.sweep_n // 4))
        r["sparse_sweep"] = bench_sparse.sweep(
            args.sweep_n, args.sweep_m, block=block
        )
        for e in r["sparse_sweep"]["entries"]:
            times = {
                k: f"{v['us_per_call']:.0f}us"
                for k, v in e["variants"].items()
            }
            print(f"density={e['density']:.4f}: {times}")
        if args.audit:
            from repro.obs.audit import run_audit

            report = run_audit()
            r["audit"] = report.as_dict()
            print(report.describe())
        persist(r)
        print(f"-> {args.json}")
        return

    suites = {
        "sequential": bench_sequential.run,    # paper Tables 2-3
        "pruning": bench_pruning.run,          # paper Tables 5-6
        "blocksize": bench_blocksize.run,      # paper Tables 7-8 / Fig 8
        "parallel": bench_parallel.run,        # paper Figs 3-6
        "apss_stream": bench_apss_stream.run,  # streaming fused extraction
        "sparse": bench_sparse.run,            # sparse vs dense density sweep
        "roofline": roofline.run,              # EXPERIMENTS.md §Roofline
    }
    selected = args.only.split(",") if args.only else list(suites)

    lines: list = ["name,us_per_call,derived"]
    failed = []
    for name in selected:
        try:
            suites[name](lines)
        except Exception:
            traceback.print_exc()
            failed.append(name)
    print("\n".join(lines))
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
