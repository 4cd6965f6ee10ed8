"""Find the highest rate the continuous server sustains (its knee).

    python3 bench/knee.py --config glove100 --traffic poisson_single \
        --seed 1 --seconds 10 --rates 300 400 500 600 700 800

One process, one fresh server per rate, the cell's own traffic at each
offered rate. Per rate it prints the offered rate; the completed rate
(every request of the window over the time from the window's start to
the last answer); the backlog at the window's close (requests due but not
yet answered, which a steady server holds too: its rate times its
latency); the median latency of the first and the last tenth of the
requests; the p50/p99 over all; the generator's lateness; and the mean
batch fill. A rate is sustained when the completed rate keeps up with the
offered rate (within 5 %) and the queue does not grow over the window:
the last tenth waits at most 1.5 times as long as the first. The knee is
the highest sustained rate; the cell runs at 0.8 of it.
Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec  # noqa: E402
from bench.run import configure_compile_cache, find_chips  # noqa: E402


def sweep_point(cell, seed: int, seconds: float, rate: float) -> dict:
    cell.traffic["rate_qps"] = rate
    driver = importlib.import_module("bench.kinds.open_loop").Driver(cell, seed, seconds)
    driver.setup()
    driver.window(seconds)
    lat = driver.latency
    due = driver.due
    close = due[-1]
    done = due + lat
    tenth = max(1, len(lat) // 10)
    first, last = np.median(lat[:tenth]), np.median(lat[-tenth:])
    backlog = int(np.sum(done > close))
    completed = len(lat) / float(done.max())
    sustained = bool(completed >= 0.95 * rate and last <= 1.5 * first)
    return {
        "offered_qps": rate,
        "requests": len(lat),
        "completed_qps": completed,
        "generator_late_p99_ms": float(np.percentile(driver.lateness, 99)) * 1e3,
        "backlog_at_close": backlog,
        "first_tenth_p50_ms": first * 1e3,
        "last_tenth_p50_ms": last * 1e3,
        "p50_ms": float(np.percentile(lat, 50)) * 1e3,
        "p99_ms": float(np.percentile(lat, 99)) * 1e3,
        "batch_fill": driver.batch_fill,
        "sustained": sustained,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", default="glove100")
    ap.add_argument("--traffic", default="poisson_single")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.make_cell(
        spec.BENCH_DIR / "configs" / f"{args.config}.json", args.traffic,
        f"{args.config}.{args.traffic}",
    )
    if find_chips(cell.chips) is None:
        return 2
    configure_compile_cache()
    points = []
    for rate in args.rates:
        points.append(sweep_point(cell, args.seed, args.seconds, rate))
        print(json.dumps(points[-1]), flush=True)
    knee = max((p["offered_qps"] for p in points if p["sustained"]), default=None)
    print(json.dumps({"knee_qps": knee, "cell_rate_qps": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
