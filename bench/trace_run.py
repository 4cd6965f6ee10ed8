"""One traced run of a cell, reduced with the program's spans and scopes.

    python3 bench/trace_run.py --workload <name> --seed <n> --seconds <s>
    python3 bench/trace_run.py --config <file> --traffic <mix> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does (set-up, one profiler
session over the window, then the check against the reference) and
reduces the session twice: with ``bench/trace.py``, for the cell's
per-layer metrics as ``bench/run.py`` computes them, and with
``bench/program_trace.py``, for the metrics that read the program's own
spans and device scopes (``program_trace.METRICS``), scope times, span
counts and idle gaps put down to the program's spans. ``--config`` and
``--traffic`` run a mix that ``BENCHMARK.json`` does not list.

For the longest idle gaps it lists what each host thread had open at the
gap's middle: the benchmark's and the program's spans, and with
``--python-tracer`` (the profiler's Python tracer, which slows the host)
the Python calls too. It prints one JSON object as the last line of
standard output and, with ``--out``, writes it to that file. Like
``bench/run.py`` it needs a TPU, and exits 2 without one.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as bench_run  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".cache", "bench-trace-program")
GAPS = 5  # longest idle gaps to describe
OPEN_NAMES = 8  # innermost open events listed per thread and gap


def traced_run(cell, seed: int, seconds: float, devices, python_tracer=False) -> dict:
    """One traced run of ``cell``; ``devices`` is ``None`` on the CPU (tests),
    where the trace holds no device operations."""
    import jax

    from bench import program_trace, spec
    from bench import trace as tr

    kind = devices[0].device_kind if devices else "cpu"
    peaks = spec.load_peaks(kind) if devices else None
    span = min(seconds, cell.traffic["trace_seconds"])
    driver = importlib.import_module(f"bench.kinds.{cell.traffic['kind']}").Driver(
        cell, seed, span
    )
    started = bench_run.process_age()
    driver.setup()
    setup_s = bench_run.process_age()
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 1 if python_tracer else 0
    with jax.profiler.trace(TRACE_DIR, profiler_options=opts):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            driver.window(span)

    t0 = time.perf_counter()
    path = tr.find_xspace(TRACE_DIR)
    reduced = tr.reduce(*tr.read_xspace(path))
    program = program_trace.read(path)
    ctx = bench_run.Context(driver.observed(), reduced, peaks)
    ctx.program = program
    metrics = {}
    new = [n for m in cell.end_to_end for n in program_trace.METRICS.get(m["name"], ())]
    for name in [m["name"] for m in cell.per_layer] + new:
        value = bench_run.load_reader(name)(ctx)
        if value is not None:
            metrics[name] = value
    units = ctx.observed.units
    result = {
        "workload": cell.name,
        "seed": seed,
        "device": {"kind": kind, "busy_s": reduced.busy_s,
                   "window_s": reduced.window_s},
        "units": units,
        # over the traced window: what tracing costs, beside an untraced run
        "end_to_end": driver.end_to_end(),
        "metrics": metrics,
        "scopes_ms": {
            s: 1e3 * program.scope_seconds(s) / max(units, 1)
            for s in ("support_gather", "fold", "mask")
        },
        "spans": span_summary(program),
        "bench_spans": bench_span_summary(program),
        "breakdown": {**reduced.breakdown(), "idle_gaps": program.idle_gaps()},
        "gaps": describe_gaps(path, program, python_tracer),
        "info": {"age_at_setup_start_s": started, "setup_s": setup_s,
                 **(driver.info() if hasattr(driver, "info") else {})},
        "reduce_s": time.perf_counter() - t0,
    }
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    checked = driver.check()
    result["correct"] = checked.correct
    result["checks"] = {
        k: {"value": v, "limit": lim} for k, (v, lim) in checked.numbers.items()
    }
    return result


def span_summary(program) -> dict:
    """Per program span name: count and summed seconds in the window, and
    the stats of its last span."""
    out: dict = {}
    for s in program.spans:
        row = out.setdefault(s.name, {"count": 0})
        row["count"] += 1
        row["last"] = s.stats
    for name, row in out.items():
        row["seconds"] = program.span_seconds((name,))
    return out


def bench_span_summary(program) -> dict:
    """Per benchmark span name (``bench/trace.py``'s ``HOST_SPANS``) inside
    the window: count, summed seconds and the longest, in ms."""
    from bench import trace as tr

    w0, w1 = program.window
    out: dict = {}
    for name, s, e in program.bench_spans:
        if name in tr.HOST_SPANS and e > w0 and s < w1:
            row = out.setdefault(name, {"count": 0, "seconds": 0.0, "max_ms": 0.0})
            row["count"] += 1
            row["seconds"] += (e - s) * 1e-9
            row["max_ms"] = max(row["max_ms"], (e - s) * 1e-6)
    return out


def describe_gaps(path: str, program, python_tracer: bool) -> list[dict]:
    """For each of the longest idle gaps, the host events open at its middle,
    per thread, innermost last. Without the Python tracer these are the
    benchmark's and the program's spans."""
    from jax.profiler import ProfileData

    from bench import program_trace
    from bench import trace as tr

    gaps = program.longest_gaps(GAPS)
    if not gaps:
        return []
    mids = [(s + e) // 2 for s, e in gaps]
    names = set(tr.HOST_SPANS) | {tr.WINDOW}
    open_at: list[dict] = [{} for _ in gaps]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"  # Python threads share one line name
            for ev in line.events:
                if not (python_tracer or ev.name in names
                        or ev.name.startswith(program_trace.PROGRAM_PREFIXES)):
                    continue
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                for g, mid in enumerate(mids):
                    if s <= mid < e:
                        open_at[g].setdefault(thread, []).append((s, ev.name))
    w0 = program.window[0]
    return [
        {"start_ms": (s - w0) * 1e-6, "ms": (e - s) * 1e-6,
         "threads": {t: [n for _, n in sorted(evs)][-OPEN_NAMES:]
                     for t, evs in open_at[g].items()}}
        for g, (s, e) in enumerate(gaps)
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--python-tracer", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import spec

    if args.workload:
        cell = spec.load_cell(args.workload)
    elif args.config and args.traffic:
        cell = spec.make_cell(args.config, args.traffic, args.traffic)
    else:
        ap.error("give --workload, or --config and --traffic")
    devices = bench_run.find_chips(cell.chips)
    if devices is None:
        return 2
    bench_run.configure_compile_cache()
    result = traced_run(cell, args.seed, args.seconds, devices, args.python_tracer)
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
