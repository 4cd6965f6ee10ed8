"""Run one cell of BENCHMARK.json once, on the chip, and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Each run is a fresh process. It makes the cell's inputs from ``--seed``,
warms up every shape of the window (set-up, reported as ``setup_s``),
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints one JSON object as the last line of standard
output. With ``--trace 0`` its metrics are the cell's end-to-end metrics;
with ``--trace 1`` a profiler session covers the window (at most the
traffic's ``trace_seconds``) and its metrics are the cell's per-layer
metrics, read from the trace and the program's counters.

Without the program beside it, without a TPU, or with fewer chips than the
cell asks for, it exits with code 2 before any phase and prints no result.
JAX's persistent compilation cache lives in ``.cache/jax`` inside the
checkout.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

import psutil

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "bench-trace")


def process_age() -> float:
    """Seconds since this process started."""
    return time.time() - psutil.Process().create_time()


def configure_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileLog:
    """Compilations, persistent-cache hits and misses, per phase of a run.

    A window with a compilation in it measures the compiler: the counts of
    the ``window`` phase should be zero, and a second run in a checkout
    should find every set-up program in the cache.
    """

    def __init__(self):
        import jax

        self.phase = "setup"
        self.counts: dict[str, float] = {}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _add(self, key: str, n: float = 1) -> None:
        key = f"{self.phase}.{key}"
        self.counts[key] = self.counts.get(key, 0) + n

    def _event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._add("cache_hits")
        elif event == "/jax/compilation_cache/cache_misses":
            self._add("cache_misses")

    def _duration(self, event: str, duration: float, **kwargs) -> None:
        name = self._DURATIONS.get(event)
        if name is not None:
            self._add(name)
            self._add(name + "_s", duration)

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
        # compiles, or loads from the persistent cache
        "/jax/core/compile/backend_compile_duration": "compiles",
    }


def find_chips(chips: int):
    """The devices to run on; ``None`` when there is no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, found {devices[0].platform}", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: needs {chips} chips, found {len(devices)}", file=sys.stderr)
        return None
    return devices[:chips]


def load_reader(metric: str):
    """``read(obs)`` of ``metrics/<metric>.py``, else of ``metrics/<prefix>.py``
    where ``<prefix>`` is the name before its first dot."""
    here = os.path.join(ROOT, "bench", "metrics")
    for stem in (metric, metric.split(".")[0]):
        path = os.path.join(here, stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def run_cell(cell, seed: int, seconds: float, trace: bool, devices, *, log=print) -> dict:
    """One run of ``cell``; returns the result object (without printing it)."""
    import jax

    from bench import spec
    from bench import trace as tr
    from bench.kinds import Observed
    from bench.memory import peak_bytes

    kind = devices[0].device_kind if devices else "cpu"
    peaks = spec.load_peaks(kind) if devices else None
    span = min(seconds, cell.traffic["trace_seconds"]) if trace else seconds
    driver_mod = importlib.import_module(f"bench.kinds.{cell.traffic['kind']}")
    driver = driver_mod.Driver(cell, seed, span)
    compiles = CompileLog()
    started = process_age()
    driver.setup()
    setup_s = process_age()
    compiles.phase = "window"

    reduced = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(TRACE_DIR, profiler_options=opts):
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                driver.window(span)
    else:
        driver.window(span)

    device = {"platform": devices[0].platform if devices else "cpu",
              "kind": kind, "count": len(devices) if devices else 0}
    compiles.phase = "memory"
    memory = (
        peak_bytes(devices, getattr(driver, "programs", [])) if devices
        else {"memory_peak_bytes": 0}
    )
    device["memory_peak_bytes"] = memory["memory_peak_bytes"]
    metrics: dict = {}
    breakdown = None
    if trace:
        t0 = time.perf_counter()
        reduced = tr.reduce(*tr.read_xspace(tr.find_xspace(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        log(f"bench: trace read in {time.perf_counter() - t0:.1f} s")
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        obs: Observed = driver.observed()
        ctx = Context(obs, reduced, peaks)
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = reduced.breakdown()
    else:
        values = dict(driver.end_to_end(), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    compiles.phase = "check"
    info = {"age_at_setup_start_s": started, "setup_s": setup_s, **memory,
            **compiles.counts}
    if hasattr(driver, "info"):
        info.update(driver.info())
    log("bench: " + json.dumps(info))

    checked = driver.check()
    result = {
        "correct": checked.correct,
        "attempted": checked.attempted,
        "failed": checked.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {
        name: {"value": v, "limit": lim} for name, (v, lim) in checked.numbers.items()
    }
    return result


class Context:
    """What a per-layer metric reader sees."""

    def __init__(self, observed, reduced, peaks):
        self.observed = observed
        self.trace = reduced
        self.peaks = peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec

    cell = spec.load_cell(args.workload)
    if importlib.util.find_spec("repro") is None:
        print("bench: the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2
    devices = find_chips(cell.chips)
    if devices is None:
        return 2
    configure_compile_cache()
    result = run_cell(
        cell, args.seed, args.seconds, bool(args.trace), devices,
        log=lambda s: print(s, file=sys.stderr, flush=True),
    )
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
