"""Readings that set a cell's limits: the program's, and the control's.

    python3 bench/control.py --workload <name> --seeds 1 2 ... \
        --control-seeds 1 2 3 --seconds 3

For each of ``--seeds`` it runs the cell as ``bench/run.py`` does (set-up,
a short window at the cell's own load, the check) and prints the numbers
the check compares: the lower readings. For each of ``--control-seeds`` it
then puts the control in the program's place, on the same inputs, and
prints the same numbers: the upper readings. The control is the reference
computed in three bfloat16 passes (``reference.dot_bf16x3``, the split
``Precision.HIGH`` makes on a TPU), the nearest precision below the
configuration's float32 at ``HIGHEST``. One process runs every seed, so
the program compiles once. Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import reference, spec  # noqa: E402
from bench.kinds import closed_loop, selfjoin  # noqa: E402
from bench.run import configure_compile_cache, find_chips  # noqa: E402


def control_numbers(cell, driver) -> dict:
    """The check's numbers with the control's answers in place of the
    program's, on the inputs ``driver`` was checked on."""
    c = cell.config
    if cell.traffic["kind"] == "selfjoin":
        answers = reference.control_selfjoin(
            *driver.host, c["m"], c["threshold"], c["k"]
        )
        (v,) = selfjoin.judge_joins(c, driver.host, [answers])
        return {"value_gap": v.value_gap, "bad_rows": v.bad_rows}
    corpus_raw, queries_raw = driver.checked_inputs
    answers = reference.control_retrieval(
        queries_raw, corpus_raw, c["threshold"], c["k"]
    )
    v, _ = closed_loop.check_sample(c, corpus_raw, queries_raw, answers)
    return {"value_gap": v.value_gap, "bad_rows": v.bad_rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import importlib

    cell = spec.load_cell(args.workload)
    if find_chips(cell.chips) is None:
        return 2
    configure_compile_cache()
    driver_mod = importlib.import_module(f"bench.kinds.{cell.traffic['kind']}")
    lower, upper = {}, {}
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        driver = driver_mod.Driver(cell, seed, args.seconds)
        driver.setup()
        driver.window(args.seconds)
        checked = driver.check()
        row = {"side": "program", "seed": seed, "correct": checked.correct,
               "attempted": checked.attempted, "failed": checked.failed,
               **{k: v for k, (v, _) in checked.numbers.items()}}
        print(json.dumps(row), flush=True)
        if seed in args.seeds:
            for k, (v, _) in checked.numbers.items():
                lower[k] = max(lower.get(k, v), v)
        if seed in args.control_seeds:
            nums = control_numbers(cell, driver)
            print(json.dumps({"side": "control", "seed": seed, **nums}), flush=True)
            for k, v in nums.items():
                upper[k] = min(upper.get(k, v), v)
        del driver
    print(json.dumps({"workload": args.workload, "lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
