"""Load a cell's description: ``BENCHMARK.json`` plus the files it names."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic mix."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]  # the cell's own end-to-end metrics
    per_layer: list[dict]   # the cell's own per-layer metrics


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``; raises ``KeyError`` if there is none."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in spec["per_layer"]
        if _applies(m, workload) and m["moves"] in moved
    ]
    return make_cell(
        root / cfg_entry["file"], w["traffic"], workload, int(w["chips"]),
        e2e, per_layer,
    )


def make_cell(
    config_file, traffic: str, name: str, chips: int = 1,
    end_to_end: list | None = None, per_layer: list | None = None,
) -> Cell:
    """A cell from a configuration file and the name of a traffic mix,
    listed in ``BENCHMARK.json`` or not (a sweep or a cell to come)."""
    config = json.loads(Path(config_file).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text())
    return Cell(name, chips, config, mix, end_to_end or [], per_layer or [])


def load_peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in bench/peaks.json"
        )
    return table["devices"][device_kind]
