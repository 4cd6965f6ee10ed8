"""Reduce a profiler trace to the per-layer metrics' raw numbers.

A run with ``--trace 1`` records one profiler session around its measured
window. The session's ``.xplane.pb`` holds, per TPU, a line of the
operations that ran on the device, and per host thread the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``window``, ``join``,
``score_call``, ``submit``, ...). :func:`reduce` turns these into

- the device's busy time: the union of its operations' intervals inside
  the ``window`` span, averaged over the devices;
- each kernel's time: the summed durations of the Pallas custom calls
  whose HLO text names the kernel or the jitted function that holds it;
- the operations that took most time, grouped by their HLO text and
  shown without layouts (a ``while`` loop's event spans the operations of
  its body, so those overlap);
- the idle gaps between operations, each put down to the innermost
  benchmark span that was open on the host at the gap's middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW = "window"
# Benchmark spans that idle time is put down to (innermost wins).
HOST_SPANS = ("generate", "warmup", "join", "score_call", "submit", "latch", "check")
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*index=\d+\*/")
LABEL_CHARS = 160
# A Pallas kernel runs as a custom call to Mosaic, named after the jitted
# function that holds it.
PALLAS_CALL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Ops:
    """One device's operations: start/end in ns, and a label id per op."""

    start: np.ndarray
    end: np.ndarray
    label: np.ndarray
    labels: list[str]  # label id -> the operation's HLO text


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                    # mean over devices
    devices: int
    op_seconds: dict[str, float]     # HLO text -> seconds (mean over devices)
    idle_by_span: dict[str, float]   # host span -> idle seconds (mean)

    def kernel_seconds(self, names) -> float:
        """Device seconds of the Pallas calls whose text holds any of
        ``names`` (a kernel's or its jitted caller's name; mean over
        devices)."""
        names = (names,) if isinstance(names, str) else tuple(names)
        return sum(
            s for g, s in self.op_seconds.items()
            if PALLAS_CALL in g and any(n in g for n in names)
        )

    def breakdown(self, top: int = 10) -> dict:
        short: dict[str, float] = {}
        for g, s in self.op_seconds.items():
            label = label_of(g)
            short[label] = short.get(label, 0.0) + s
        ops = sorted(short.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[g, s] for g, s in ops],
            "idle_gaps": [[g, s] for g, s in gaps],
        }


def find_xspace(log_dir: str) -> str:
    """The newest ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def label_of(text: str) -> str:
    """An operation's HLO text without layouts, cut to ``LABEL_CHARS``."""
    return " ".join(_LAYOUT.sub("", text).split())[:LABEL_CHARS]


def read_xspace(path: str) -> tuple[list[Ops], list[tuple[str, int, int]]]:
    """Device operations per TPU, and the benchmark's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: list[Ops] = []
    spans: list[tuple[str, int, int]] = []
    wanted = set(HOST_SPANS) | {WINDOW}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(_device_ops(plane))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return devices, spans


def _device_ops(plane) -> Ops:
    start, end, label = [], [], []
    ids: dict[str, int] = {}
    labels: list[str] = []
    for line in plane.lines:
        if line.name != OPS_LINE:
            continue
        for ev in line.events:
            i = ids.get(ev.name)
            if i is None:
                i = ids[ev.name] = len(labels)
                labels.append(ev.name)
            s = int(ev.start_ns)
            start.append(s)
            end.append(s + int(ev.duration_ns))
            label.append(i)
    return Ops(
        np.asarray(start, np.int64), np.asarray(end, np.int64),
        np.asarray(label, np.int64), labels,
    )


def _union(start: np.ndarray, end: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Disjoint, sorted intervals covering the union of ``[start, end)``."""
    if start.size == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.nonzero(new)[0]
    last = np.append(first[1:] - 1, s.size - 1)
    return s[first], reach[last]


def _attribute(gap_mid: np.ndarray, spans) -> np.ndarray:
    """Per gap, the innermost host span open at its middle (-1: none)."""
    owner = np.full(gap_mid.size, -1, np.int64)
    width = np.full(gap_mid.size, np.iinfo(np.int64).max, np.int64)
    order = np.argsort(gap_mid)
    mids = gap_mid[order]
    for sid, (name, s, e) in enumerate(spans):
        if name not in HOST_SPANS:
            continue
        lo, hi = np.searchsorted(mids, [s, e])
        if lo == hi:
            continue
        sel = order[lo:hi]
        inner = (e - s) < width[sel]
        owner[sel[inner]] = sid
        width[sel[inner]] = e - s
    return owner


def reduce(devices: list[Ops], spans) -> Reduced:
    """Busy, per-group and idle seconds inside the benchmark's ``window``."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    w0 = min(s for s, _ in windows)
    w1 = max(e for _, e in windows)
    nd = max(1, len(devices))
    busy = 0.0
    op_seconds: dict[str, float] = {}
    idle: dict[str, float] = {}
    for ops in devices:
        s = np.clip(ops.start, w0, w1)
        e = np.clip(ops.end, w0, w1)
        keep = e > s
        s, e, lab = s[keep], e[keep], ops.label[keep]
        per_label = np.bincount(lab, weights=(e - s), minlength=len(ops.labels))
        for i, g in enumerate(ops.labels):
            if per_label[i] > 0:
                op_seconds[g] = op_seconds.get(g, 0.0) + per_label[i] * 1e-9 / nd
        us, ue = _union(s, e)
        busy += float(np.sum(ue - us)) * 1e-9 / nd
        gap_s = np.concatenate([[w0], ue])
        gap_e = np.concatenate([us, [w1]])
        real = gap_e > gap_s
        gap_s, gap_e = gap_s[real], gap_e[real]
        owner = _attribute((gap_s + gap_e) // 2, spans)
        for sid in np.unique(owner):
            name = spans[sid][0] if sid >= 0 else "no_benchmark_span"
            secs = float(np.sum((gap_e - gap_s)[owner == sid])) * 1e-9 / nd
            idle[name] = idle.get(name, 0.0) + secs
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy,
        devices=len(devices),
        op_seconds=op_seconds,
        idle_by_span=idle,
    )
