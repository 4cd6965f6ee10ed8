"""The program's own spans and device scopes, read from a profiler trace.

While a profiler session is on, the program (``repro.obs.trace``) writes
each of its spans as a ``jax.profiler.TraceAnnotation``: host spans named
``apss/...`` (the sparse self-join), ``query/...`` (``query_topk``) and
``serving/...`` (the server), with the counts it computed as the
annotation's metadata (``live``, ``total``, ``entries``, ``support``, ...).
Its jitted programs name their stages with ``jax.named_scope``
(``support_gather``, ``fold``, ``mask``), and the compiler keeps that path
in each device operation's ``tf_op`` stat.

:func:`read` takes both from a session's ``.xplane.pb``, beside what
``bench/trace.py`` reads from it:

- the program's host spans, with their stats and thread, through
  ``jax.profiler.ProfileData``;
- the device's operations with their ``tf_op`` path. ``ProfileData`` does
  not expose an operation's metadata stats, so a protobuf wire-format
  reader takes them from the device plane's ``event_metadata`` and the
  operations from its ``XLA Ops`` line.

A scope's time is the union of the intervals of the operations whose path
holds the scope as one of its components (a ``while`` event spans its
body's operations, and carries no ``tf_op`` itself), inside the window,
averaged over the devices. :meth:`ProgramTrace.idle_gaps` puts each idle
gap down to the innermost span open at its middle among the benchmark's
spans and the program's.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import trace as bench_trace

# The program's span families that the readers use.
PROGRAM_PREFIXES = ("apss/", "query/", "serving/")
# Host planning steps: worklist, support gather and upload (and the
# self-join's bounds). ``query/mask`` is left out: it waits on the device.
PLAN_SPANS = (
    "apss/bounds", "apss/worklist", "apss/support_gather", "apss/upload",
    "query/worklist",
)
WORKLIST_SPANS = ("apss/worklist", "query/worklist")
# The per-layer metrics that read this reduction, by the end-to-end metric
# each moves.
METRICS = {
    "selfjoin_s": (
        "host_plan_ms.selfjoin", "support_gather_ms.selfjoin", "fold_ms.selfjoin",
    ),
    "query_qps": ("host_plan_ms.batch", "fold_ms.batch", "worklist_fill.batch"),
}


@dataclasses.dataclass
class Span:
    name: str
    start: int   # ns
    end: int
    thread: str  # the host line the span was on
    stats: dict


@dataclasses.dataclass
class DeviceOps:
    """One device's operations: start/end in ns and their ``tf_op`` path."""

    start: np.ndarray
    end: np.ndarray
    path: np.ndarray       # path id per operation
    paths: list[str]       # path id -> ``tf_op`` ('' where there is none)


@dataclasses.dataclass
class ProgramTrace:
    window: tuple[int, int]  # the benchmark's window span, ns
    spans: list[Span]        # the program's spans that overlap the window
    bench_spans: list        # the benchmark's spans (name, start, end)
    devices: list[DeviceOps]

    def span_seconds(self, names) -> float:
        """Summed time of the spans named in ``names``, inside the window."""
        w0, w1 = self.window
        return 1e-9 * sum(
            max(0, min(s.end, w1) - max(s.start, w0))
            for s in self.spans if s.name in names
        )

    def stat_sum(self, names, key: str) -> float:
        """Sum of the stat ``key`` over the window's spans named in ``names``
        (spans that began inside the window)."""
        w0, w1 = self.window
        return float(sum(
            s.stats.get(key, 0) for s in self.spans
            if s.name in names and w0 <= s.start < w1
        ))

    def scope_seconds(self, scope: str) -> float:
        """Device time of the operations under ``jax.named_scope(scope)``:
        the union of their intervals inside the window, mean over devices."""
        if not self.devices:
            return 0.0
        w0, w1 = self.window
        total = 0.0
        for ops in self.devices:
            hit = np.array([scope in p.split("/") for p in ops.paths], bool)
            keep = hit[ops.path] if ops.paths else np.zeros(0, bool)
            s = np.clip(ops.start[keep], w0, w1)
            e = np.clip(ops.end[keep], w0, w1)
            us, ue = bench_trace._union(s[e > s], e[e > s])
            total += float(np.sum(ue - us)) * 1e-9
        return total / len(self.devices)

    def idle_gaps(self, top: int = 10) -> list[list]:
        """Idle seconds inside the window (mean over devices), each gap put
        down to the innermost span open at its middle among the benchmark's
        spans (``bench/trace.py``'s ``HOST_SPANS``) and the program's;
        ``no_benchmark_span`` where none is."""
        owners = [
            (n, s, e) for n, s, e in self.bench_spans if n in bench_trace.HOST_SPANS
        ] + [(s.name, s.start, s.end) for s in self.spans]
        idle: dict[str, float] = {}
        for gap_s, gap_e in self._gaps():
            owner = _innermost((gap_s + gap_e) // 2, owners)
            for name, secs in zip(owner, (gap_e - gap_s) * 1e-9 / len(self.devices)):
                idle[name] = idle.get(name, 0.0) + float(secs)
        return [[n, s] for n, s in sorted(idle.items(), key=lambda kv: -kv[1])[:top]]

    def longest_gaps(self, top: int = 5) -> list[tuple[int, int]]:
        """The ``top`` longest idle gaps of the first device, ``(start, end)``."""
        if not self.devices:
            return []
        gap_s, gap_e = self._gaps()[0]
        order = np.argsort(gap_s - gap_e, kind="stable")[:top]
        return [(int(gap_s[i]), int(gap_e[i])) for i in order]

    def _gaps(self) -> list[tuple[np.ndarray, np.ndarray]]:
        w0, w1 = self.window
        out = []
        for ops in self.devices:
            s = np.clip(ops.start, w0, w1)
            e = np.clip(ops.end, w0, w1)
            us, ue = bench_trace._union(s[e > s], e[e > s])
            gap_s = np.concatenate([[w0], ue])
            gap_e = np.concatenate([us, [w1]])
            real = gap_e > gap_s
            out.append((gap_s[real], gap_e[real]))
        return out


def _innermost(mids: np.ndarray, owners) -> list[str]:
    """Per point, the name of the shortest span holding it."""
    name = ["no_benchmark_span"] * mids.size
    width = np.full(mids.size, np.iinfo(np.int64).max, np.int64)
    for n, s, e in owners:
        inside = (mids >= s) & (mids < e) & ((e - s) < width)
        width[inside] = e - s
        for i in np.nonzero(inside)[0]:
            name[i] = n
    return name


def read(path: str) -> ProgramTrace:
    """The program's spans and the devices' operations in the trace at
    ``path``, around the benchmark's ``window`` span."""
    from jax.profiler import ProfileData

    wanted = set(bench_trace.HOST_SPANS) | {bench_trace.WINDOW}
    bench_spans, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if ev.name in wanted:
                    bench_spans.append((ev.name, s, e))
                elif ev.name.startswith(PROGRAM_PREFIXES):
                    spans.append(Span(ev.name, s, e, line.name, dict(ev.stats)))
    windows = [(s, e) for n, s, e in bench_spans if n == bench_trace.WINDOW]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    w0, w1 = min(s for s, _ in windows), max(e for _, e in windows)
    spans = [sp for sp in spans if sp.end > w0 and sp.start < w1]
    with open(path, "rb") as f:
        devices = device_ops(f.read())
    return ProgramTrace((w0, w1), spans, bench_spans, devices)


# --- protobuf wire format --------------------------------------------------
# XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4 (map),
# stat_metadata = 5 (map); XLine: name = 2, timestamp_ns = 3, events = 4;
# XEvent: metadata_id = 1, offset_ps = 2, duration_ps = 3;
# XEventMetadata: id = 1, stats = 5; XStatMetadata: id = 1, name = 2;
# XStat: metadata_id = 1, str_value = 5, ref_value = 7; map entry: key = 1,
# value = 2.


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` slice for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, sl) -> str:
    return buf[sl[0]:sl[1]].decode("utf-8", "replace")


def device_ops(buf: bytes) -> list[DeviceOps]:
    """Each TPU's ``XLA Ops`` line with every operation's ``tf_op`` path,
    from the bytes of an ``.xplane.pb``. Times as ``bench/trace.py`` reads
    them: whole nanoseconds, start and duration each rounded down."""
    out = []
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, lines, event_md, stat_md = "", [], [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                event_md.append(v)
            elif f == 5:
                key, val = _map_entry(buf, v)
                for g, w in _fields(buf, *val):
                    if g == 2:
                        stat_md[key] = _text(buf, w)
        if not bench_trace._DEVICE_PLANE.match(name):
            continue
        tf_op = next((k for k, n in stat_md.items() if n == "tf_op"), None)
        paths: dict[int, str] = {}
        for entry in event_md:
            key, val = _map_entry(buf, entry)
            paths[key] = _metadata_path(buf, val, tf_op, stat_md)
        out.append(_ops_line(buf, lines, paths))
    return out


def _map_entry(buf: bytes, sl) -> tuple[int, tuple[int, int]]:
    key, val = 0, (sl[0], sl[0])
    for f, v in _fields(buf, *sl):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _metadata_path(buf, sl, tf_op, stat_md) -> str:
    for f, stat in _fields(buf, *sl):
        if f != 5:
            continue
        sid, text = None, ""
        for g, w in _fields(buf, *stat):
            if g == 1:
                sid = w
            elif g == 5:
                text = _text(buf, w)
            elif g == 7:
                text = stat_md.get(w, "")
        if sid == tf_op and tf_op is not None:
            return text
    return ""


def _ops_line(buf, lines, paths: dict[int, str]) -> DeviceOps:
    start, dur, mid = [], [], []
    for sl in lines:
        name, ts, events = "", 0, []
        for f, v in _fields(buf, *sl):
            if f == 2:
                name = _text(buf, v)
            elif f == 3:
                ts = v
            elif f == 4:
                events.append(v)
        if name != bench_trace.OPS_LINE:
            continue
        for lo, hi in events:
            m = off = d = 0
            for f, v in _fields(buf, lo, hi):
                if f == 1:
                    m = v
                elif f == 2:
                    off = v
                elif f == 3:
                    d = v
                    break  # the fields come in order; stats follow
            mid.append(m)
            start.append(ts + off // 1000)
            dur.append(d // 1000)
    ids = {p: i for i, p in enumerate(sorted(set(paths.values()) | {""}))}
    path = np.array([ids[paths.get(m, "")] for m in mid], np.int64)
    s = np.array(start, np.int64)
    return DeviceOps(s, s + np.array(dur, np.int64), path, list(ids))

