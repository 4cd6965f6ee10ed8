"""Compile-time observability: retrace registry, no-retrace contracts,
lower/compile spans with XLA memory analysis.

The repo's retrace discipline ("build once, query many" — DESIGN.md §6/§9)
is enforced through ONE public registry:

- :class:`CompileMonitor` (module singleton :data:`MONITOR`) holds the
  per-entry-point retrace :attr:`~CompileMonitor.counts`. Every jitted
  entry point calls :func:`mark` at trace time (a Python side effect runs
  only when jit re-traces, so the counter IS the compilation count).
- :func:`assert_no_retrace` is the budget contract: inside the context any
  watched entry point that re-traces fires every active
  :class:`~repro.obs.recorder.FlightRecorder` (reason
  ``compile.retrace.<name>``) and raises :class:`RetraceError` — at mark
  time, so the violating call is still on the stack. Hot-path groups are
  registered by name (:func:`register_entry_points`): ``"serving.query"``
  and ``"serving.mutable"``.
- :meth:`CompileMonitor.lower_and_compile` is the AOT seam: times
  ``fn.lower(...)`` / ``.compile()`` under a ``compile/<name>`` span
  (PR-8 ``Tracer``), captures ``compiled.memory_analysis()`` argument/
  output/temp bytes into a :class:`CompileRecord`, and returns
  ``(compiled, record)`` — the workhorse of :mod:`repro.obs.audit`.
- :func:`capture_calls` / :func:`offer_capture` let host-staged call sites
  (the serving/mutable inners, whose worklist arguments are built host-
  side) hand one real ``(fn, args, kwargs)`` triple to the audit, which
  can then lower the exact program the hot path runs.

Guard discipline matches the rest of ``obs``: counting is always on (one
``Counter`` increment per *compilation*, not per call); contracts, spans,
metrics and recorder notes cost nothing unless their sink is active.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Iterator, Optional

from repro.obs import metrics, recorder, trace


class RetraceError(RuntimeError):
    """An entry point re-traced under an active no-retrace contract."""


@dataclasses.dataclass
class CompileRecord:
    """One measured lower+compile of a jitted entry point."""

    name: str
    t_lower_s: float
    t_compile_s: float
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    code_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        """Peak live-buffer footprint: arguments + outputs + temporaries."""
        return self.argument_bytes + self.output_bytes + self.temp_bytes

    def as_dict(self) -> dict:
        return {
            "name": self.name,
            "t_lower_s": self.t_lower_s,
            "t_compile_s": self.t_compile_s,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "code_bytes": self.code_bytes,
            "total_bytes": self.total_bytes,
        }


@dataclasses.dataclass
class CapturedCall:
    """One jitted call site offered to :func:`capture_calls`."""

    name: str
    fn: object
    args: tuple
    kwargs: dict


class _NoRetraceContract:
    """Snapshot-on-enter budget: watched counters must not move."""

    __slots__ = ("monitor", "names", "baseline", "watch_all", "violated")

    def __init__(self, monitor: "CompileMonitor", names: tuple):
        self.monitor = monitor
        self.names = names
        self.watch_all = not names
        self.baseline: dict = {}
        self.violated: set = set()

    def __enter__(self) -> "_NoRetraceContract":
        counts = self.monitor.counts
        watched = self.names if self.names else tuple(counts)
        self.baseline = {n: counts[n] for n in watched}
        self.monitor._contracts.append(self)
        return self

    def __exit__(self, exc_type, *exc) -> None:
        stack = self.monitor._contracts
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        if exc_type is not None:
            return  # already failing (possibly with our own RetraceError)
        # Belt-and-braces: catch direct `counts[x] += 1` bumps that
        # bypassed mark(). Names whose mark-time violation was already
        # raised (and possibly caught by the caller) are not re-raised here.
        counts = self.monitor.counts
        for n in (counts if self.watch_all else self.names):
            if n not in self.violated and counts[n] > self.baseline.get(n, 0):
                self.violated.add(n)
                self.monitor._violate(n, self.baseline.get(n, 0))

    def check(self, name: str) -> None:
        if not self.watch_all and name not in self.names:
            return
        if name in self.violated:
            return
        allowed = self.baseline.get(name, 0)
        if self.monitor.counts[name] > allowed:
            self.violated.add(name)
            self.monitor._violate(name, allowed)


class CompileMonitor:
    """Public registry of retrace counts, contracts, and compile records."""

    def __init__(self) -> None:
        # Per-entry-point compilation counts.
        self.counts: collections.Counter = collections.Counter()
        self.records: list[CompileRecord] = []
        self.groups: dict[str, tuple[str, ...]] = {}
        self._contracts: list[_NoRetraceContract] = []

    # -- retrace registry ----------------------------------------------------

    def mark(self, name: str) -> None:
        """Count one (re)trace of ``name``; called at trace time only."""
        self.counts[name] += 1
        if metrics.enabled():
            metrics.incr(f"compile.traces.{name}")
        if recorder.enabled():
            recorder.note("compile", name, count=self.counts[name])
        for c in reversed(self._contracts):
            c.check(name)

    def snapshot(self) -> dict:
        """Plain dict copy of the current counts (the public read API)."""
        return dict(self.counts)

    def register_entry_points(self, group: str, *names: str) -> None:
        """Declare a named hot-path group for :meth:`assert_no_retrace`."""
        self.groups[group] = tuple(names)

    def _resolve(self, names: tuple) -> tuple:
        out: list[str] = []
        for n in names:
            out.extend(self.groups.get(n, (n,)))
        return tuple(dict.fromkeys(out))

    def assert_no_retrace(self, *names: str) -> _NoRetraceContract:
        """Context manager: watched entry points must not re-trace inside.

        ``names`` are counter names and/or registered group names
        (``"serving.query"``, ``"serving.mutable"``); with no names, EVERY
        entry point is watched. A violation fires the flight recorder
        (reason ``compile.retrace.<name>``) and raises
        :class:`RetraceError` at the re-tracing call.
        """
        return _NoRetraceContract(self, self._resolve(names))

    def _violate(self, name: str, allowed: int) -> None:
        count = self.counts[name]
        if metrics.enabled():
            metrics.incr("compile.retrace_violations")
        recorder.trigger(
            f"compile.retrace.{name}",
            entry_point=name, count=count, allowed=allowed,
        )
        raise RetraceError(
            f"entry point '{name}' re-traced under a no-retrace contract "
            f"(compilations {count} > budget {allowed}): a traced-shape or "
            "static-argument change leaked into the hot path (see the "
            "flight-record dump for the lead-up)"
        )

    # -- AOT lower/compile ---------------------------------------------------

    def lower_and_compile(self, fn, *args, name: Optional[str] = None,
                          **kwargs):
        """``fn.lower(*args, **kwargs).compile()`` with full accounting.

        Emits a ``compile/<name>`` span carrying lower/compile wall times,
        captures ``memory_analysis()`` bytes (zeros where the backend
        offers none), appends a :class:`CompileRecord`, and returns
        ``(compiled, record)``.
        """
        label = name or getattr(fn, "__name__", None) or repr(fn)
        with trace.span(f"compile/{label}"):
            t0 = time.perf_counter()
            lowered = fn.lower(*args, **kwargs)
            t_lower = time.perf_counter() - t0
            t0 = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0
            trace.annotate(t_lower_s=t_lower, t_compile_s=t_compile)
        rec = CompileRecord(
            name=label, t_lower_s=t_lower, t_compile_s=t_compile,
            **_memory_bytes(compiled),
        )
        self.records.append(rec)
        if metrics.enabled():
            metrics.observe("compile.lower_s", t_lower)
            metrics.observe("compile.compile_s", t_compile)
        if recorder.enabled():
            recorder.note(
                "compile.aot", label,
                t_compile_s=t_compile, total_bytes=rec.total_bytes,
            )
        return compiled, rec

    def reset(self) -> None:
        """Drop counts and records (test isolation only — the serving
        no-retrace tests rely on counts persisting across calls)."""
        self.counts.clear()
        self.records.clear()


def _memory_bytes(compiled) -> dict:
    """``memory_analysis()`` fields, zeros when the backend lacks them."""
    out = {
        "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
        "code_bytes": 0,
    }
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return out
    if mem is None:
        return out
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("code_bytes", "generated_code_size_in_bytes"),
    ):
        try:
            out[key] = int(getattr(mem, attr, 0) or 0)
        except Exception:
            pass
    return out


# ---------------------------------------------------------------------------
# Module singleton + functional API
# ---------------------------------------------------------------------------

MONITOR = CompileMonitor()


def mark(name: str) -> None:
    """Count one (re)trace of ``name`` on the module :data:`MONITOR`."""
    MONITOR.mark(name)


def snapshot() -> dict:
    return MONITOR.snapshot()


def register_entry_points(group: str, *names: str) -> None:
    MONITOR.register_entry_points(group, *names)


def entry_points(group: str) -> tuple[str, ...]:
    """The registered counter names of a hot-path group."""
    return MONITOR.groups.get(group, ())


def assert_no_retrace(*names: str) -> _NoRetraceContract:
    return MONITOR.assert_no_retrace(*names)


def lower_and_compile(fn, *args, name: Optional[str] = None, **kwargs):
    return MONITOR.lower_and_compile(fn, *args, name=name, **kwargs)


# ---------------------------------------------------------------------------
# Call-site capture (audit seam)
# ---------------------------------------------------------------------------

_CAPTURE: Optional[dict] = None


@contextlib.contextmanager
def capture_calls() -> Iterator[dict]:
    """Collect ``offer_capture``'d call sites into the yielded dict.

    The first offer per name wins (the audit wants one representative
    call, not every batch). Nests by shadowing: the inner context sees a
    fresh dict, the outer resumes on exit.
    """
    global _CAPTURE
    prev, _CAPTURE = _CAPTURE, {}
    try:
        yield _CAPTURE
    finally:
        _CAPTURE = prev


def offer_capture(name: str, fn, *args, **kwargs) -> None:
    """Record a jitted call site for later AOT lowering (no-op unless a
    :func:`capture_calls` context is active — one ``is None`` check)."""
    if _CAPTURE is not None and name not in _CAPTURE:
        _CAPTURE[name] = CapturedCall(name, fn, args, dict(kwargs))
