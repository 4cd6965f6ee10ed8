"""Record what the program's own functions were called with, without changing them.

The live-tile worklist (``(2, T)`` live tile coordinates, before any
bucket padding) is the program's own count of live tiles, and the tiles'
sizes are the ones the program chose. The work counts in ``bench/work.py``
are computed from both, so a change that prunes more tiles counts less
work as well as taking less time, and a change of tiling is counted as
the tiling it runs.
"""

from __future__ import annotations

import threading


def result(args, kwargs, out):
    return out


class CallSpy:
    """Wraps ``module.<name>`` and keeps ``keep(args, kwargs, result)`` of
    every call."""

    def __init__(self, module, name: str, keep=result):
        self._module = module
        self._name = name
        self._real = getattr(module, name)
        self._keep = keep
        self._lock = threading.Lock()
        self._local = threading.local()
        self.kept: list = []

    def __enter__(self) -> "CallSpy":
        real, keep = self._real, self._keep

        def spy(*args, **kwargs):
            out = real(*args, **kwargs)
            got = keep(args, kwargs, out)
            with self._lock:
                self.kept.append(got)
            self._local.last = got
            return out

        setattr(self._module, self._name, spy)
        return self

    def __exit__(self, *exc) -> None:
        setattr(self._module, self._name, self._real)

    def clear(self) -> None:
        with self._lock:
            self.kept = []

    def last_in_thread(self):
        """What this thread's latest call kept."""
        return getattr(self._local, "last", None)


def query_block(args, kwargs, out) -> int:
    """Rows in a query block, from ``_query_mask(Qp, ...) -> (mask, ub)``:
    the padded batch's rows over the mask's query blocks."""
    return int(args[0].shape[0]) // int(out[0].shape[0])


def support_block(args, kwargs, out) -> int:
    """Rows in a corpus block, from ``block_support_gather -> (bdims, bx)``
    with ``bx`` of shape ``(blocks, rows, support)``."""
    return int(out[1].shape[1])
