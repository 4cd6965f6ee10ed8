"""The chip's peak memory over a run, program temporaries included.

The runtime's ``peak_bytes_in_use`` counts the buffers a program takes
and returns (its arguments and results) but not the temporaries it holds
while it runs: on a TPU v5e it read under 0.5 GB across a self-join whose
support gather writes a 5.35 GB slab. So the peak is read as that figure
plus the largest temporaries of any program the window ran, from the
compiled program's own ``memory_analysis()``.

:class:`ProgramSpy` records the shapes each call of a jitted program was
made with; after the window they are lowered and compiled again (a hit in
the persistent cache) to read the analysis.
"""

from __future__ import annotations

import jax

from bench.spy import CallSpy


def _abstract(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def signature(args, kwargs, out):
    """The call's arrays as shapes; static keywords as given."""
    return jax.tree.map(_abstract, args), jax.tree.map(_abstract, dict(kwargs))


class ProgramSpy(CallSpy):
    """Records the signature of every call of the jitted ``module.<name>``."""

    def __init__(self, module, name: str):
        super().__init__(module, name, keep=signature)

    def temp_bytes(self) -> int:
        """The largest temporaries of the programs the recorded calls ran."""
        seen, most = set(), 0
        for args, kwargs in self.kept:
            key = repr((args, sorted(kwargs.items())))
            if key in seen:
                continue
            seen.add(key)
            stats = self._real.lower(*args, **kwargs).compile().memory_analysis()
            if stats is not None:
                most = max(most, int(stats.temp_size_in_bytes))
        return most


def peak_bytes(devices, programs) -> dict:
    """``memory_peak_bytes`` of the fullest chip and the two readings it
    is made of."""
    in_use = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices),
        default=0,
    )
    temps = max((p.temp_bytes() for p in programs), default=0)
    return {"memory_peak_bytes": in_use + temps,
            "peak_bytes_in_use": in_use, "program_temp_bytes": temps}
