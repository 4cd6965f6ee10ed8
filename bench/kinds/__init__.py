"""One driver per kind of traffic; a traffic file names its ``kind``.

Each module defines ``Driver(cell, seed, seconds)`` with

- ``setup()``: make the inputs from the seed and warm up every shape the
  window uses (this is ``setup_s``);
- ``window(seconds)``: the measured loop;
- ``end_to_end()``: the cell's end-to-end metrics over the window;
- ``observed()``: an :class:`Observed` for the per-layer metric readers;
- ``check()``: a :class:`Checked`, once the window has closed; it frees
  the program's device state before the reference runs.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Observed:
    """What the window did, in the program's own counts."""

    units: int = 0                 # joins or batches completed in the window
    unit: str = ""                 # "join" or "batch"
    work: dict = dataclasses.field(default_factory=dict)  # kernel -> (flops, bytes)
    # kernel -> names that find its Pallas call in a trace: the kernel's own
    # and that of the jitted function the call is named after
    names: dict = dataclasses.field(default_factory=dict)
    live_tiles: int = 0
    total_tiles: int = 0
    batch_fill: float | None = None  # mean share of a batch's slots in use


@dataclasses.dataclass
class Checked:
    """The comparison with the reference: each number beside its limit."""

    attempted: int
    failed: int
    numbers: dict  # name -> (value, limit); correct iff every value <= limit

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(v <= lim for v, lim in self.numbers.values())
