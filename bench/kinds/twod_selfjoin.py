"""Exact self-joins of one sparse corpus over a 2-D mesh of chips, back to back.

Each join is ``apss(corpus, threshold, k, mesh, distribution="2d")`` on a
``SparseCorpus`` over the configuration's ``mesh`` (rows over ``data``,
dimensions over ``model``): the program deals the dimensions to the mesh's
columns, pads the rows, rings each row shard's cell over the rows and, per
query block and ring step, accumulates the partial tiles over the columns
with collectives. Everything else is the vertical cell's: the quoted-reply
corpus made from the seed and held on every chip, the window, the counts of
the routes, and the check of every distinct answer against the float64
reference.
"""

from __future__ import annotations

from jax.sharding import Mesh

from bench.kinds import vertical_selfjoin


class Driver(vertical_selfjoin.Driver):
    """The vertical cell's driver over a 2-D mesh, reading the 2-D records."""

    @property
    def mesh(self) -> Mesh:
        return self._grid

    @mesh.setter
    def mesh(self, flat: Mesh) -> None:
        # the vertical driver lays the cell's chips out in one row
        shape = self.cfg["mesh"]
        self._grid = Mesh(flat.devices.reshape(tuple(shape.values())), tuple(shape))

    def setup(self) -> None:
        from repro.planner import telemetry

        # The window runs under telemetry, which plants the ring steps' host
        # ticks: a program of its own, so the warm-up runs under it too.
        with telemetry.CommLog():
            super().setup()

    def window(self, seconds: float) -> None:
        from repro.planner import telemetry

        with telemetry.CommLog() as log:
            super().window(seconds)
        self.records = [
            r.extra for r in log.records if r.variant.startswith("2d/")
        ]
