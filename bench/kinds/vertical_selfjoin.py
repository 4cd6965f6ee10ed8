"""Exact self-joins of one sparse corpus over a 1-D mesh of chips, back to back.

Each join is ``apss(corpus, threshold, k, mesh, distribution="vertical")``
on a ``SparseCorpus``: the program deals the dimensions to the chips, pads
the rows, scores each query block's partial tile on every chip and
accumulates the partials over the mesh with collectives. The corpus is the
configuration's Zipf stand-in with quoted replies (``bench/replies.py``),
made from the seed and held on every chip of the mesh, where the split
reads it. Every
join of the window is checked in full against the float64 reference
(``selfjoin.judge_joins``, as the one-chip self-join is), once per
distinct answer.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from bench import data, replies, work_vertical
from bench.kinds import Checked, Observed, selfjoin

WORK = "vertical_partials"  # the partial tiles of every chip


@dataclasses.dataclass
class VerticalObserved(Observed):
    """What the window did, with the distribution's own counts."""

    shard_nnz: tuple = ()   # nonzeros each chip's shard holds
    blocks_pruned: int = 0  # query blocks scored at the Lemma-1 candidates
    blocks_exact: int = 0   # query blocks scored by the whole tile's all-reduce


class Driver(selfjoin.Driver):
    """The one-chip self-join's driver, with the mesh, the quoted-reply
    corpus and the distribution's counts."""

    def __init__(self, cell, seed: int, seconds: float):
        super().__init__(cell, seed, seconds)
        self.chips = cell.chips

    def _join(self):
        c = self.cfg
        return self._apss(
            self.corpus, c["threshold"], c["k"], self.mesh,
            distribution=c["distribution"], block_rows=c["block_rows"],
        )

    def setup(self) -> None:
        from repro.core.distributed import apss
        from repro.core.sparse import SparseCorpus

        c = self.cfg
        self._apss = apss
        self.programs = []
        self.mesh = Mesh(np.asarray(jax.devices()[: self.chips]), ("model",))
        with TraceAnnotation("generate"):
            base = data.sparse_zipf_csr(
                c["n"], c["m"], c["nnz"], c["zipf_alpha"], self.seed
            )
            self.host = replies.quoted_replies(
                *base, self.seed, share=c["reply_share"],
                quoted=tuple(c["quoted_share"]),
            )
            # held on every chip: the distribution deals it there
            everywhere = NamedSharding(self.mesh, PartitionSpec())
            self.corpus = SparseCorpus(
                *(jax.device_put(a, everywhere) for a in self.host), c["m"]
            )
            jax.block_until_ready((self.corpus.indices, self.corpus.values))
        with TraceAnnotation("warmup"):
            for _ in range(self.traffic["warmup_joins"]):
                jax.block_until_ready(self._join())

    def window(self, seconds: float) -> None:
        from repro.planner import telemetry

        with telemetry.CommLog() as log:
            t0 = time.perf_counter()
            while not self.outs or time.perf_counter() - t0 < seconds:
                with TraceAnnotation("join"):
                    s = time.perf_counter()
                    out = jax.block_until_ready(self._join())
                    self.times.append(time.perf_counter() - s)
                self.outs.append(out)
        self.records = [
            r.extra for r in log.records if r.variant.startswith("vertical/")
        ]

    def _routes(self) -> dict:
        """The program's counts: the last join's shard sizes, and the
        blocks of the window's joins that took each route."""
        return {
            "shard_nnz": tuple(self.records[-1].get("shard_nnz", ()))
            if self.records else (),
            "blocks_pruned": sum(r.get("blocks_pruned", 0) for r in self.records),
            "blocks_exact": sum(r.get("blocks_exact", 0) for r in self.records),
        }

    def info(self) -> dict:
        return {"join_s": self.times, **self._routes()}

    def observed(self) -> VerticalObserved:
        c = self.cfg
        routes = self._routes()
        flops, nbytes = work_vertical.partial_tiles(
            c["n"], c["nnz"], self.chips, c["k"]
        )
        joins = len(self.outs)
        return VerticalObserved(
            units=joins, unit="join",
            work={WORK: (joins * flops, joins * nbytes)},
            **routes,
        )

    def check(self) -> Checked:
        """Every join of the window against the reference. Joins whose
        answers are bit-identical share one verdict, so the reference
        judges each distinct answer once."""
        c = self.cfg
        answers = [tuple(np.asarray(a) for a in m) for m in self.outs]
        self.outs = []
        del self.corpus
        distinct: dict[bytes, int] = {}
        which = [
            distinct.setdefault(b"".join(a.tobytes() for a in ans), len(distinct))
            for ans in answers
        ]
        firsts = [answers[which.index(i)] for i in range(len(distinct))]
        with TraceAnnotation("check"):
            verdicts = selfjoin.judge_joins(c, self.host, firsts)
        verdicts = [verdicts[i] for i in which]
        tol = c["score_tol"]
        return Checked(
            attempted=len(answers),
            failed=sum(v.bad_rows > 0 or v.value_gap > tol for v in verdicts),
            numbers={
                "value_gap": (max(v.value_gap for v in verdicts), tol),
                "bad_rows": (sum(v.bad_rows for v in verdicts), 0),
            },
        )
