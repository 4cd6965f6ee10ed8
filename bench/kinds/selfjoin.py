"""Exact self-joins of one sparse corpus, run back to back.

Each join is ``apss_blocked(corpus, threshold, k, use_kernel=True)`` on a
``SparseCorpus``: the CSR worklist, the support gather, the CSR tile kernel
and the packet fold. Every join of the window is checked in full against
the float64 reference.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import data, reference, work
from bench.kinds import Checked, Observed
from bench.memory import ProgramSpy
from bench.spy import CallSpy, support_block

KERNEL = "_sparse_tile_kernel"
PROGRAM = "_sparse_compacted_inner"  # the jitted caller the Pallas call is named after
KERNEL_NAMES = (KERNEL, PROGRAM)
_REF_ROWS = 1024  # reference rows scored at a time


class Driver:
    def __init__(self, cell, seed: int, seconds: float):
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.outs: list = []
        self.times: list[float] = []
        self.records: list = []

    def _join(self):
        c = self.cfg
        return self._apss_blocked(self.corpus, c["threshold"], c["k"], use_kernel=True)

    def setup(self) -> None:
        import repro.kernels.apss_block.sparse as sparse_kernels
        from repro.core.apss import apss_blocked
        from repro.core.sparse import SparseCorpus

        c = self.cfg
        self._apss_blocked = apss_blocked
        with TraceAnnotation("generate"):
            self.host = data.sparse_zipf_csr(
                c["n"], c["m"], c["nnz"], c["zipf_alpha"], self.seed
            )
            idx, val, nnz = self.host
            self.corpus = SparseCorpus(
                jnp.asarray(idx), jnp.asarray(val), jnp.asarray(nnz), c["m"]
            )
            jax.block_until_ready((self.corpus.indices, self.corpus.values))
        self.spy = CallSpy(sparse_kernels, "compact_worklist").__enter__()
        self.blocks = CallSpy(
            sparse_kernels, "block_support_gather", keep=support_block
        ).__enter__()
        self.programs = [ProgramSpy(sparse_kernels, PROGRAM).__enter__()]
        with TraceAnnotation("warmup"):
            for _ in range(self.traffic["warmup_joins"]):
                jax.block_until_ready(self._join())

    def window(self, seconds: float) -> None:
        from repro.planner import telemetry

        self.spy.clear()
        self.blocks.clear()
        with telemetry.CommLog() as log:
            t0 = time.perf_counter()
            while not self.outs or time.perf_counter() - t0 < seconds:
                with TraceAnnotation("join"):
                    s = time.perf_counter()
                    out = jax.block_until_ready(self._join())
                    self.times.append(time.perf_counter() - s)
                self.outs.append(out)
        for spy in (self.spy, self.blocks, *self.programs):
            spy.__exit__()
        self.records = log.by_variant("blocked/sparse-kernel")

    def end_to_end(self) -> dict:
        return {"selfjoin_s": sum(self.times) / len(self.times)}

    def info(self) -> dict:
        return {"join_s": self.times}

    def observed(self) -> Observed:
        c = self.cfg
        idx, _, nnz = self.host
        flops = nbytes = 0.0
        if self.blocks.kept:
            # the row block the program tiled the corpus with
            block = self.blocks.kept[-1]
            rows = work.block_rows(c["n"], block)
            support, nonzeros = work.csr_blocks(idx, nnz, block)
        for wl in self.spy.kept:
            if wl is not None:
                f, b = work.sparse_selfjoin(wl, rows, support, nonzeros, c["k"])
                flops, nbytes = flops + f, nbytes + b
        return Observed(
            units=len(self.outs), unit="join",
            work={KERNEL: (flops, nbytes)}, names={KERNEL: KERNEL_NAMES},
            live_tiles=sum(r.live_tiles or 0 for r in self.records),
            total_tiles=sum(r.total_tiles or 0 for r in self.records),
        )

    def check(self) -> Checked:
        c = self.cfg
        answers = [tuple(np.asarray(a) for a in m) for m in self.outs]
        self.outs = []
        del self.corpus
        with TraceAnnotation("check"):
            verdicts = judge_joins(c, self.host, answers)
        tol = c["score_tol"]
        return Checked(
            attempted=len(answers),
            failed=sum(v.bad_rows > 0 or v.value_gap > tol for v in verdicts),
            numbers={
                "value_gap": (max(v.value_gap for v in verdicts), tol),
                "bad_rows": (sum(v.bad_rows for v in verdicts), 0),
            },
        )


def judge_joins(cfg: dict, host, answers) -> list[reference.Verdict]:
    """One verdict per join: ``answers`` are ``(values, indices, counts)``
    over every row of the corpus ``host = (indices, values, nnz)``."""
    ref = reference.SparseSelfJoin(*host, cfg["m"])
    verdicts = [reference.Verdict() for _ in answers]
    for lo in range(0, cfg["n"], _REF_ROWS):
        hi = min(lo + _REF_ROWS, cfg["n"])
        scores = ref.scores(lo, hi)
        for i, (values, indices, counts) in enumerate(answers):
            verdicts[i] = verdicts[i].add(reference.judge(
                scores, values[lo:hi], indices[lo:hi], counts[lo:hi],
                cfg["threshold"], cfg["k"], cfg["score_tol"],
            ))
    return verdicts
