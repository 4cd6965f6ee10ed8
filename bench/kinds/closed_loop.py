"""One closed-loop caller: batches of distinct queries through ``query_topk``.

The corpus is built into an index once in set-up. Each call scores one
batch with the rectangular Pallas kernel and waits for its ``Matches``
before the next call starts. A sample of the window's answered queries,
drawn from the seed, is checked against the float64 reference.
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import data, reference, work
from bench.kinds import Checked, Observed
from bench.memory import ProgramSpy
from bench.spy import CallSpy, query_block

KERNEL = "_rect_cand_kernel"
PROGRAM = "_rect_dense_inner"  # the jitted caller the Pallas call is named after
KERNEL_NAMES = (KERNEL, PROGRAM)
_REF_QUERIES = 32  # reference queries scored at a time


def build_corpus(cfg: dict, seed: int):
    """The raw corpus on the device and the program's index over it."""
    from repro.serving.index import build_index

    corpus = data.gaussian_rows(seed, data.STREAM_CORPUS, cfg["n"], cfg["m"])
    index = build_index(corpus)
    jax.block_until_ready(index)
    return corpus, index


def check_sample(cfg, corpus_raw, queries_raw, answers) -> tuple[reference.Verdict, int]:
    """Judge answered queries: ``queries_raw (q, m)`` raw rows and their
    answers ``(values, indices, counts)``; returns the verdict and the
    number of queries that failed."""
    values, indices, counts = answers
    ref = reference.DenseRetrieval(data.normalize_f64(corpus_raw))
    queries = data.normalize_f64(queries_raw)
    gaps, bads = [], []
    for lo in range(0, queries.shape[0], _REF_QUERIES):
        sl = slice(lo, lo + _REF_QUERIES)
        gap, bad, _ = reference.judge_rows(
            ref.scores(queries[sl]), values[sl], indices[sl], counts[sl],
            cfg["threshold"], cfg["k"], cfg["score_tol"],
        )
        gaps.append(gap)
        bads.append(bad)
    gap, bad = np.concatenate(gaps), np.concatenate(bads)
    total = reference.Verdict(
        float(gap.max(initial=0.0)), int(bad.sum()), int(gap.size), 0
    )
    failed = int(np.sum(bad | (gap > cfg["score_tol"])))
    return total, failed


class Driver:
    def __init__(self, cell, seed: int, seconds: float):
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.outs: list = []
        self.spans: list[tuple[float, float]] = []

    def _call(self, i: int):
        c = self.cfg
        return self._query_topk(
            self.index, self.batches[i % len(self.batches)], c["threshold"],
            c["k"], use_kernel=True,
        )

    def setup(self) -> None:
        import repro.serving.query as query

        c, tr = self.cfg, self.traffic
        self._query_topk = query.query_topk
        with TraceAnnotation("generate"):
            self.corpus, self.index = build_corpus(c, self.seed)
            self.index_block = self.index.block_rows
            pool = tr["batch"] * tr["pool_batches"]
            self.queries = data.gaussian_rows(
                self.seed, data.STREAM_QUERIES, pool, c["m"]
            )
            unit = data.normalize_f32(self.queries).reshape(
                tr["pool_batches"], tr["batch"], c["m"]
            )
            self.batches = [unit[i] for i in range(tr["pool_batches"])]
            jax.block_until_ready(self.batches)
        self.spy = CallSpy(query, "compact_rect_worklist").__enter__()
        self.blocks = CallSpy(query, "_query_mask", keep=query_block).__enter__()
        self.programs = [ProgramSpy(query, PROGRAM).__enter__()]
        with TraceAnnotation("warmup"):
            for i in range(tr["warmup_calls"]):
                jax.block_until_ready(self._call(i))

    def window(self, seconds: float) -> None:
        self.spy.clear()
        self.blocks.clear()
        t0 = time.perf_counter()
        while not self.outs or time.perf_counter() - t0 < seconds:
            with TraceAnnotation("score_call"):
                s = time.perf_counter()
                out = jax.block_until_ready(self._call(len(self.outs)))
                self.spans.append((s, time.perf_counter()))
            self.outs.append(out)
        for spy in (self.spy, self.blocks, *self.programs):
            spy.__exit__()

    def end_to_end(self) -> dict:
        queries = len(self.outs) * self.traffic["batch"]
        return {"query_qps": queries / (self.spans[-1][1] - self.spans[0][0])}

    def observed(self) -> Observed:
        c, tr = self.cfg, self.traffic
        # the tiles the program chose: its query block and the index's rows
        c_rows = work.block_rows(c["n"], self.index_block)
        flops = nbytes = 0.0
        live = total = 0
        for wl, block in zip(self.spy.kept, self.blocks.kept):
            q_rows = work.block_rows(tr["batch"], block)
            if wl is not None:
                f, b = work.rect_dense(wl, q_rows, c_rows, c["m"], c["k"])
                flops, nbytes = flops + f, nbytes + b
                live += wl.shape[1]
            total += len(q_rows) * len(c_rows)
        return Observed(
            units=len(self.outs), unit="batch",
            work={KERNEL: (flops, nbytes)}, names={KERNEL: KERNEL_NAMES},
            live_tiles=live, total_tiles=total,
        )

    def check(self) -> Checked:
        c, tr = self.cfg, self.traffic
        B, nb = tr["batch"], len(self.batches)
        answered = len(self.outs) * B
        rng = data.numpy_rng(self.seed, data.STREAM_SAMPLE)
        pick = np.sort(rng.choice(answered, min(tr["check_queries"], answered), replace=False))
        calls, rows = pick // B, pick % B
        host = [jax.tree.map(np.asarray, m) for m in self.outs]
        answers = tuple(
            np.stack([getattr(host[ci], f)[r] for ci, r in zip(calls, rows)])
            for f in ("values", "indices", "counts")
        )
        query_ids = (calls % nb) * B + rows
        queries_raw = np.asarray(self.queries)[query_ids]
        corpus_raw = np.asarray(self.corpus)
        self.outs, self.batches = [], []
        del self.index, self.corpus, self.queries
        with TraceAnnotation("check"):
            v, failed = check_sample(c, corpus_raw, queries_raw, answers)
        self.checked_inputs = (corpus_raw, queries_raw)
        return Checked(
            attempted=answered, failed=failed,
            numbers={"value_gap": (v.value_gap, c["score_tol"]), "bad_rows": (v.bad_rows, 0)},
        )
