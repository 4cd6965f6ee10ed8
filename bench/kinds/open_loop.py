"""Open-loop Poisson arrivals of single queries into the continuous server.

Single distinct queries arrive at a fixed rate (every seed offers the same
set of inter-arrival gaps, in its own order) and go into
``ContinuousRetrievalServer``; the queries are distinct, so its LRU cache
never answers. Each request's latency runs from the time it was due to
the time its batch latched its result, so a late generator or a stalled
server shows in the tail. A request that is shed, degraded to a lower
tier, retried, stale, answered from the cache or never answered counts as
failed (``not_served``; ``setbacks`` is the server's own tally of these
events); a sample of the answered ones, drawn from the seed, is checked
against the float64 reference.
"""

from __future__ import annotations

import math
import threading
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import data, work
from bench.kinds import Checked, Observed
from bench.kinds.closed_loop import KERNEL, KERNEL_NAMES, PROGRAM, build_corpus, check_sample
from bench.memory import ProgramSpy
from bench.spy import CallSpy, query_block


def stamped_server(base):
    """``base`` (the program's continuous server) with the benchmark's
    stamps: when each request latched, and each scoring call's batch size
    and worklist. Behaviour is the base class's."""

    class Stamped(base):
        def __init__(self, index, *, spy, blocks, **kwargs):
            self.done_at: dict[int, float] = {}
            self.off_kernel: set[int] = set()  # answered by a lower tier
            # (real queries, query block, worklist) per scoring call
            self.calls: list = []
            self._spy = spy
            self._blocks = blocks
            self._sizes = threading.local()
            super().__init__(index, **kwargs)

        def _take_batch(self):
            taken = super()._take_batch()
            if taken is not None:
                self._sizes.last = len(taken[0])
            return taken

        def _score_batch(self, Qj):
            with TraceAnnotation("score_call"):
                out = super()._score_batch(Qj)
            self.calls.append((
                self._sizes.last, self._blocks.last_in_thread(),
                self._spy.last_in_thread(),
            ))
            return out

        def _latch_batch(self, batch, m, tier, seq):
            with TraceAnnotation("latch"):
                super()._latch_batch(batch, m, tier, seq)
            now = time.perf_counter()
            for entry in batch:
                self.done_at[entry[0]] = now
                if tier != "kernel":
                    self.off_kernel.add(entry[0])

    return Stamped


class Driver:
    def __init__(self, cell, seed: int, seconds: float):
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.count = max(1, math.ceil(self.traffic["rate_qps"] * seconds))
        self.latency: np.ndarray | None = None

    def setup(self) -> None:
        import repro.serving.query as query
        from repro.serving.server import ContinuousRetrievalServer

        c, tr = self.cfg, self.traffic
        warm = tr["warmup_batches"] * tr["max_batch"] + 1
        with TraceAnnotation("generate"):
            self.corpus, index = build_corpus(c, self.seed)
            self.queries = np.asarray(data.gaussian_rows(
                self.seed, data.STREAM_QUERIES, self.count + warm, c["m"]
            ))
            gaps = data.poisson_gaps(self.count, tr["rate_qps"], self.seed)
            self.due = np.cumsum(gaps)
        self.index_block = index.block_rows
        self.spy = CallSpy(query, "compact_rect_worklist").__enter__()
        self.blocks = CallSpy(query, "_query_mask", keep=query_block).__enter__()
        self.programs = [ProgramSpy(query, PROGRAM).__enter__()]
        self.server = stamped_server(ContinuousRetrievalServer)(
            index, spy=self.spy, blocks=self.blocks, threshold=c["threshold"], k=c["k"],
            max_batch=tr["max_batch"], use_kernel=True,
        )
        with TraceAnnotation("warmup"):
            # Full batches, then one single query: the window's shapes.
            self.server.serve(list(self.queries[self.count:]))
        self.server.calls.clear()

    def window(self, seconds: float) -> None:
        from repro.obs import metrics

        srv, n = self.server, self.count
        rids = np.empty(n, np.int64)
        late = np.empty(n)
        with metrics.MetricsRegistry() as reg:
            t0 = time.perf_counter()
            for i in range(n):
                due = t0 + self.due[i]
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                with TraceAnnotation("submit"):
                    rids[i] = srv.submit(self.queries[i])
                late[i] = time.perf_counter() - due
            close = t0 + self.due[-1]
            self.results = []
            for rid in rids:
                remaining = close + self.traffic["drain_s"] - time.perf_counter()
                try:
                    self.results.append(srv.result(int(rid), timeout_s=max(remaining, 1e-3)))
                except TimeoutError:
                    self.results.append(None)
            srv.close()
        for spy in (self.spy, self.blocks, *self.programs):
            spy.__exit__()
        self.rids = rids
        end = close + self.traffic["drain_s"]
        done = np.array([srv.done_at.get(int(r), end) for r in rids])
        self.latency = done - (t0 + self.due)
        self.lateness = late
        occupancy = reg.histogram("serving.batch_occupancy")
        self.batch_fill = occupancy.total / occupancy.count if occupancy else None

    def end_to_end(self) -> dict:
        return {"query_p99_ms": float(np.percentile(self.latency, 99)) * 1e3}

    def observed(self) -> Observed:
        c, tr = self.cfg, self.traffic
        c_rows = work.block_rows(c["n"], self.index_block)
        flops = nbytes = 0.0
        live = total = 0
        for size, block, wl in self.server.calls:
            q_rows = work.block_rows(size, block)
            if wl is not None:
                f, b = work.rect_dense(wl, q_rows, c_rows, c["m"], c["k"])
                flops, nbytes = flops + f, nbytes + b
                live += wl.shape[1]
            total += len(q_rows) * len(c_rows)
        return Observed(
            units=len(self.server.calls), unit="batch",
            work={KERNEL: (flops, nbytes)}, names={KERNEL: KERNEL_NAMES},
            live_tiles=live, total_tiles=total,
            batch_fill=self.batch_fill,
        )

    def info(self) -> dict:
        return {
            "requests": self.count,
            "generator_late_p99_ms": float(np.percentile(self.lateness, 99)) * 1e3,
            "generator_late_max_ms": float(self.lateness.max()) * 1e3,
            "query_p50_ms": float(np.percentile(self.latency, 50)) * 1e3,
            "query_p95_ms": float(np.percentile(self.latency, 95)) * 1e3,
            "scoring_calls": len(self.server.calls),
        }

    def check(self) -> Checked:
        c, tr = self.cfg, self.traffic
        srv = self.server
        ok = [
            i for i, (rid, r) in enumerate(zip(self.rids, self.results))
            if r is not None and r.status == "ok" and not r.cached
            and int(rid) not in srv.off_kernel
        ]
        failed = len(self.results) - len(ok)
        st = srv.stats
        setbacks = st.shed + st.degraded + st.retries + st.stale + st.cache_hits
        rng = data.numpy_rng(self.seed, data.STREAM_SAMPLE)
        pick = np.sort(rng.choice(ok, min(tr["check_queries"], len(ok)), replace=False))
        answers = (
            np.stack([self.results[i].values for i in pick]),
            np.stack([self.results[i].indices for i in pick]),
            np.array([self.results[i].count for i in pick]),
        )
        corpus_raw = np.asarray(self.corpus)
        del self.corpus, self.server
        with TraceAnnotation("check"):
            v, wrong = check_sample(c, corpus_raw, self.queries[pick], answers)
        self.checked_inputs = (corpus_raw, self.queries[pick])
        return Checked(
            attempted=len(self.results), failed=failed + wrong,
            numbers={
                "value_gap": (v.value_gap, c["score_tol"]),
                "bad_rows": (v.bad_rows, 0),
                "not_served": (failed, 0),
                "setbacks": (setbacks, 0),
            },
        )
