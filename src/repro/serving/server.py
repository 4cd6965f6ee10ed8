"""RetrievalServer: batched query-time APSS over a build-once index.

A slot/latch pattern, as in continuous-batch decode servers: requests join a
padded query batch at the next ``step()`` boundary, ONE jit'd
:func:`~repro.serving.query.query_topk` call serves the whole batch (the
batch is always padded to ``max_batch`` rows, so the compiled executable is
reused forever), and per-request results latch into their slots. A tiny
LRU cache keyed on the query-vector hash short-circuits repeat queries —
the classic head-of-zipf serving win — without touching the device.

For mesh-sharded indexes the underlying ``query_topk`` runs the per-shard
scoring path and merges partial top-k host-side; the server is agnostic.

Degraded-mode contract (DESIGN.md §8): under overload or scoring failure
the server prefers a *worse answer now* over a perfect answer too late —

- **admission control**: past ``max_pending`` queued requests, new submits
  are shed immediately (``status="shed"``, empty result);
- **deadlines**: requests whose deadline lapses before their batch is
  scored are shed at the step boundary; in-budget requests in the same
  batch still get exact results;
- **degradation ladder**: each scoring tier (Pallas kernel → XLA scan) is
  retried ``max_retries`` times with exponential backoff, then the server
  degrades to the next tier; when every tier fails, a stale LRU entry (one
  past ``ttl_s``, ineligible for fresh hits) still answers
  (``status="stale"``), and only cache misses fail.

Every event increments a counter here AND in the active telemetry log
(``planner.telemetry.incr``: ``serving.shed`` / ``serving.degraded`` /
``serving.retries`` / ``serving.stale``). Adversarial input is rejected at
``submit`` — non-numeric dtypes and non-finite (NaN/inf) queries raise
``ValueError``; all-zero queries under ``normalize=True`` are served (they
normalize to zero, match nothing, and return an empty result).
"""

from __future__ import annotations

import collections
import hashlib
import threading
import time
from typing import NamedTuple, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.apss import normalize_rows
from repro.obs import metrics, recorder, trace
from repro.planner import telemetry
from repro.serving.index import APSSIndex
from repro.serving.mutable import MutableAPSSIndex
from repro.serving.query import query_topk


class RetrievalResult(NamedTuple):
    """One request's top-k neighbors (host numpy; ready to serialize)."""

    values: np.ndarray   # (k,) f32 similarities, -inf padded
    indices: np.ndarray  # (k,) i32 corpus row ids, -1 padded
    count: int           # exact #corpus rows ≥ threshold (may exceed k)
    cached: bool         # served from the LRU cache
    status: str = "ok"   # "ok" | "shed" | "stale" | "failed"


class ServerStats(NamedTuple):
    requests: int
    steps: int
    cache_hits: int
    shed: int = 0        # admission-control + deadline rejections
    degraded: int = 0    # scoring-tier downgrades (kernel → XLA → stale)
    retries: int = 0     # same-tier retry attempts
    stale: int = 0       # answers served from an expired cache entry


class RetrievalServer:
    """Batched online retrieval over a prebuilt :class:`APSSIndex`.

    Args:
      index: built once via :func:`~repro.serving.index.build_index`, or a
        live :class:`~repro.serving.mutable.MutableAPSSIndex` — mutations
        bump its ``version``, which invalidates every cached answer
        (result indices are then global row ids, stable across
        compaction).
      threshold / k: fixed per server (one compiled executable).
      max_batch: padded batch width; requests beyond it wait for the next
        step boundary.
      normalize: L2-normalize incoming queries (the paper's ``||x|| = 1``
        contract; cache keys hash the raw bytes BEFORE normalization so
        clients need not normalize consistently).
      cache_size: LRU entries; 0 disables the cache.
      use_kernel: route tile scoring through the rectangular Pallas
        kernels (single-host indexes; TPU); on failure the server degrades
        to the XLA scan tier instead of erroring.
      deadline_s: default per-request deadline (None = no deadline);
        requests not scored within it are shed at the next step boundary.
      max_pending: admission budget — submits past this queue depth are
        shed immediately (None = unbounded).
      max_retries / backoff_s: per-tier retry policy around the jitted
        scoring call (exponential backoff starting at ``backoff_s``).
      ttl_s: cache freshness horizon. Entries older than this no longer
        satisfy submit-time hits but remain eligible for the stale-answer
        tier when every scoring tier is down (None = never stale).
      fault_plan: a ``robust.faults.FaultPlan`` for chaos testing — armed
        ``delay`` faults (scope ``"serving"``) stall the step like a slow
        shard; ``error`` faults (scope ``"serving.kernel"`` /
        ``"serving.xla"``) fail scoring tiers.
    """

    def __init__(
        self,
        index: APSSIndex,
        *,
        threshold: float,
        k: int = 32,
        max_batch: int = 8,
        normalize: bool = True,
        cache_size: int = 256,
        use_kernel: bool = False,
        block_q: Optional[int] = None,
        deadline_s: Optional[float] = None,
        max_pending: Optional[int] = None,
        max_retries: int = 1,
        backoff_s: float = 0.01,
        ttl_s: Optional[float] = None,
        fault_plan=None,
    ):
        self.index = index
        self.threshold = float(threshold)
        self.k = int(k)
        self.max_batch = int(max_batch)
        self.normalize = bool(normalize)
        self.use_kernel = bool(use_kernel)
        # Pad every batch to one query block: the jitted scoring path then
        # sees a single (block_q, m) shape for the server's lifetime.
        self.block_q = int(block_q or max(8, self.max_batch))
        self.cache_size = int(cache_size)
        self.deadline_s = deadline_s
        self.max_pending = max_pending
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.ttl_s = ttl_s
        self.fault_plan = fault_plan
        # entries: (result, born, index version at scoring time) — fresh
        # hits require the version to still match, so a mutation
        # invalidates every prior entry without touching the dict
        self._cache: collections.OrderedDict[
            str, tuple[RetrievalResult, float, int]
        ] = collections.OrderedDict()
        # pending entries: (rid, query, cache_key, absolute deadline | inf,
        # submit time — the request-latency clock start)
        self._pending: collections.deque[
            tuple[int, np.ndarray, str, float, float]
        ] = collections.deque()
        self._results: dict[int, RetrievalResult] = {}
        self._next_id = 0
        self._requests = 0
        self._steps = 0
        self._cache_hits = 0
        self._shed = 0
        self._degraded = 0
        self._retries = 0
        self._stale = 0

    # -- input contract -----------------------------------------------------

    def _coerce_query(self, query) -> np.ndarray:
        """Validate + coerce one query to finite f32 ``(m,)``.

        The adversarial-input contract (pinned by
        ``tests/test_robust_serving.py``): non-numeric dtypes and
        non-finite values are *rejected* (a NaN poisons every score it
        touches and -inf sorts unpredictably through top-k — garbage in a
        result no client can detect); numeric dtypes are cast; all-zero
        vectors are *accepted* (``normalize_rows`` keeps them zero — they
        simply match nothing).
        """
        q = np.asarray(query)
        if q.dtype.kind not in "fiub":
            raise ValueError(
                f"query dtype {q.dtype} is not numeric "
                "(float/int/bool accepted)"
            )
        q = np.asarray(q, np.float32).reshape(-1)
        if q.shape[0] != self.index.m:
            raise ValueError(f"query dim {q.shape[0]} != index m {self.index.m}")
        if not np.all(np.isfinite(q)):
            raise ValueError("query contains non-finite values (NaN/inf)")
        return q

    def _empty_result(self, status: str) -> RetrievalResult:
        v = np.full((self.k,), -np.inf, np.float32)
        i = np.full((self.k,), -1, np.int32)
        v.setflags(write=False)
        i.setflags(write=False)
        return RetrievalResult(
            values=v, indices=i, count=0, cached=False, status=status
        )

    def _shed_request(self, rid: int) -> None:
        self._shed += 1
        telemetry.incr("serving.shed")
        trace.event("shed", rid=rid)
        self._results[rid] = self._empty_result("shed")

    # -- request lifecycle --------------------------------------------------

    def submit(self, query, *, deadline_s: Optional[float] = None) -> int:
        """Enqueue one query vector ``(m,)``; returns a request id.

        Cache hits latch their result immediately and never join a batch.
        Submits past the admission budget latch a ``status="shed"`` result
        instead of queueing (overload must fail fast, not pile up).
        """
        q = self._coerce_query(query)
        rid = self._next_id
        self._next_id += 1
        self._requests += 1
        telemetry.incr("serving.requests")
        key = self._cache_key(q)
        hit = self._cache_get(key)
        if hit is not None:
            self._cache_hits += 1
            telemetry.incr("serving.cache_hits")
            trace.event("cache_hit", rid=rid)
            if metrics.enabled():
                metrics.observe("serving.latency_s", 0.0)
            self._results[rid] = hit._replace(cached=True)
            return rid
        if self.max_pending is not None and len(self._pending) >= self.max_pending:
            self._shed_request(rid)
            return rid
        trace.event("admit", rid=rid)
        budget = deadline_s if deadline_s is not None else self.deadline_s
        now = time.monotonic()
        deadline = now + budget if budget is not None else np.inf
        self._pending.append((rid, q, key, deadline, now))
        return rid

    # -- tiered scoring ------------------------------------------------------

    def _tiers(self) -> list[tuple[str, bool]]:
        tiers = [("kernel", True)] if self.use_kernel else []
        tiers.append(("xla", False))
        return tiers

    def _score_batch(self, Qj):
        """Run the degradation ladder; returns ``(matches | None, tier)``.

        Each tier gets ``1 + max_retries`` attempts with exponential
        backoff; a tier that stays down degrades to the next. ``None``
        means every tier failed — the caller falls to stale answers.
        """
        tiers = self._tiers()
        for nth, (tier, use_k) in enumerate(tiers):
            delay = self.backoff_s
            for attempt in range(1 + self.max_retries):
                try:
                    if self.fault_plan is not None:
                        self.fault_plan.fail_point(f"serving.{tier}")
                    if isinstance(self.index, MutableAPSSIndex):
                        m = self.index.query(
                            np.asarray(Qj), self.threshold, self.k,
                            block_q=self.block_q, use_kernel=use_k,
                        )
                    else:
                        m = query_topk(
                            self.index, Qj, self.threshold, self.k,
                            block_q=self.block_q, use_kernel=use_k,
                        )
                    if nth > 0:
                        self._degraded += 1
                        telemetry.incr("serving.degraded")
                        trace.event("degrade", tier=tier)
                        recorder.trigger("serving.tier_down", tier=tier)
                    return m, tier
                except Exception:
                    if attempt < self.max_retries:
                        self._retries += 1
                        telemetry.incr("serving.retries")
                        trace.event("retry", tier=tier, attempt=attempt + 1)
                        time.sleep(delay)
                        delay *= 2
        self._degraded += 1
        telemetry.incr("serving.degraded")
        trace.event("degrade", tier="stale")
        recorder.trigger("serving.tier_down", tier="stale")
        return None, "stale"

    def step(self) -> int:
        """Serve up to ``max_batch`` pending requests with ONE jit'd call.

        Returns the number of requests finished this step (scored + shed;
        0 = idle). Past-deadline requests are shed *before* the batch is
        assembled, so a slow previous step never wastes scoring work on
        answers nobody is waiting for.
        """
        if not self._pending:
            return 0
        with trace.span("serving/step", step=self._steps):
            return self._step_inner()

    def _step_inner(self) -> int:
        if self.fault_plan is not None:
            # Chaos seam: an armed delay here models a slow shard/step.
            self.fault_plan.delay("serving", step=self._steps)
        now = time.monotonic()
        shed_count = 0
        keep: collections.deque = collections.deque()
        while self._pending:
            rid, q, key, deadline, born = self._pending.popleft()
            if deadline < now:
                self._shed_request(rid)
                shed_count += 1
            else:
                keep.append((rid, q, key, deadline, born))
        self._pending = keep
        if not self._pending:
            return shed_count
        batch = [
            self._pending.popleft()
            for _ in range(min(self.max_batch, len(self._pending)))
        ]
        trace.event("batch", size=len(batch), queued=len(self._pending))
        self._observe_batch(batch, now)
        Q = np.zeros((self.max_batch, self.index.m), np.float32)
        for slot, (_, q, _, _, _) in enumerate(batch):
            Q[slot] = q
        Qj = jnp.asarray(Q)
        if self.normalize:
            Qj = normalize_rows(Qj)
        with trace.span("serving/score", batch=len(batch)):
            m, tier = self._score_batch(Qj)
            trace.annotate(tier=tier)
        self._steps += 1

        def latch(rid: int, born: float, res: RetrievalResult) -> None:
            self._results[rid] = res
            if metrics.enabled():
                metrics.observe(
                    "serving.latency_s", time.monotonic() - born
                )

        if m is None:
            # Every scoring tier is down: stale cache answers beat no
            # answers; true misses fail explicitly.
            for rid, _, key, _, born in batch:
                stale = self._cache_get(key, stale_ok=True)
                if stale is not None:
                    self._stale += 1
                    telemetry.incr("serving.stale")
                    latch(rid, born, stale._replace(
                        cached=True, status="stale"
                    ))
                else:
                    latch(rid, born, self._empty_result("failed"))
            return len(batch) + shed_count
        values = np.asarray(m.values)
        indices = np.asarray(m.indices)
        counts = np.asarray(m.counts)
        trace.event("merge", batch=len(batch))
        for slot, (rid, _, key, _, born) in enumerate(batch):
            # Per-request copies, frozen: the cache and every client hold
            # the same arrays, so in-place mutation by one caller would
            # otherwise corrupt later cache hits — make it raise instead.
            v = values[slot].copy()
            i = indices[slot].copy()
            v.setflags(write=False)
            i.setflags(write=False)
            res = RetrievalResult(
                values=v, indices=i, count=int(counts[slot]), cached=False
            )
            latch(rid, born, res)
            self._cache_put(key, res)
        return len(batch) + shed_count

    def _observe_batch(self, batch, start: float) -> None:
        """Record a batch that starts scoring at ``start``: its occupancy
        and each request's queue wait (admission to ``start``), the
        longest as ``max_wait_ms`` on the current span."""
        waits = [start - born for *_, born in batch]
        trace.annotate(max_wait_ms=1e3 * max(waits))
        if metrics.enabled():
            metrics.observe("serving.batch_occupancy", len(batch) / self.max_batch)
            for w in waits:
                metrics.observe("serving.queue_wait_s", w)

    def result(self, rid: int) -> RetrievalResult:
        """Pop a finished request's result (steps until it is ready)."""
        while rid not in self._results:
            if not self.step():
                raise KeyError(f"unknown request id {rid}")
        return self._results.pop(rid)

    def serve(self, queries: Sequence) -> list[RetrievalResult]:
        """Convenience: submit all, drain in batches, return in order."""
        rids = [self.submit(q) for q in queries]
        while self._pending:
            self.step()
        return [self.result(r) for r in rids]

    def close(self) -> None:
        """Nothing to stop (no background workers); here so callers can
        treat step and continuous servers uniformly."""

    # -- LRU cache ----------------------------------------------------------

    def _cache_key(self, q: np.ndarray) -> str:
        h = hashlib.blake2b(q.tobytes(), digest_size=16)
        h.update(np.float32(self.threshold).tobytes())
        h.update(np.int32(self.k).tobytes())
        return h.hexdigest()

    def _index_version(self) -> int:
        """Mutable indexes bump ``version`` per mutation; immutable = 0."""
        return int(getattr(self.index, "version", 0))

    def _cache_get(
        self, key: str, *, stale_ok: bool = False
    ) -> Optional[RetrievalResult]:
        """Fresh hits only by default: in-TTL AND scored against the
        current index version (a post-mutation query must never see a
        pre-mutation answer). ``stale_ok`` ignores both — the explicit
        last-resort tier when every scoring tier is down."""
        if self.cache_size <= 0:
            return None
        hit = self._cache.get(key)
        if hit is None:
            return None
        res, born, version = hit
        if not stale_ok:
            if version != self._index_version():
                return None
            if self.ttl_s is not None and time.monotonic() - born > self.ttl_s:
                return None
        self._cache.move_to_end(key)
        return res

    def _cache_put(self, key: str, res: RetrievalResult) -> None:
        if self.cache_size <= 0:
            return
        self._cache[key] = (res, time.monotonic(), self._index_version())
        self._cache.move_to_end(key)
        while len(self._cache) > self.cache_size:
            self._cache.popitem(last=False)

    @property
    def stats(self) -> ServerStats:
        return ServerStats(
            requests=self._requests,
            steps=self._steps,
            cache_hits=self._cache_hits,
            shed=self._shed,
            degraded=self._degraded,
            retries=self._retries,
            stale=self._stale,
        )


class ContinuousRetrievalServer(RetrievalServer):
    """Slot-granularity (continuous-batching) retrieval server.

    :class:`RetrievalServer` quantizes latency to ``step()`` boundaries:
    every admitted request waits for the caller's next step, and one slow
    batch (a straggling peer, an armed chaos delay) holds EVERY queued
    request behind it — the classic step-latch p99 cliff. This subclass
    keeps the whole request lifecycle — admission control, deadlines,
    version-keyed LRU, the kernel→XLA→stale degradation ladder — and
    replaces only the latch: ``workers`` background threads pull up to
    ``max_batch`` requests the moment any are pending (slot recycling
    applied to retrieval batches), so

    - a request's service time starts at SUBMIT, not at the next step
      boundary, and
    - with ``workers ≥ 2`` a straggling batch delays only its own
      occupants: the other worker keeps draining fresh arrivals, which is
      precisely the p99 win ``benchmarks/bench_serve.py`` measures.

    Threading contract: one lock guards the queue/results/cache/counters;
    scoring runs OUTSIDE the lock (concurrent jit dispatch is safe — the
    compiled executable is shared). Each worker scores a batch inside its
    own ``serving/step`` and ``serving/score`` spans (the tracer keeps one
    open-span stack per thread) and emits ``slot``/``exit`` events; admission
    emits ``admit`` on the submitting thread. Deadline sheds
    happen at batch ASSEMBLY, same as the step server — a straggler never
    wastes scoring work on answers nobody is waiting for. ``step()`` is a
    no-op here (workers drain continuously); use ``result()``/``serve()``,
    and ``close()`` (or the context manager) to stop the workers.
    """

    def __init__(self, index: APSSIndex, *, workers: int = 2, **kwargs):
        super().__init__(index, **kwargs)
        self._lock = threading.Lock()
        self._work_ready = threading.Condition(self._lock)
        self._done = threading.Condition(self._lock)
        self._stop = False
        self._batch_seq = 0  # chaos seam: the continuous analog of _steps
        self._inflight: set[int] = set()  # claimed by a worker, not latched
        self._workers = [
            threading.Thread(
                target=self._worker_loop, name=f"retrieval-slot-{i}",
                daemon=True,
            )
            for i in range(max(1, int(workers)))
        ]
        for w in self._workers:
            w.start()

    # -- lifecycle -----------------------------------------------------------

    def close(self) -> None:
        """Stop the worker threads (idempotent). Pending requests are left
        queued — ``close()`` is shutdown, not drain; call ``serve``/
        ``result`` first if completion matters."""
        with self._lock:
            if self._stop:
                return
            self._stop = True
            self._work_ready.notify_all()
        for w in self._workers:
            w.join()

    def __enter__(self) -> "ContinuousRetrievalServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- request lifecycle ---------------------------------------------------

    def submit(self, query, *, deadline_s: Optional[float] = None) -> int:
        """Enqueue one query; a worker picks it up immediately.

        Same admission/cache/validation contract as the step server, made
        thread-safe; the only behavioral difference is that admission WAKES
        a worker instead of waiting for a step boundary.
        """
        q = self._coerce_query(query)
        key = self._cache_key(q)
        with self._lock:
            rid = self._next_id
            self._next_id += 1
            self._requests += 1
            telemetry.incr("serving.requests")
            hit = self._cache_get(key)
            if hit is not None:
                self._cache_hits += 1
                telemetry.incr("serving.cache_hits")
                trace.event("cache_hit", rid=rid)
                if metrics.enabled():
                    metrics.observe("serving.latency_s", 0.0)
                self._results[rid] = hit._replace(cached=True)
                self._done.notify_all()
                return rid
            if (
                self.max_pending is not None
                and len(self._pending) >= self.max_pending
            ):
                self._shed_request(rid)
                self._done.notify_all()
                return rid
            trace.event("admit", rid=rid, queued=len(self._pending))
            budget = deadline_s if deadline_s is not None else self.deadline_s
            now = time.monotonic()
            deadline = now + budget if budget is not None else np.inf
            self._pending.append((rid, q, key, deadline, now))
            self._work_ready.notify()
        return rid

    def step(self) -> int:
        """No-op: workers drain the queue continuously."""
        return 0

    def result(self, rid: int, timeout_s: Optional[float] = None) -> RetrievalResult:
        """Block until ``rid``'s result latches, then pop it."""
        deadline = (
            time.monotonic() + timeout_s if timeout_s is not None else None
        )
        with self._lock:
            while rid not in self._results:
                if (
                    rid >= self._next_id
                    or (
                        rid not in self._inflight
                        and all(p[0] != rid for p in self._pending)
                    )
                ):
                    raise KeyError(f"unknown request id {rid}")
                if self._stop:
                    raise RuntimeError("server closed while request pending")
                wait = None
                if deadline is not None:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        raise TimeoutError(f"result({rid}) timed out")
                self._done.wait(timeout=wait)
            return self._results.pop(rid)

    def serve(self, queries: Sequence) -> list[RetrievalResult]:
        """Submit all, block until every result latches, return in order."""
        rids = [self.submit(q) for q in queries]
        return [self.result(r) for r in rids]

    # -- worker loop ---------------------------------------------------------

    def _take_batch(self):
        """Under the lock: shed expired requests, then claim up to
        ``max_batch``. Returns ``(batch, seq)`` or ``None`` at shutdown."""
        while True:
            if self._stop:
                return None
            now = time.monotonic()
            while self._pending and self._pending[0][3] < now:
                rid = self._pending.popleft()[0]
                self._shed_request(rid)
                self._done.notify_all()
            if self._pending:
                batch = [
                    self._pending.popleft()
                    for _ in range(min(self.max_batch, len(self._pending)))
                ]
                self._inflight.update(b[0] for b in batch)
                seq = self._batch_seq
                self._batch_seq += 1
                return batch, seq
            self._work_ready.wait()

    def _worker_loop(self) -> None:
        while True:
            with self._lock:
                taken = self._take_batch()
                if taken is None:
                    return
                batch, seq = taken
                start = time.monotonic()
                trace.event(
                    "slot", seq=seq, size=len(batch),
                    queued=len(self._pending),
                )
            with trace.span("serving/step", step=seq):
                with self._lock:
                    self._observe_batch(batch, start)
                self._run_batch(batch, seq)

    def _run_batch(self, batch, seq: int) -> None:
        """Score a claimed batch and latch its results. Scoring runs
        UNLOCKED: a straggling batch (chaos delay, slow tier) must not stop
        sibling workers from draining arrivals."""
        if self.fault_plan is not None:
            self.fault_plan.delay("serving", step=seq)
        Q = np.zeros((self.max_batch, self.index.m), np.float32)
        for slot, (_, q, _, _, _) in enumerate(batch):
            Q[slot] = q
        Qj = jnp.asarray(Q)
        if self.normalize:
            Qj = normalize_rows(Qj)
        with trace.span("serving/score", batch=len(batch)):
            m, tier = self._score_batch(Qj)
            trace.annotate(tier=tier)
        with self._lock:
            self._steps += 1
            self._latch_batch(batch, m, tier, seq)
            self._done.notify_all()

    def _latch_batch(self, batch, m, tier, seq) -> None:
        now = time.monotonic()

        def latch(rid: int, born: float, res: RetrievalResult) -> None:
            self._results[rid] = res
            self._inflight.discard(rid)
            trace.event("exit", rid=rid, seq=seq, status=res.status, tier=tier)
            if metrics.enabled():
                metrics.observe("serving.latency_s", now - born)

        if m is None:
            for rid, _, key, _, born in batch:
                stale = self._cache_get(key, stale_ok=True)
                if stale is not None:
                    self._stale += 1
                    telemetry.incr("serving.stale")
                    latch(rid, born, stale._replace(cached=True, status="stale"))
                else:
                    latch(rid, born, self._empty_result("failed"))
            return
        values = np.asarray(m.values)
        indices = np.asarray(m.indices)
        counts = np.asarray(m.counts)
        for slot, (rid, _, key, _, born) in enumerate(batch):
            v = values[slot].copy()
            i = indices[slot].copy()
            v.setflags(write=False)
            i.setflags(write=False)
            res = RetrievalResult(
                values=v, indices=i, count=int(counts[slot]), cached=False
            )
            latch(rid, born, res)
            self._cache_put(key, res)
