"""MutableAPSSIndex: a live corpus with delta similarity joins (ISSUE 7).

``APSSIndex`` is immutable — every corpus change pays a full rebuild. A
production system has rows arriving continuously, so this module wraps the
same machinery with an append/delete log:

- :meth:`MutableAPSSIndex.append` normalizes the delta, packs it after the
  existing rows, recomputes :class:`~repro.core.pruning.BlockStats` for the
  touched window of blocks only, and runs the **delta join** —
  ``(new × existing) ∪ (new × new)`` — through the rectangular worklist
  scorers to keep a standing top-k similarity graph current at cost
  proportional to the delta, not the corpus.
- :meth:`MutableAPSSIndex.delete` sets tombstones (rows are zeroed on
  device and masked out of every join by a live-row mask honored alongside
  ``live_tile_mask``), repairs exactly the graph rows that referenced a
  deleted neighbor, and triggers :meth:`compact` when the tombstone
  fraction crosses a threshold.

**Bit-equality contract** (the metamorphic harness's invariant): after ANY
interleaving of append/delete/compact, the graph and query results are
bit-identical to a fresh index built from the surviving rows in the same
order. Three design rules make this hold:

1. *Canonical top-k order.* Every merge respects the strict total order
   (value desc, physical position asc): worklists are plain ascending
   ``(i, j)`` (``compact_rect_worklist`` with no ``ub``), the packet fold
   concatenates buffer-before-packet (``lax.top_k`` ties break on earliest
   concat position), and host merges use a stable argsort — equivalent to
   ``lax.top_k``. Appends pack at the end and compaction preserves order,
   so physical order always equals gid order among live rows and the
   tie-break is layout-independent.
2. *Layout-independent score bits.* Dense tiles contract over the fixed
   lane-padded feature axis; sparse tiles score with
   :func:`~repro.core.sparse.gather_dot` over each column row's own ELL
   slots (NOT the per-block support compaction, whose reduction grouping
   depends on which rows share a block). Either way a pair's score depends
   only on the two rows' contents — identical bits before and after
   deletes or compaction. Sparse bit-equality additionally requires the
   same ELL ``cap`` on both sides (pin ``cap=``); widening appends inert
   zero slots but changes the chunk count, which is not guaranteed stable.
3. *Scoring extra tiles is harmless.* Stats are updated exactly for append
   windows and left stale (upper bounds over a superset) across deletes —
   sound either way; a tile live here but dead in the fresh rebuild is
   provably matchless, its packet is all-empty, and empty entries are
   neutralized before every merge.

**Durability** (the robust seam): with ``directory=``, every mutation is
written to a write-ahead log (one ``CheckpointManager`` step per op,
``keep=0`` — digests included) *before* it is applied, and a state
snapshot lands after. Reopening with ``corpus=None`` restores the newest
intact snapshot and replays the log tail — a kill between WAL write and
snapshot resumes bit-identically. A corrupt log entry walks back exactly
that op (``mutable.log_walkback``) instead of poisoning the state.

Retrace discipline: capacity grows in powers of two, deltas are bucketed
to powers of two, worklists are bucket-padded (``pad_worklist``), and the
valid-row count / live mask / window start enter the jitted inners as
traced arguments — repeated same-shape appends trace nothing new
(asserted under an ``obs.compile.assert_no_retrace("serving.mutable")``
contract by ``tests/test_mutable_index.py``).
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.checkpoint import (
    CheckpointCorruptionError,
    CheckpointManager,
    load_checkpoint,
)
from repro.core.apss import normalize_rows
from repro.core.matches import SCORE_PRECISION, Matches
from repro.core.pruning import (
    BlockStats,
    dense_block_stats,
    live_tile_mask,
    sparse_block_stats,
)
from repro.core.sparse import (
    SparseCorpus,
    from_dense,
    gather_dot,
    normalize_sparse,
    to_dense,
)
from repro.kernels.apss_block.fused import (
    NEG_LARGE,
    _rect_tile_packets,
    _topk_sort,
)
from repro.kernels.apss_block.ops import (
    _pick_bk,
    compact_rect_worklist,
    fold_rect_packets,
    pad_worklist,
)
from repro.obs import compile as obs_compile
from repro.obs import trace
from repro.planner import telemetry
from repro.serving.index import APSSIndex
from repro.serving.query import _query_mask, query_topk

obs_compile.register_entry_points(
    "serving.mutable",
    "mutable_update", "mutable_full_stats", "mutable_self_mask",
    "mutable_zero_rows", "mutable_dense_inner", "mutable_sparse_inner",
    "mutable_sparse_self_inner",
)

_META = "meta.json"


def _p2(x: int) -> int:
    """Smallest power of two ≥ x (x ≥ 1)."""
    return 1 << max(0, (int(x) - 1).bit_length())


# ---------------------------------------------------------------------------
# Jitted state updates (all mark the retrace registry at trace time only)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_rows", "wb"))
def _update_dense(C, maxw, mw, mnnz, delta, nv, w0, *, block_rows, wb):
    """Write a bucketed delta at row ``nv``; recompute the ``wb``-block
    stats window starting at row ``w0`` (covers every touched block)."""
    obs_compile.mark("mutable_update")
    C = lax.dynamic_update_slice(C, delta, (nv, 0))
    W = lax.dynamic_slice(C, (w0, 0), (wb * block_rows, C.shape[1]))
    ws = dense_block_stats(W, block_rows)
    b0 = w0 // block_rows
    maxw = lax.dynamic_update_slice(maxw, ws.maxw, (b0, 0))
    mw = lax.dynamic_update_slice(mw, ws.mw, (b0,))
    mnnz = lax.dynamic_update_slice(mnnz, ws.max_nnz, (b0,))
    return C, maxw, mw, mnnz


@functools.partial(jax.jit, static_argnames=("block_rows", "wb", "m"))
def _update_sparse(
    idx, val, nnz, maxw, mw, mnnz, didx, dval, dnnz, nv, w0, *,
    block_rows, wb, m,
):
    """Sparse twin of :func:`_update_dense` over the ELL triple."""
    obs_compile.mark("mutable_update")
    idx = lax.dynamic_update_slice(idx, didx, (nv, 0))
    val = lax.dynamic_update_slice(val, dval, (nv, 0))
    nnz = lax.dynamic_update_slice(nnz, dnnz, (nv,))
    rows = wb * block_rows
    Wi = lax.dynamic_slice(idx, (w0, 0), (rows, idx.shape[1]))
    Wv = lax.dynamic_slice(val, (w0, 0), (rows, val.shape[1]))
    Wn = lax.dynamic_slice(nnz, (w0,), (rows,))
    ws = sparse_block_stats(SparseCorpus(Wi, Wv, Wn, m), block_rows)
    b0 = w0 // block_rows
    maxw = lax.dynamic_update_slice(maxw, ws.maxw, (b0, 0))
    mw = lax.dynamic_update_slice(mw, ws.mw, (b0,))
    mnnz = lax.dynamic_update_slice(mnnz, ws.max_nnz, (b0,))
    return idx, val, nnz, maxw, mw, mnnz


@functools.partial(jax.jit, static_argnames=("block_rows",))
def _full_dense_stats(C, *, block_rows):
    obs_compile.mark("mutable_full_stats")
    return dense_block_stats(C, block_rows)


@functools.partial(jax.jit, static_argnames=("block_rows", "m"))
def _full_sparse_stats(idx, val, nnz, *, block_rows, m):
    obs_compile.mark("mutable_full_stats")
    return sparse_block_stats(SparseCorpus(idx, val, nnz, m), block_rows)


@functools.partial(jax.jit, static_argnames=("threshold", "use_minsize"))
def _self_mask(maxw, mw, mnnz, *, threshold, use_minsize):
    """Corpus-vs-corpus live mask for the reverse join (old × new)."""
    obs_compile.mark("mutable_self_mask")
    st = BlockStats(maxw, mw, mnnz)
    return live_tile_mask(
        st, st, threshold, use_minsize=use_minsize, normalized=True
    )


@jax.jit
def _zero_rows(x, phys):
    """Zero rows at ``phys`` (padded entries point past the array: dropped).

    The pad value MUST be out of range — jnp scatters clamp by default,
    which would silently re-zero the last row instead of no-op'ing.
    """
    obs_compile.mark("mutable_zero_rows")
    return x.at[phys].set(0, mode="drop")


# ---------------------------------------------------------------------------
# Jitted tile scorers. These are the mutable siblings of the
# serving/query.py inners: same packet/fold machinery, but column liveness
# and per-query self-exclusion positions are TRACED vectors (they change
# every mutation; static arguments would retrace per append).
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "k", "block_q", "block_c", "grid_q"),
)
def _mut_dense_inner(
    Qp, C, col_live, qpos, ij, tvalid, *,
    threshold, k, block_q, block_c, grid_q,
):
    """Dense rect scorer with traced liveness + self-exclusion.

    ``qpos[r]`` is query row r's own physical corpus position (−1 = not a
    corpus row): the matching column is masked so a corpus row never
    matches itself. Dead/padding columns (``col_live`` False) are masked to
    ``NEG_LARGE`` so they fail any real threshold, including t ≤ 0.
    """
    obs_compile.mark("mutable_dense_inner")
    m = Qp.shape[1]
    ncap = C.shape[0]
    Qb = Qp.reshape(grid_q, block_q, m)
    Cb = C.reshape(-1, block_c, m)
    liveb = col_live.reshape(-1, block_c)
    qposb = qpos.reshape(grid_q, block_q)

    def tile(_, t):
        i, j = ij[0, t], ij[1, t]
        s = jnp.einsum(
            "qm,cm->qc", Qb[i], Cb[j],
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        gcol = j * block_c + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(liveb[j][None, :], s, NEG_LARGE)
        s = jnp.where(qposb[i][:, None] == gcol, NEG_LARGE, s)
        return _, _rect_tile_packets(
            s, j, threshold=threshold, k=k, block_q=block_q,
            block_c=block_c, nc_valid=ncap, topk=_topk_sort,
        )

    _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij.shape[1]))
    return fold_rect_packets(
        ij, tvalid, fv, fi, fc[..., 0], grid_q=grid_q,
        grid_c=ncap // block_c, block_q=block_q, k=k,
    )


@functools.partial(
    jax.jit,
    static_argnames=("threshold", "k", "block_q", "block_c", "grid_q"),
)
def _mut_sparse_inner(
    Qp, idx, val, col_live, qpos, ij, tvalid, *,
    threshold, k, block_q, block_c, grid_q,
):
    """Sparse rect scorer: dense query block × raw ELL corpus block.

    Scores via :func:`gather_dot` over each corpus row's OWN cap slots —
    the reduction grouping is a property of the row, not of the block it
    lives in, so bits survive deletes and compaction (module doc, rule 2).
    """
    obs_compile.mark("mutable_sparse_inner")
    cap = idx.shape[1]
    ncap = idx.shape[0]
    Qb = Qp.astype(jnp.float32).reshape(grid_q, block_q, -1)
    Ib = idx.reshape(-1, block_c, cap)
    Vb = val.reshape(-1, block_c, cap)
    liveb = col_live.reshape(-1, block_c)
    qposb = qpos.reshape(grid_q, block_q)

    def tile(_, t):
        i, j = ij[0, t], ij[1, t]
        s = gather_dot(Qb[i], Ib[j], Vb[j])
        gcol = j * block_c + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(liveb[j][None, :], s, NEG_LARGE)
        s = jnp.where(qposb[i][:, None] == gcol, NEG_LARGE, s)
        return _, _rect_tile_packets(
            s, j, threshold=threshold, k=k, block_q=block_q,
            block_c=block_c, nc_valid=ncap, topk=_topk_sort,
        )

    _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij.shape[1]))
    return fold_rect_packets(
        ij, tvalid, fv, fi, fc[..., 0], grid_q=grid_q,
        grid_c=ncap // block_c, block_q=block_q, k=k,
    )


@functools.partial(
    jax.jit, static_argnames=("threshold", "k", "block_c", "grid_q", "m")
)
def _mut_sparse_self_inner(
    idx, val, col_live, ij, tvalid, *, threshold, k, block_c, grid_q, m,
):
    """Sparse reverse join: corpus row blocks as queries, densified per
    live tile (O(live tiles · block · m), never O(corpus · m))."""
    obs_compile.mark("mutable_sparse_self_inner")
    cap = idx.shape[1]
    ncap = idx.shape[0]
    Ib = idx.reshape(-1, block_c, cap)
    Vb = val.reshape(-1, block_c, cap)
    liveb = col_live.reshape(-1, block_c)

    def tile(_, t):
        i, j = ij[0, t], ij[1, t]
        r = jnp.arange(block_c, dtype=jnp.int32)[:, None]
        qd = jnp.zeros((block_c, m), jnp.float32).at[r, Ib[i]].add(Vb[i])
        s = gather_dot(qd, Ib[j], Vb[j])
        grow = i * block_c + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        gcol = j * block_c + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(liveb[j][None, :], s, NEG_LARGE)
        s = jnp.where(grow == gcol, NEG_LARGE, s)
        return _, _rect_tile_packets(
            s, j, threshold=threshold, k=k, block_q=block_c,
            block_c=block_c, nc_valid=ncap, topk=_topk_sort,
        )

    _, (fv, fi, fc) = lax.scan(tile, 0, jnp.arange(ij.shape[1]))
    return fold_rect_packets(
        ij, tvalid, fv, fi, fc[..., 0], grid_q=grid_q,
        grid_c=ncap // block_c, block_q=block_c, k=k,
    )


def _np_merge(gv, gi, pv, pi, k):
    """Host merge of graph rows with packet rows, canonical order.

    Stable argsort on negated values == ``lax.top_k`` (k best, ties to the
    earliest concat position). Old entries come first in the concat and
    always reference lower physical positions than a packet's new columns,
    so the tie-break matches the canonical (value desc, position asc).
    """
    av = np.concatenate([gv, pv], axis=1)
    ai = np.concatenate([gi, pi], axis=1)
    sel = np.argsort(-av, axis=1, kind="stable")[:, :k]
    v = np.take_along_axis(av, sel, axis=1)
    i = np.take_along_axis(ai, sel, axis=1)
    return v, np.where(v > -np.inf, i, -1)


class MutableAPSSIndex:
    """Live-corpus APSS index: append/delete log + standing top-k graph.

    Args:
      corpus: optional initial rows — dense ``(n, m)`` or a
        :class:`SparseCorpus`; applied as the first append. Must be None
        when reopening an existing ``directory`` (the state on disk wins).
      threshold / k: the standing graph's match threshold and capacity,
        fixed for the index's lifetime (recorded in ``meta.json``).
      kind: ``"dense"`` / ``"sparse"``; inferred from the first corpus
        when omitted (SparseCorpus ⇒ sparse).
      block_rows: row-block size (power of two) for stats and tiles.
      cap: pin the sparse ELL width. Bit-equality across instances
        requires equal caps (module doc, rule 2); unpinned caps widen on
        demand.
      compact_threshold: tombstone fraction that triggers auto-compaction
        inside :meth:`delete`.
      directory: WAL + snapshot root (``<dir>/log``, ``<dir>/state``);
        None disables durability.
      keep: snapshots kept (the WAL keeps every entry).
      fault_plan: a ``robust.faults.FaultPlan`` — kill seams fire at
        ``"mutable.append"`` (post-WAL, pre-apply) and ``"mutable.commit"``
        (post-apply, pre-snapshot).
    """

    def __init__(
        self,
        corpus=None,
        *,
        threshold: float,
        k: int = 32,
        kind: str | None = None,
        block_rows: int = 64,
        cap: int | None = None,
        compact_threshold: float = 0.25,
        directory: str | None = None,
        keep: int = 3,
        fault_plan=None,
    ):
        if block_rows & (block_rows - 1):
            raise ValueError(f"block_rows must be a power of two: {block_rows}")
        self.threshold = float(threshold)
        self.k = int(k)
        self.block_rows = int(block_rows)
        self.compact_threshold = float(compact_threshold)
        self.fault_plan = fault_plan
        self._kind = kind
        self._cap_param = cap
        self._m = None
        self._mlanes = None
        self._cap = cap
        # device state (None until the first append / restore)
        self._C = None
        self._idx = self._val = self._nnz = None
        self._maxw = self._mw = self._mnnz = None
        # host state
        self._ncap = 0
        self._nv = 0
        self._ndead = 0
        self._next_gid = 0
        self._gids = np.zeros(0, np.int64)
        self._live = np.zeros(0, bool)
        self._phys: dict[int, int] = {}
        self._gv = np.zeros((0, self.k), np.float32)
        self._gi = np.zeros((0, self.k), np.int64)
        self._gc = np.zeros(0, np.int64)
        self.version = 0
        self._op_seq = 0
        self._replaying = False
        self._view = None
        self._view_version = -1
        # durability
        self._dir = directory
        self._log_mgr = self._state_mgr = None
        if directory is not None:
            self._log_dir = os.path.join(directory, "log")
            self._state_dir = os.path.join(directory, "state")
            self._log_mgr = CheckpointManager(self._log_dir, keep=0)
            self._state_mgr = CheckpointManager(self._state_dir, keep=keep)
            self._check_meta()
        has_state = self._log_mgr is not None and (
            self._log_mgr.all_steps() or self._state_mgr.all_steps()
        )
        if has_state:
            if corpus is not None:
                raise ValueError(
                    f"directory {directory} already holds index state; "
                    "pass corpus=None to resume"
                )
            self._restore_and_replay()
        elif corpus is not None:
            self.append(corpus)

    # -- properties ---------------------------------------------------------

    @property
    def m(self) -> int | None:
        return self._m

    @property
    def kind(self) -> str | None:
        return self._kind

    @property
    def is_sparse(self) -> bool:
        return self._kind == "sparse"

    @property
    def n(self) -> int:
        """Live row count."""
        return self._nv - self._ndead

    def __repr__(self) -> str:
        return (
            f"MutableAPSSIndex(kind={self._kind}, live={self.n}, "
            f"dead={self._ndead}, version={self.version})"
        )

    # -- meta / durability helpers ------------------------------------------

    def _meta_dict(self) -> dict:
        return {
            "kind": self._kind, "m": self._m, "k": self.k,
            "threshold": self.threshold, "block_rows": self.block_rows,
            "cap": self._cap_param,
            "compact_threshold": self.compact_threshold,
        }

    def _check_meta(self) -> None:
        path = os.path.join(self._dir, _META)
        if not os.path.exists(path):
            return
        with open(path) as f:
            meta = json.load(f)
        for key in ("k", "threshold", "block_rows", "compact_threshold"):
            if meta[key] != getattr(self, key):
                raise ValueError(
                    f"meta mismatch for {key}: directory has {meta[key]}, "
                    f"constructor got {getattr(self, key)}"
                )
        if self._kind is not None and meta["kind"] != self._kind:
            raise ValueError(
                f"meta mismatch for kind: directory has {meta['kind']}, "
                f"constructor got {self._kind}"
            )
        self._kind = meta["kind"]
        self._m = meta["m"]
        self._cap_param = meta["cap"]
        if self._cap is None:
            self._cap = meta["cap"]

    def _write_meta(self) -> None:
        if self._dir is None:
            return
        path = os.path.join(self._dir, _META)
        if not os.path.exists(path):
            with open(path, "w") as f:
                json.dump(self._meta_dict(), f)

    def _log(self, entry: dict, seq: int) -> None:
        if self._log_mgr is not None and not self._replaying:
            self._log_mgr.save(entry, seq)

    def _kill(self, seq: int, scope: str) -> None:
        if self.fault_plan is not None and not self._replaying:
            self.fault_plan.kill_point(seq, scope)

    def _state_dict(self) -> dict:
        d = {
            "gids": self._gids, "live": self._live,
            "gv": self._gv, "gi": self._gi, "gc": self._gc,
            "maxw": np.asarray(self._maxw), "mw": np.asarray(self._mw),
            "mnnz": np.asarray(self._mnnz),
            "meta_ints": np.array(
                [self._nv, self._next_gid, self._op_seq, self.version,
                 self._ndead], np.int64,
            ),
        }
        if self.is_sparse:
            d["sidx"] = np.asarray(self._idx)
            d["sval"] = np.asarray(self._val)
            d["snnz"] = np.asarray(self._nnz)
        else:
            d["C"] = np.asarray(self._C)
        return d

    def _load_state(self, d: dict) -> None:
        self._gids = np.asarray(d["gids"], np.int64)
        self._live = np.asarray(d["live"], bool)
        self._gv = np.asarray(d["gv"], np.float32)
        self._gi = np.asarray(d["gi"], np.int64)
        self._gc = np.asarray(d["gc"], np.int64)
        self._maxw = jnp.asarray(d["maxw"])
        self._mw = jnp.asarray(d["mw"])
        self._mnnz = jnp.asarray(d["mnnz"])
        nv, ng, seq, ver, nd = (int(x) for x in d["meta_ints"])
        self._nv, self._next_gid, self._op_seq = nv, ng, seq
        self.version, self._ndead = ver, nd
        if self.is_sparse:
            self._idx = jnp.asarray(d["sidx"])
            self._val = jnp.asarray(d["sval"])
            self._nnz = jnp.asarray(d["snnz"])
            self._ncap = self._idx.shape[0]
            self._cap = self._idx.shape[1]
        else:
            self._C = jnp.asarray(d["C"])
            self._ncap = self._C.shape[0]
            self._mlanes = self._C.shape[1]
        self._phys = {
            int(g): int(p)
            for p, g in enumerate(self._gids)
            if g >= 0 and self._live[p]
        }

    def _snapshot(self) -> None:
        if self._state_mgr is not None:
            self._state_mgr.save(self._state_dict(), self._op_seq)

    def _restore_and_replay(self) -> None:
        with trace.span("mutable/replay"):
            self._restore_and_replay_inner()

    def _restore_and_replay_inner(self) -> None:
        latest = self._state_mgr.latest_step()
        state, step = self._state_mgr.restore(fallback=True)
        if state is not None:
            self._load_state(state)
            if step != latest:
                telemetry.incr("mutable.restore_fallback")
        replayed = 0
        for seq in sorted(self._log_mgr.all_steps()):
            if seq <= self._op_seq:
                continue
            if seq != self._op_seq + 1:
                break  # a hole in the log: stop at the contiguous prefix
            try:
                entry = load_checkpoint(self._log_dir, seq)
            except CheckpointCorruptionError as e:
                warnings.warn(
                    f"mutation log entry {seq} corrupt ({e}); "
                    "walking back this op",
                    stacklevel=2,
                )
                telemetry.incr("mutable.log_walkback")
                break
            op = int(np.asarray(entry["op"]))
            self._replaying = True
            try:
                if op == 1:
                    self._apply_append(np.asarray(entry["rows"], np.float32))
                elif op == 2:
                    self._apply_delete(np.asarray(entry["ids"], np.int64))
                elif op == 3:
                    self._compact()
                else:  # pragma: no cover - defensive
                    raise ValueError(f"unknown log op {op}")
            finally:
                self._replaying = False
            self._op_seq = seq
            replayed += 1
        if replayed:
            telemetry.incr("mutable.replayed_ops", replayed)
        # Drop log entries past the applied prefix (the walked-back op and
        # anything after): future ops must be able to reuse those steps —
        # CheckpointManager.save skips existing step dirs.
        for s in self._log_mgr.all_steps():
            if s > self._op_seq:
                shutil.rmtree(
                    os.path.join(self._log_dir, f"step_{s:010d}"),
                    ignore_errors=True,
                )
        if replayed:
            self._snapshot()

    # -- layout / capacity --------------------------------------------------

    def _coerce_rows(self, rows) -> np.ndarray:
        """Any accepted delta → raw (pre-normalization) dense f32 host array.

        The WAL stores exactly this canonical payload, so replay applies
        the same bytes the original call did.
        """
        if isinstance(rows, SparseCorpus):
            if self._kind is None:
                self._kind = "sparse"
            raw = np.asarray(to_dense(rows), np.float32)
        else:
            raw = np.asarray(rows, np.float32)
        if raw.ndim != 2:
            raise ValueError(f"rows must be 2-D, got shape {raw.shape}")
        if not np.all(np.isfinite(raw)):
            raise ValueError("rows contain non-finite values (NaN/inf)")
        if self._kind is None:
            self._kind = "dense"
        if self._m is None:
            self._m = int(raw.shape[1])
            self._write_meta()
        if raw.shape[1] != self._m:
            raise ValueError(f"rows dim {raw.shape[1]} != index m {self._m}")
        return raw

    def _init_arrays(self) -> None:
        if self._ncap:
            return
        self._ncap = self.block_rows
        nb = self._ncap // self.block_rows
        if self.is_sparse:
            cap = self._cap or 1
            self._cap = cap
            self._idx = jnp.zeros((self._ncap, cap), jnp.int32)
            self._val = jnp.zeros((self._ncap, cap), jnp.float32)
            self._nnz = jnp.zeros((self._ncap,), jnp.int32)
            width = self._m
        else:
            self._mlanes = self._m + (-self._m) % _pick_bk(self._m, 512)
            self._C = jnp.zeros((self._ncap, self._mlanes), jnp.float32)
            width = self._mlanes
        self._maxw = jnp.zeros((nb, width), jnp.float32)
        self._mw = jnp.zeros((nb,), jnp.float32)
        self._mnnz = jnp.zeros((nb,), jnp.int32)
        self._grow_host(self._ncap)

    def _grow_host(self, ncap: int) -> None:
        old = self._gids.shape[0]
        if ncap <= old:
            return
        pad = ncap - old
        self._gids = np.concatenate([self._gids, np.full(pad, -1, np.int64)])
        self._live = np.concatenate([self._live, np.zeros(pad, bool)])
        self._gv = np.concatenate(
            [self._gv, np.full((pad, self.k), -np.inf, np.float32)]
        )
        self._gi = np.concatenate(
            [self._gi, np.full((pad, self.k), -1, np.int64)]
        )
        self._gc = np.concatenate([self._gc, np.zeros(pad, np.int64)])

    def _ensure_capacity(self, need: int) -> None:
        """Grow every capacity array to a power-of-two row count ≥ need.

        MUST run before the delta's ``dynamic_update_slice`` — JAX clamps
        start indices, so an overflowing write would silently shift.
        """
        if need <= self._ncap:
            return
        ncap = self._ncap
        while ncap < need:
            ncap *= 2
        pad = ncap - self._ncap
        nbpad = pad // self.block_rows
        if self.is_sparse:
            self._idx = jnp.pad(self._idx, ((0, pad), (0, 0)))
            self._val = jnp.pad(self._val, ((0, pad), (0, 0)))
            self._nnz = jnp.pad(self._nnz, (0, pad))
        else:
            self._C = jnp.pad(self._C, ((0, pad), (0, 0)))
        self._maxw = jnp.pad(self._maxw, ((0, nbpad), (0, 0)))
        self._mw = jnp.pad(self._mw, (0, nbpad))
        self._mnnz = jnp.pad(self._mnnz, (0, nbpad))
        self._grow_host(ncap)
        self._ncap = ncap

    def _widen_cap(self, need: int) -> None:
        """Widen the ELL layout with inert zero slots (sparse only).

        Documented caveat: widening changes gather_dot's chunk count, so
        bit-equality across different realized caps is NOT guaranteed —
        pin ``cap=`` when bit-stability matters.
        """
        if need <= self._cap:
            return
        pad = need - self._cap
        self._idx = jnp.pad(self._idx, ((0, 0), (0, pad)))
        self._val = jnp.pad(self._val, ((0, 0), (0, pad)))
        self._cap = need

    def _stats(self) -> BlockStats:
        return BlockStats(self._maxw, self._mw, self._mnnz)

    def _gid_of(self, pi: np.ndarray) -> np.ndarray:
        """Physical column ids (−1 empty) → global ids."""
        return np.where(pi >= 0, self._gids[np.maximum(pi, 0)], -1)

    # -- public mutations ---------------------------------------------------

    def append(self, rows) -> list[int]:
        """Append a batch of rows; returns their new global ids.

        WAL-first: the raw delta is logged, then applied (normalize → pack
        → window stats → delta join into the graph), then snapshotted.
        An empty delta is a no-op (no log entry, no version bump).
        """
        raw = self._coerce_rows(rows)
        if raw.shape[0] == 0:
            return []
        with trace.span("mutable/append", rows=int(raw.shape[0])):
            seq = self._op_seq + 1
            self._log({"op": np.int64(1), "rows": raw}, seq)
            self._kill(seq, "mutable.append")
            gids = self._apply_append(raw)
            self._op_seq = seq
            self._kill(seq, "mutable.commit")
            self._snapshot()
            telemetry.incr("serving.appends")
            return gids

    def delete(self, ids) -> int:
        """Tombstone rows by global id; repairs the graph exactly.

        Raises ``KeyError`` for unknown/dead ids (before logging anything).
        Returns the number of rows deleted. Auto-compacts when the dead
        fraction reaches ``compact_threshold``.
        """
        ids = np.asarray(list(ids), np.int64).reshape(-1)
        if len(set(ids.tolist())) != ids.shape[0]:
            raise ValueError("duplicate ids in delete batch")
        for g in ids:
            if int(g) not in self._phys:
                raise KeyError(f"unknown or already-deleted id {int(g)}")
        if ids.shape[0] == 0:
            return 0
        with trace.span("mutable/delete", rows=int(ids.shape[0])):
            seq = self._op_seq + 1
            self._log({"op": np.int64(2), "ids": ids}, seq)
            self._kill(seq, "mutable.append")
            self._apply_delete(ids)
            self._op_seq = seq
            self._kill(seq, "mutable.commit")
            self._snapshot()
            telemetry.incr("serving.deletes")
            return int(ids.shape[0])

    def compact(self) -> None:
        """Rewrite live rows contiguously (order preserved) and rebuild
        stats; logged as its own op so resume replays it."""
        with trace.span("mutable/compact"):
            seq = self._op_seq + 1
            self._log({"op": np.int64(3)}, seq)
            self._kill(seq, "mutable.append")
            self._compact()
            self._op_seq = seq
            self._kill(seq, "mutable.commit")
            self._snapshot()

    # -- mutation internals -------------------------------------------------

    def _apply_append(self, raw: np.ndarray) -> list[int]:
        self._coerce_rows(raw)  # replay path: sets kind/m/meta
        self._init_arrays()
        rb = raw.shape[0]
        rbp = _p2(max(8, rb))
        br = self.block_rows
        nv0 = self._nv
        self._ensure_capacity(nv0 + rbp)
        nb = self._ncap // br
        # window of blocks whose stats the delta can touch (+2, not +1:
        # a sub-block delta can still straddle a block boundary)
        wb = min(nb, rbp // br + 2)
        w0 = max(0, min(nv0 // br, nb - wb)) * br

        if self.is_sparse:
            sp = from_dense(raw)
            self._widen_cap(sp.cap)
            if sp.cap < self._cap:
                sp = SparseCorpus(
                    jnp.pad(sp.indices, ((0, 0), (0, self._cap - sp.cap))),
                    jnp.pad(sp.values, ((0, 0), (0, self._cap - sp.cap))),
                    sp.nnz, self._m,
                )
            spn = normalize_sparse(sp)
            didx = jnp.pad(spn.indices, ((0, rbp - rb), (0, 0)))
            dval = jnp.pad(spn.values, ((0, rbp - rb), (0, 0)))
            dnnz = jnp.pad(spn.nnz, (0, rbp - rb))
            (self._idx, self._val, self._nnz,
             self._maxw, self._mw, self._mnnz) = _update_sparse(
                self._idx, self._val, self._nnz,
                self._maxw, self._mw, self._mnnz,
                didx, dval, dnnz, jnp.int32(nv0), jnp.int32(w0),
                block_rows=br, wb=wb, m=self._m,
            )
            Qp = jnp.pad(to_dense(spn), ((0, rbp - rb), (0, 0)))
            depth = self._cap
        else:
            deltan = np.asarray(
                normalize_rows(jnp.asarray(raw, jnp.float32))
            )
            deltap = np.zeros((rbp, self._mlanes), np.float32)
            deltap[:rb, : self._m] = deltan
            deltap = jnp.asarray(deltap)
            self._C, self._maxw, self._mw, self._mnnz = _update_dense(
                self._C, self._maxw, self._mw, self._mnnz,
                deltap, jnp.int32(nv0), jnp.int32(w0),
                block_rows=br, wb=wb,
            )
            Qp = deltap
            depth = self._mlanes

        gids = list(range(self._next_gid, self._next_gid + rb))
        self._gids[nv0:nv0 + rb] = gids
        self._live[nv0:nv0 + rb] = True
        for g, p in zip(gids, range(nv0, nv0 + rb)):
            self._phys[g] = p
        self._next_gid += rb
        self._nv = nv0 + rb
        self.version += 1

        # ---- forward join: new rows × all live rows (incl. new) ----
        t = self.threshold
        bqf = min(rbp, br)
        gqf = rbp // bqf
        mask = np.asarray(_query_mask(
            Qp, self._stats(), threshold=t, block_q=bqf,
            use_minsize=True, normalized=True,
        )[0])
        col_any = self._live.reshape(nb, br).any(axis=1)
        qpos_f = np.full(rbp, -1, np.int32)
        qpos_f[:rb] = nv0 + np.arange(rb)
        wlf = compact_rect_worklist(mask & col_any[None, :])
        tf = 0
        if wlf is not None:
            tf = wlf.shape[1]
            ij, tv = pad_worklist(wlf)
            args = (jnp.asarray(self._live), jnp.asarray(qpos_f),
                    jnp.asarray(ij), jnp.asarray(tv))
            inner_kwargs = dict(
                threshold=t, k=self.k, block_q=bqf, block_c=br, grid_q=gqf,
            )
            if self.is_sparse:
                obs_compile.offer_capture(
                    "mutable.sparse_inner", _mut_sparse_inner,
                    Qp, self._idx, self._val, *args, **inner_kwargs,
                )
                fv, fi, fc = _mut_sparse_inner(
                    Qp, self._idx, self._val, *args, **inner_kwargs,
                )
            else:
                obs_compile.offer_capture(
                    "mutable.dense_inner", _mut_dense_inner,
                    Qp, self._C, *args, **inner_kwargs,
                )
                fv, fi, fc = _mut_dense_inner(
                    Qp, self._C, *args, **inner_kwargs,
                )
            pv = np.asarray(fv)[:rb]
            pi = np.asarray(fi)[:rb]
            pc = np.asarray(fc)[:rb]
        else:
            pv = np.full((rb, self.k), -np.inf, np.float32)
            pi = np.full((rb, self.k), -1, np.int32)
            pc = np.zeros(rb, np.int32)
        self._gv[nv0:self._nv] = pv
        self._gi[nv0:self._nv] = self._gid_of(pi)
        self._gc[nv0:self._nv] = pc

        # ---- reverse join: live OLD rows × new rows ----
        tr = 0
        if nv0 > 0:
            old_live = self._live.copy()
            old_live[nv0:] = False
            if old_live.any():
                mask_s = np.asarray(_self_mask(
                    self._maxw, self._mw, self._mnnz,
                    threshold=t, use_minsize=True,
                ))
                row_any_old = old_live.reshape(nb, br).any(axis=1)
                col_new = np.zeros(nb, bool)
                col_new[nv0 // br:(self._nv - 1) // br + 1] = True
                wlr = compact_rect_worklist(
                    mask_s & row_any_old[:, None] & col_new[None, :]
                )
                if wlr is not None:
                    tr = wlr.shape[1]
                    col_live_rev = np.zeros(self._ncap, bool)
                    col_live_rev[nv0:self._nv] = True
                    ij, tv = pad_worklist(wlr)
                    clr = jnp.asarray(col_live_rev)
                    ijj, tvj = jnp.asarray(ij), jnp.asarray(tv)
                    if self.is_sparse:
                        rv, ri, rc = _mut_sparse_self_inner(
                            self._idx, self._val, clr, ijj, tvj,
                            threshold=t, k=self.k, block_c=br, grid_q=nb,
                            m=self._m,
                        )
                    else:
                        rv, ri, rc = _mut_dense_inner(
                            self._C, self._C, clr,
                            jnp.arange(self._ncap, dtype=jnp.int32),
                            ijj, tvj,
                            threshold=t, k=self.k, block_q=br, block_c=br,
                            grid_q=nb,
                        )
                    # merge ONLY into live old rows: new × new is already
                    # covered by the forward join (no double count)
                    rows = np.nonzero(old_live)[0]
                    rv = np.asarray(rv)[rows]
                    ri = self._gid_of(np.asarray(ri)[rows])
                    rc = np.asarray(rc)[rows]
                    v, i = _np_merge(
                        self._gv[rows], self._gi[rows], rv, ri, self.k
                    )
                    self._gv[rows] = v
                    self._gi[rows] = i
                    self._gc[rows] += rc

        if telemetry.enabled():
            total = mask.size + (nb * nb if nv0 > 0 else 0)
            telemetry.record(telemetry.ApssStats(
                variant="serving/delta-join",
                n=self.n, m=self._m, block_rows=br, sparse=self.is_sparse,
                flops=2.0 * (tf * bqf + tr * br) * br * depth,
                live_tiles=tf + tr, total_tiles=total,
                extra={
                    "delta": rb,
                    "live_fraction_rows": self.n / max(1, self._nv),
                    "model_flops": telemetry.delta_join_flops(
                        rb, self.n, depth
                    ),
                },
            ))
        return gids

    def _apply_delete(self, ids: np.ndarray) -> None:
        phys = np.array([self._phys[int(g)] for g in ids], np.int64)
        dead_set = {int(g) for g in ids}
        # A deleted row whose exact count exceeds k has neighbors missing
        # from its buffer — the affected set is unknowable, so rescore
        # every surviving row (exactness beats delta cost here).
        full_rescore = bool(np.any(self._gc[phys] > self.k))
        if full_rescore:
            affected = [
                int(g) for g in self._phys if int(g) not in dead_set
            ]
        else:
            neigh: set[int] = set()
            for p in phys:
                neigh.update(
                    int(g) for g in self._gi[p] if g >= 0
                )
            affected = [
                g for g in neigh
                if g not in dead_set and g in self._phys
            ]
        # tombstone + zero device rows (zeroed rows keep stale stats sound:
        # stats stay upper bounds over a superset)
        self._live[phys] = False
        self._gids[phys] = -1
        for g in ids:
            del self._phys[int(g)]
        self._ndead += int(phys.shape[0])
        pp = np.full(_p2(max(8, phys.shape[0])), self._ncap, np.int64)
        pp[: phys.shape[0]] = phys
        ppj = jnp.asarray(pp, jnp.int32)
        if self.is_sparse:
            self._idx = _zero_rows(self._idx, ppj)
            self._val = _zero_rows(self._val, ppj)
            self._nnz = _zero_rows(self._nnz, ppj)
        else:
            self._C = _zero_rows(self._C, ppj)
        self._gv[phys] = -np.inf
        self._gi[phys] = -1
        self._gc[phys] = 0
        self.version += 1

        if affected:
            aff_phys = np.sort(
                np.array([self._phys[g] for g in affected], np.int64)
            )
            na = aff_phys.shape[0]
            abp = _p2(max(8, na))
            idxp = np.zeros(abp, np.int32)
            idxp[:na] = aff_phys
            qpos = np.full(abp, -1, np.int32)
            qpos[:na] = aff_phys
            ij_take = jnp.asarray(idxp)
            if self.is_sparse:
                qi = jnp.take(self._idx, ij_take, axis=0)
                qv = jnp.take(self._val, ij_take, axis=0)
                r = jnp.arange(abp, dtype=jnp.int32)[:, None]
                Qa = jnp.zeros((abp, self._m), jnp.float32).at[r, qi].add(qv)
            else:
                Qa = jnp.take(self._C, ij_take, axis=0)
            bqa = min(abp, self.block_rows)
            gqa = abp // bqa
            nb = self._ncap // self.block_rows
            mask = np.asarray(_query_mask(
                Qa, self._stats(), threshold=self.threshold, block_q=bqa,
                use_minsize=True, normalized=True,
            )[0])
            col_any = self._live.reshape(nb, self.block_rows).any(axis=1)
            wl = compact_rect_worklist(mask & col_any[None, :])
            if wl is not None:
                ij, tv = pad_worklist(wl)
                args = (jnp.asarray(self._live), jnp.asarray(qpos),
                        jnp.asarray(ij), jnp.asarray(tv))
                if self.is_sparse:
                    fv, fi, fc = _mut_sparse_inner(
                        Qa, self._idx, self._val, *args,
                        threshold=self.threshold, k=self.k, block_q=bqa,
                        block_c=self.block_rows, grid_q=gqa,
                    )
                else:
                    fv, fi, fc = _mut_dense_inner(
                        Qa, self._C, *args,
                        threshold=self.threshold, k=self.k, block_q=bqa,
                        block_c=self.block_rows, grid_q=gqa,
                    )
                nv_ = np.asarray(fv)[:na]
                ni = self._gid_of(np.asarray(fi)[:na])
                nc = np.asarray(fc)[:na]
            else:
                nv_ = np.full((na, self.k), -np.inf, np.float32)
                ni = np.full((na, self.k), -1, np.int64)
                nc = np.zeros(na, np.int64)
            # REPLACE the affected rows: a fresh canonical rescore equals
            # what a from-scratch rebuild would compute for them
            self._gv[aff_phys] = nv_
            self._gi[aff_phys] = ni
            self._gc[aff_phys] = nc

        if self._nv and self._ndead / self._nv >= self.compact_threshold:
            self._compact()

    def _compact(self) -> None:
        """Pack live rows contiguously in physical order (gid order) and
        rebuild exact stats. No rescoring: row contents, gids, and the
        graph are all preserved — only physical positions change, and
        order-preservation keeps the canonical tie-break intact."""
        self._init_arrays()
        order = np.nonzero(self._live)[0]
        nl = order.shape[0]
        ncap, br = self._ncap, self.block_rows
        if self.is_sparse:
            si = np.zeros((ncap, self._cap), np.int32)
            sv = np.zeros((ncap, self._cap), np.float32)
            sn = np.zeros(ncap, np.int32)
            si[:nl] = np.asarray(self._idx)[order]
            sv[:nl] = np.asarray(self._val)[order]
            sn[:nl] = np.asarray(self._nnz)[order]
            self._idx = jnp.asarray(si)
            self._val = jnp.asarray(sv)
            self._nnz = jnp.asarray(sn)
            st = _full_sparse_stats(
                self._idx, self._val, self._nnz, block_rows=br, m=self._m
            )
        else:
            C = np.zeros((ncap, self._mlanes), np.float32)
            C[:nl] = np.asarray(self._C)[order]
            self._C = jnp.asarray(C)
            st = _full_dense_stats(self._C, block_rows=br)
        self._maxw, self._mw, self._mnnz = st.maxw, st.mw, st.max_nnz
        gids = np.full(ncap, -1, np.int64)
        gids[:nl] = self._gids[order]
        live = np.zeros(ncap, bool)
        live[:nl] = True
        gv = np.full((ncap, self.k), -np.inf, np.float32)
        gi = np.full((ncap, self.k), -1, np.int64)
        gc = np.zeros(ncap, np.int64)
        gv[:nl] = self._gv[order]
        gi[:nl] = self._gi[order]
        gc[:nl] = self._gc[order]
        self._gids, self._live = gids, live
        self._gv, self._gi, self._gc = gv, gi, gc
        self._phys = {int(g): p for p, g in enumerate(gids[:nl])}
        self._nv, self._ndead = nl, 0
        self.version += 1
        telemetry.incr("serving.compactions")

    # -- queries ------------------------------------------------------------

    def graph(self) -> tuple[np.ndarray, Matches]:
        """The standing similarity graph over live rows.

        Returns ``(gids, Matches)``: live global ids in physical (== gid)
        order, and per-row top-k matches whose indices are GLOBAL ids
        (int64, −1 padded) with exact counts.
        """
        order = np.nonzero(self._live)[0]
        return self._gids[order].copy(), Matches(
            values=self._gv[order].copy(),
            indices=self._gi[order].copy(),
            counts=self._gc[order].copy(),
        )

    def as_index(self) -> APSSIndex:
        """A read-only :class:`APSSIndex` view for the kernel query path
        (dense only; zero-copy — dead rows are already zeroed)."""
        if self.is_sparse:
            raise NotImplementedError(
                "sparse kernel path needs the per-block support compaction, "
                "which is not layout-stable under mutation; use the XLA path"
            )
        if self._view is None or self._view_version != self.version:
            self._view = APSSIndex(
                self._C, self._stats(), None, None,
                n=self._nv, m=self._m, block_rows=self.block_rows,
                kind="dense", normalized=True,
            )
            self._view_version = self.version
        return self._view

    def query(
        self,
        Q,
        threshold: float | None = None,
        k: int | None = None,
        *,
        block_q: int | None = None,
        use_kernel: bool = False,
        use_minsize: bool = True,
        interpret: bool | None = None,
    ) -> Matches:
        """Top-k live neighbors for a dense query batch ``(B, m)``.

        Returns host Matches whose indices are GLOBAL ids (int64). The XLA
        path masks dead rows explicitly (sound at any threshold); the
        kernel path serves through :meth:`as_index`, where dead rows are
        merely zero vectors, so it requires ``threshold > 0``.
        """
        t = self.threshold if threshold is None else float(threshold)
        kk = self.k if k is None else int(k)
        if isinstance(Q, SparseCorpus):
            Q = to_dense(Q)
        Q = np.asarray(Q, np.float32)
        if Q.ndim != 2 or (self._m is not None and Q.shape[1] != self._m):
            raise ValueError(f"Q must be (B, {self._m}); got {Q.shape}")
        B = Q.shape[0]
        if self.n == 0 or B == 0:
            return Matches(
                values=np.full((B, kk), -np.inf, np.float32),
                indices=np.full((B, kk), -1, np.int64),
                counts=np.zeros(B, np.int32),
            )
        if use_kernel:
            if t <= 0:
                raise ValueError(
                    "use_kernel requires threshold > 0: the kernel view "
                    "cannot mask tombstoned (zeroed) rows, which match "
                    "everything at t <= 0"
                )
            m = query_topk(
                self.as_index(), jnp.asarray(Q), t, kk,
                block_q=block_q or 128, use_kernel=True,
                use_minsize=use_minsize, interpret=interpret,
            )
            pi = np.asarray(m.indices)
            return Matches(
                values=np.asarray(m.values),
                indices=self._gid_of(pi),
                counts=np.asarray(m.counts),
            )
        br = self.block_rows
        Bp = _p2(max(8, B))
        bq = min(Bp, _p2(block_q) if block_q else br, br)
        gq = Bp // bq
        width = self._m if self.is_sparse else self._mlanes
        Qp = np.zeros((Bp, width), np.float32)
        Qp[:B, : self._m] = Q
        Qp = jnp.asarray(Qp)
        nb = self._ncap // br
        mask = np.asarray(_query_mask(
            Qp, self._stats(), threshold=t, block_q=bq,
            use_minsize=use_minsize, normalized=True,
        )[0])
        col_any = self._live.reshape(nb, br).any(axis=1)
        wl = compact_rect_worklist(mask & col_any[None, :])
        if wl is None:
            return Matches(
                values=np.full((B, kk), -np.inf, np.float32),
                indices=np.full((B, kk), -1, np.int64),
                counts=np.zeros(B, np.int32),
            )
        ij, tv = pad_worklist(wl)
        args = (
            jnp.asarray(self._live),
            jnp.full((Bp,), -1, jnp.int32),
            jnp.asarray(ij), jnp.asarray(tv),
        )
        if self.is_sparse:
            fv, fi, fc = _mut_sparse_inner(
                Qp, self._idx, self._val, *args,
                threshold=t, k=kk, block_q=bq, block_c=br, grid_q=gq,
            )
        else:
            fv, fi, fc = _mut_dense_inner(
                Qp, self._C, *args,
                threshold=t, k=kk, block_q=bq, block_c=br, grid_q=gq,
            )
        return Matches(
            values=np.asarray(fv)[:B],
            indices=self._gid_of(np.asarray(fi)[:B]),
            counts=np.asarray(fc)[:B],
        )
