"""APSSIndex: every corpus-side support structure, built once.

Today's self-join paths rebuild block maxima, posting-list supports and
``bdims``/``bx`` compaction on every call — fine for one batch job, wasteful
for a server answering a stream of queries against a FIXED corpus. DISCO
(Zadeh & Goel 2012) and the adaptive similarity-join line both land on the
same conclusion: amortize index construction, adapt candidate generation.

:class:`APSSIndex` is a registered pytree holding

- the (row-normalized, block-padded) corpus — dense array or the padded-CSR
  triple of :class:`~repro.core.sparse.SparseCorpus`,
- :class:`~repro.core.pruning.BlockStats` — per-block per-dimension
  maxweight vectors (whose support IS the tile-granular posting lists /
  inverted index), per-block max weight, and exact per-block max nnz for
  the minsize bound,
- for sparse corpora, the per-block support compaction ``bdims (nb, S)`` /
  ``bx (nb, bm, S)`` consumed by the CSR tile kernels,

so :func:`~repro.serving.query.query_topk` can evaluate the paper's bounds
query-side only and score live tiles straight away. Static metadata
(block size, valid row count, mesh placement) rides the pytree aux field,
so jit'd consumers retrace only when the corpus *shape* changes — never
per query.

With ``mesh=``, corpus rows are ``device_put``-sharded over ``axis_name``
(``P(axis, None)`` — block-aligned row shards) and queries are served by
the per-shard scoring path (``query.py``): each device scores the
replicated query batch against its local shard; partial top-k results are
merged host-side. The small block stats stay REPLICATED (``P()``), which
is what makes sharded-query pruning work: the host evaluates the global
bounds once, slices each shard's block range (:meth:`shard_block_range`)
out of the live mask, and ships every device its own compacted worklist —
a shard scores only its live tiles, never its whole local corpus.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.apss import normalize_rows, pad_rows
from repro.core.pruning import (
    BlockStats,
    dense_block_stats,
    sparse_block_stats,
)
from repro.core.sparse import (
    SparseCorpus,
    normalize_sparse,
    pad_rows_sparse,
)
from repro.kernels.apss_block.sparse import block_support_gather


@jax.tree_util.register_pytree_node_class
class APSSIndex:
    """Build-once retrieval index over a fixed corpus (see module doc).

    Children (traced): corpus leaves, block stats, support compaction.
    Aux (static): ``n`` valid rows, ``m`` dims, ``block_rows``, ``kind``
    (``"dense"``/``"sparse"``), ``normalized``, mesh placement.
    """

    def __init__(
        self,
        corpus,              # (ncp, m) dense | (indices, values, nnz) CSR triple
        stats: BlockStats,   # corpus-side block pruning summaries
        bdims,               # (nb, S) i32 sparse support lists | None
        bx,                  # (nb, bm, S) f32 support-densified blocks | None
        *,
        n: int,
        m: int,
        block_rows: int,
        kind: str,
        normalized: bool,
        mesh: Optional[Mesh] = None,
        axis_name: str = "data",
    ):
        self.corpus = corpus
        self.stats = stats
        self.bdims = bdims
        self.bx = bx
        self.n = int(n)
        self.m = int(m)
        self.block_rows = int(block_rows)
        self.kind = kind
        self.normalized = bool(normalized)
        self.mesh = mesh
        self.axis_name = axis_name

    def tree_flatten(self):
        children = (self.corpus, self.stats, self.bdims, self.bx)
        aux = (
            self.n, self.m, self.block_rows, self.kind, self.normalized,
            self.mesh, self.axis_name,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        n, m, block_rows, kind, normalized, mesh, axis_name = aux
        corpus, stats, bdims, bx = children
        return cls(
            corpus, stats, bdims, bx,
            n=n, m=m, block_rows=block_rows, kind=kind,
            normalized=normalized, mesh=mesh, axis_name=axis_name,
        )

    @property
    def is_sparse(self) -> bool:
        return self.kind == "sparse"

    @property
    def n_padded(self) -> int:
        if self.is_sparse:
            return self.corpus[0].shape[0]
        return self.corpus.shape[0]

    @property
    def n_blocks(self) -> int:
        return self.n_padded // self.block_rows

    @property
    def n_shards(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.shape[self.axis_name])

    @property
    def nb_local(self) -> int:
        """Corpus blocks owned by each shard (= ``n_blocks`` unsharded).

        Build-time padding is to ``p · block_rows`` multiples, so the
        division is always exact and shard ``s`` owns the contiguous global
        block range ``[s · nb_local, (s+1) · nb_local)``.
        """
        return self.n_blocks // self.n_shards

    def shard_block_range(self, s: int) -> tuple[int, int]:
        """Global ``[lo, hi)`` corpus-block ids owned by shard ``s``.

        The corpus-side :class:`BlockStats` are replicated (``P()``), so the
        query path slices this range out of the GLOBAL live mask to compact
        a per-shard worklist — each device then scores only its own live
        tiles instead of every local tile (``query._sharded_query``).
        """
        lo = s * self.nb_local
        return lo, lo + self.nb_local

    def stats_host(self) -> tuple[np.ndarray, np.ndarray]:
        """Host copies of the small per-block stat vectors ``(mw, max_nnz)``.

        Cached on the instance: the per-batch query planner
        (``planner.costmodel.plan_query_topk``) consults these exact bounds
        on every plan decision — zero sampling, but also zero device
        round-trips after the first call.
        """
        cached = getattr(self, "_stats_host", None)
        if cached is None:
            cached = (
                np.asarray(self.stats.mw),
                np.asarray(self.stats.max_nnz),
            )
            self._stats_host = cached
        return cached

    def sparse_corpus(self) -> SparseCorpus:
        """The padded corpus as a :class:`SparseCorpus` view (sparse kind)."""
        assert self.is_sparse, "dense index has no CSR triple"
        idx, val, nnz = self.corpus
        return SparseCorpus(idx, val, nnz, self.m)

    def __repr__(self) -> str:
        placed = f", sharded={self.axis_name}" if self.mesh is not None else ""
        return (
            f"APSSIndex(kind={self.kind}, n={self.n}, m={self.m}, "
            f"block_rows={self.block_rows}{placed})"
        )


def build_index(
    corpus,
    *,
    block_rows: int = 256,
    normalize: bool = True,
    assume_normalized: bool = True,
    mesh: Optional[Mesh] = None,
    axis_name: str = "data",
    lane_pad: int = 128,
    plan=None,
) -> APSSIndex:
    """Build every corpus-side structure ONCE (host + one XLA pass).

    ``corpus`` is a dense ``(n, m)`` array or a
    :class:`~repro.core.sparse.SparseCorpus`. Rows are L2-normalized and
    padded to ``block_rows`` (to ``p · block_rows`` under a mesh, so every
    shard is block-aligned).

    ``normalize=False`` skips the normalization pass for corpora whose
    rows are ALREADY unit-norm (the repo's generators and ``normalize_*``
    outputs); ``assume_normalized`` then records whether that's true — it
    gates the minsize pruning bound, which needs ``||y|| = 1``
    (``core.pruning``). Pass ``assume_normalized=False`` only for a
    genuinely unnormalized corpus served as-is (weaker pruning, still
    exact).

    This is the ONLY place serving-side support structures are computed;
    ``query_topk`` consumes the returned pytree and never rebuilds
    (asserted by ``tests/test_serving.py`` via trace counters).

    ``plan=`` takes a planner decision (a ``planner.Plan`` or bare
    ``VariantConfig``): its ``block_rows`` becomes the index block size and
    the corpus is converted to the planned representation (dense ↔ padded
    CSR) before building — so ``build_index(corpus,
    plan=plan_apss(corpus, t, k))`` materializes the layout the cost model
    actually priced.
    """
    if plan is not None:
        cfg = getattr(plan, "config", plan)
        block_rows = cfg.block_rows
        if cfg.sparse and not isinstance(corpus, SparseCorpus):
            from repro.core.sparse import from_dense

            corpus = from_dense(np.asarray(corpus))
        elif not cfg.sparse and isinstance(corpus, SparseCorpus):
            from repro.core.sparse import to_dense

            corpus = to_dense(corpus)
    normalized = True if normalize else assume_normalized
    if isinstance(corpus, SparseCorpus):
        return _build_sparse(
            corpus, block_rows=block_rows, normalize=normalize,
            normalized=normalized, mesh=mesh, axis_name=axis_name,
            lane_pad=lane_pad,
        )
    return _build_dense(
        jnp.asarray(corpus), block_rows=block_rows, normalize=normalize,
        normalized=normalized, mesh=mesh, axis_name=axis_name,
    )


def _row_multiple(block_rows: int, mesh, axis_name: str) -> int:
    if mesh is None:
        return block_rows
    return block_rows * mesh.shape[axis_name]


def _build_dense(
    C, *, block_rows, normalize, normalized, mesh, axis_name
) -> APSSIndex:
    n, m = C.shape
    if normalize:
        C = normalize_rows(C)
    Cp, _ = pad_rows(C, _row_multiple(block_rows, mesh, axis_name))
    # Lane-pad the feature axis ONCE to the kernel tile the query path will
    # pick, so per-query scoring never touches corpus-sized memory (the
    # jitted inner's pad becomes a no-op). Zero columns change no score,
    # bound, or nnz count.
    from repro.kernels.apss_block.ops import _pick_bk

    bk = _pick_bk(m, 512)
    remk = (-m) % bk
    if remk:
        Cp = jnp.pad(Cp, ((0, 0), (0, remk)))
    stats = dense_block_stats(Cp, block_rows)
    if mesh is not None:
        Cp = jax.device_put(Cp, NamedSharding(mesh, P(axis_name, None)))
        stats = jax.device_put(stats, NamedSharding(mesh, P()))
    return APSSIndex(
        Cp, stats, None, None,
        n=n, m=m, block_rows=block_rows, kind="dense",
        normalized=normalized, mesh=mesh, axis_name=axis_name,
    )


def _build_sparse(
    sp, *, block_rows, normalize, normalized, mesh, axis_name, lane_pad
) -> APSSIndex:
    n = sp.n
    if normalize:
        sp = normalize_sparse(sp)
    spp, _ = pad_rows_sparse(sp, _row_multiple(block_rows, mesh, axis_name))
    stats = sparse_block_stats(spp, block_rows)
    if mesh is not None:
        # Sharded placement: the CSR triple splits over row blocks; the
        # per-shard scoring path gathers only its LIVE CSR blocks (worklist
        # from the replicated stats) with gather_dot, so the
        # (replicated-size) bdims/bx compaction is not built at all.
        sharded = NamedSharding(mesh, P(axis_name, None))
        triple = (
            jax.device_put(spp.indices, sharded),
            jax.device_put(spp.values, sharded),
            jax.device_put(spp.nnz, NamedSharding(mesh, P(axis_name))),
        )
        stats = jax.device_put(stats, NamedSharding(mesh, P()))
        bdims = bx = None
    else:
        bdims, bx = block_support_gather(spp, block_rows, pad_to=lane_pad)
        triple = (spp.indices, spp.values, spp.nnz)
    return APSSIndex(
        triple, stats, bdims, bx,
        n=n, m=sp.m, block_rows=block_rows, kind="sparse",
        normalized=normalized, mesh=mesh, axis_name=axis_name,
    )


def index_nbytes(index: APSSIndex) -> int:
    """Total bytes across index leaves (benchmark accounting)."""
    return int(
        sum(
            np.asarray(x).nbytes
            for x in jax.tree_util.tree_leaves(index)
        )
    )
