"""plan_apss: turn variant choice from folklore into a measured decision.

The paper's closing finding — "the performance depends on the dataset,
therefore a variety of parallelizations is useful" — left the *choice*
among the variety to the caller. This module closes that loop:

1. :func:`summarize_corpus` samples the corpus (never densifying a sparse
   one): density, realized row cap, Zipf skew of the posting-list
   histogram, and the live-tile fraction + per-block histogram of the
   paper's pruning bounds at the query threshold.
2. :func:`candidate_configs` enumerates every valid
   ``(variant, block_rows, use_kernel)`` configuration for the given mesh
   (divisibility and backend constraints applied here, not at dispatch).
3. :func:`plan_apss` prices each candidate with the closed-form cost
   models (``planner.costmodel``, parameterized by the calibrated
   hardware profile) and returns a ranked :class:`Plan`; with
   ``autotune=True`` the best-predicted config of each of the top
   ``autotune_top`` (default 3) variant families is additionally
   microbenchmarked and the measured winner is chosen.

``core.apss.similarity_topk(..., variant="auto")``,
``core.distributed.apss(..., distribution="auto")`` and
``serving.build_index(..., plan=...)`` dispatch through here.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.obs import trace
from repro.planner import calibrate as _calibrate
from repro.planner.costmodel import (
    CalibrationProfile,
    CorpusSummary,
    CostEstimate,
    VariantConfig,
    estimate_cost,
)


# ---------------------------------------------------------------------------
# Corpus summary — sampled statistics, never densified
# ---------------------------------------------------------------------------


def _fit_zipf(hist: np.ndarray) -> float:
    """Least-squares Zipf exponent of a posting-list (document-frequency)
    histogram: slope of log(freq) vs log(rank) over the populated lists."""
    freq = np.sort(hist[hist > 0])[::-1].astype(np.float64)
    if freq.size < 4 or freq[0] == freq[-1]:
        return 0.0
    rank = np.arange(1, freq.size + 1, dtype=np.float64)
    x, y = np.log(rank), np.log(freq)
    slope = float(np.polyfit(x, y, 1)[0])
    return float(np.clip(-slope, 0.0, 4.0))


def _sample_rows(n: int, sample_rows: int, seed: int) -> np.ndarray:
    if n <= sample_rows:
        return np.arange(n)
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=sample_rows, replace=False))


def _live_profile(stats, threshold: float) -> tuple[float, tuple[int, ...]]:
    """Self-join live fraction + per-row-block live counts from BlockStats."""
    import numpy as _np

    from repro.core.pruning import live_tile_mask

    mask = _np.asarray(live_tile_mask(stats, stats, threshold))
    return float(mask.mean()), tuple(int(c) for c in mask.sum(axis=1))


def summarize_corpus(
    corpus,
    threshold: float,
    *,
    sample_rows: int = 2048,
    stats_block: int = 64,
    seed: int = 0,
) -> CorpusSummary:
    """Sampled planner-side statistics (see module doc).

    ``corpus`` is a dense ``(n, m)`` array, a
    :class:`~repro.core.sparse.SparseCorpus`, or a prebuilt
    :class:`~repro.serving.index.APSSIndex` (whose corpus-side
    :class:`~repro.core.pruning.BlockStats` give the live profile exactly,
    with no sampling pass at all).
    """
    from repro.core.sparse import SparseCorpus
    from repro.serving.index import APSSIndex

    if isinstance(corpus, APSSIndex):
        return _summarize_index(corpus, threshold)
    if isinstance(corpus, SparseCorpus):
        return _summarize_sparse(
            corpus, threshold, sample_rows=sample_rows,
            stats_block=stats_block, seed=seed,
        )
    return _summarize_dense(
        corpus, threshold, sample_rows=sample_rows,
        stats_block=stats_block, seed=seed,
    )


def _summarize_sparse(sp, threshold, *, sample_rows, stats_block, seed):
    import jax.numpy as jnp

    from repro.core.pruning import sparse_block_stats
    from repro.core.sparse import SparseCorpus, pad_rows_sparse

    nnz = np.asarray(sp.nnz)
    n, m = sp.n, sp.m
    sel = _sample_rows(n, sample_rows, seed)
    idx = np.asarray(sp.indices)[sel]
    val = np.asarray(sp.values)[sel]
    valid = np.arange(sp.cap)[None, :] < nnz[sel, None]
    hist = np.bincount(idx[valid].ravel(), minlength=m) if valid.any() else np.zeros(m)
    sub = SparseCorpus(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(nnz[sel]), m
    )
    bs = min(stats_block, max(1, len(sel)))
    sub, _ = pad_rows_sparse(sub, bs)
    live, tiles = _live_profile(sparse_block_stats(sub, bs), threshold)
    return CorpusSummary(
        n=n, m=m, threshold=float(threshold), sparse_input=True,
        density=float(nnz.sum()) / float(n * m), cap=sp.cap,
        avg_nnz=float(nnz.mean()), zipf_alpha=_fit_zipf(hist),
        live_fraction=live, tile_counts=tiles, itemsize=4,
    )


def _summarize_dense(D, threshold, *, sample_rows, stats_block, seed):
    import jax.numpy as jnp

    from repro.core.pruning import dense_block_stats

    n, m = D.shape
    itemsize = np.dtype(D.dtype).itemsize if np.dtype(D.dtype).itemsize in (2, 4) else 4
    sel = _sample_rows(n, sample_rows, seed)
    S = np.asarray(D[np.asarray(sel)], np.float32)
    nnzs = (S != 0).sum(axis=1)
    hist = (S != 0).sum(axis=0)
    bs = min(stats_block, max(1, len(sel)))
    rem = (-len(sel)) % bs
    Sp = np.pad(S, ((0, rem), (0, 0))) if rem else S
    live, tiles = _live_profile(dense_block_stats(jnp.asarray(Sp), bs), threshold)
    return CorpusSummary(
        n=n, m=m, threshold=float(threshold), sparse_input=False,
        density=float(nnzs.mean()) / float(m), cap=int(max(1, nnzs.max(initial=1))),
        avg_nnz=float(nnzs.mean()), zipf_alpha=_fit_zipf(hist),
        live_fraction=live, tile_counts=tiles, itemsize=itemsize,
    )


def _summarize_index(index, threshold) -> CorpusSummary:
    live, tiles = _live_profile(index.stats, threshold)
    if index.is_sparse:
        idx_arr, _, nnz = index.corpus
        nnz = np.asarray(nnz)[: index.n]
        m = index.m
        valid = np.arange(idx_arr.shape[1])[None, :] < nnz[:, None]
        hist = np.bincount(
            np.asarray(idx_arr)[: index.n][valid].ravel(), minlength=m
        )
        return CorpusSummary(
            n=index.n, m=m, threshold=float(threshold), sparse_input=True,
            density=float(nnz.sum()) / float(index.n * m),
            cap=int(idx_arr.shape[1]), avg_nnz=float(nnz.mean()),
            zipf_alpha=_fit_zipf(hist), live_fraction=live,
            tile_counts=tiles, itemsize=4,
        )
    D = np.asarray(index.corpus)[: index.n, : index.m]
    nnzs = (D != 0).sum(axis=1)
    return CorpusSummary(
        n=index.n, m=index.m, threshold=float(threshold), sparse_input=False,
        density=float(nnzs.mean()) / float(index.m),
        cap=int(max(1, nnzs.max(initial=1))), avg_nnz=float(nnzs.mean()),
        zipf_alpha=_fit_zipf((D != 0).sum(axis=0)), live_fraction=live,
        tile_counts=tiles, itemsize=4,
    )


# ---------------------------------------------------------------------------
# Candidate enumeration (validity constraints live HERE, not at dispatch)
# ---------------------------------------------------------------------------

# Densifying a sparse corpus for a dense variant is capped at this many
# bytes — beyond it the dense representation is not a candidate at all.
MAX_DENSIFY_BYTES = 512 * 1024 * 1024

# A dense input is only offered sparse candidates below this density
# (above it padded CSR stores ~the dense array with extra indices).
SPARSE_REP_MAX_DENSITY = 0.25


def candidate_configs(
    s: CorpusSummary,
    mesh=None,
    k: int = 32,
    *,
    block_rows_choices: Sequence[int] = (128, 256, 512),
    include_kernel: Optional[bool] = None,
) -> list[VariantConfig]:
    """Every valid configuration for this corpus/mesh (see module doc)."""
    if include_kernel is None:
        from repro.kernels.apss_block.ops import _on_tpu

        include_kernel = _on_tpu()
    reps: list[bool] = []
    if s.sparse_input or s.density <= SPARSE_REP_MAX_DENSITY:
        reps.append(True)
    if not s.sparse_input or s.n * s.m * 4 <= MAX_DENSIFY_BYTES:
        reps.append(False)

    blocks = [b for b in dict.fromkeys(block_rows_choices) if b <= max(s.n, 1)]
    blocks = blocks or [min(128, s.n)]
    cfgs: list[VariantConfig] = []
    for sparse in reps:
        for b in blocks:
            cfgs.append(VariantConfig("blocked", sparse, b, use_kernel=False))
            if include_kernel:
                cfgs.append(VariantConfig("blocked", sparse, b, use_kernel=True))
    if mesh is None:
        return cfgs

    names = tuple(mesh.axis_names)
    sizes = dict(mesh.shape)
    p = 1
    for v in sizes.values():
        p *= v
    if p <= 1 or s.n % p:
        return cfgs

    for sparse in reps:
        kern = [False] + ([True] if include_kernel and not sparse else [])
        for b in blocks:
            if len(names) == 1:
                for sched in ("allgather", "ring", "halfring"):
                    for uk in kern:
                        cfgs.append(
                            VariantConfig(
                                "horizontal", sparse, b, use_kernel=uk,
                                schedule=sched,
                            )
                        )
            else:
                for uk in kern:
                    cfgs.append(
                        VariantConfig("hierarchical", sparse, b, use_kernel=uk)
                    )
        if len(names) == 1 and (sparse or s.m % p == 0):
            # dense shards P(None, axis) columns, so m and n must divide;
            # sparse deals posting lists to the shards and pads rows to the
            # block, so any m and n go
            for b in blocks:
                if not sparse and s.n % b:
                    continue
                for acc in ("allreduce", "scatter", "compressed", "recursive"):
                    if acc == "scatter" and b % p:
                        continue
                    if acc == "recursive" and p & (p - 1):
                        continue
                    cfgs.append(
                        VariantConfig(
                            "vertical", sparse, b, accumulation=acc,
                        )
                    )
    if len(names) == 2:
        q, r = sizes[names[0]], sizes[names[1]]
        # Both representations split the dimension axis r ways: dense as
        # P(row, col) column shards (m must divide), sparse as shard_dims
        # posting lists (any m).
        if s.n % q == 0:
            n_loc = s.n // q
            for sparse in reps:
                if not sparse and s.m % r:
                    continue
                for b in blocks:
                    for acc in ("allreduce", "compressed"):
                        cfgs.append(
                            VariantConfig(
                                "2d", sparse, min(b, n_loc), accumulation=acc,
                            )
                        )
    return list(dict.fromkeys(cfgs))


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def _index_valid_corpus(index):
    """The index's corpus restricted to its VALID rows/dims (indexes pad
    rows to the block multiple and lane-pad dense feature axes; planning
    and dispatch must see the real ``(n, m)`` — phantom padded rows would
    leak into results and break the n-divisibility gates)."""
    from repro.core.sparse import SparseCorpus

    if index.is_sparse:
        idx, val, nnz = index.corpus
        return SparseCorpus(
            idx[: index.n], val[: index.n], nnz[: index.n], index.m
        )
    return index.corpus[: index.n, : index.m]


def _to_representation(corpus, sparse: bool):
    """Convert the corpus to the representation a config wants (host-side,
    one-off — conversion cost is not part of the per-call model)."""
    import jax.numpy as jnp

    from repro.core.sparse import SparseCorpus, from_dense, to_dense

    if isinstance(corpus, SparseCorpus):
        return corpus if sparse else to_dense(corpus)
    return from_dense(np.asarray(corpus)) if sparse else jnp.asarray(corpus)


def _dispatch(cfg: VariantConfig, data, threshold: float, k: int, mesh):
    """Raw variant dispatch (``data`` already in the config's representation)."""
    from repro.core.apss import apss_blocked
    from repro.core import distributed

    if cfg.kind == "blocked":
        return apss_blocked(
            data, threshold, k, block_rows=cfg.block_rows,
            use_kernel=cfg.use_kernel,
        )
    if mesh is None:
        raise ValueError(f"config {cfg.name} needs a mesh")
    names = tuple(mesh.axis_names)
    if cfg.kind == "horizontal":
        axis = names[0] if len(names) == 1 else names
        return distributed.apss_horizontal(
            data, threshold, k, mesh, axis, schedule=cfg.schedule,
            block_rows=cfg.block_rows, use_kernel=cfg.use_kernel,
        )
    if cfg.kind == "hierarchical":
        return distributed.apss_horizontal_hierarchical(
            data, threshold, k, mesh, names, block_rows=cfg.block_rows,
            use_kernel=cfg.use_kernel,
        )
    if cfg.kind == "vertical":
        return distributed.apss_vertical(
            data, threshold, k, mesh, names[-1],
            accumulation=cfg.accumulation, block_rows=cfg.block_rows,
        )
    if cfg.kind == "2d":
        return distributed.apss_2d(
            data, threshold, k, mesh, names[0], names[1],
            accumulation=cfg.accumulation, block_rows=cfg.block_rows,
        )
    raise ValueError(f"unknown variant kind: {cfg.kind}")


def _has_host_stage(cfg: VariantConfig) -> bool:
    """Configs whose dispatch runs host-side stages (worklist compaction,
    the sizing of a dimension split) and therefore cannot be traced under
    jit."""
    if cfg.kind == "blocked" and cfg.sparse and cfg.use_kernel:
        return True  # apss_sparse_compacted: host-compacted worklist
    if cfg.kind in ("vertical", "2d") and cfg.sparse:
        return True  # the dimension split is sized on the host
    return False


def execute(
    cfg: VariantConfig,
    corpus,
    threshold: float,
    k: int = 32,
    mesh=None,
    *,
    prepared: bool = False,
):
    """Run one configuration: representation conversion + jitted dispatch.

    Traceable configs go through one module-level jit (static over the
    frozen ``VariantConfig``), so repeated executions — the autotuner, the
    benchmark, a caller's request loop — pay compilation once per config,
    exactly like the hand-written call sites. Host-staged configs (sparse
    worklist / ``shard_dims``) run eagerly, as they do everywhere else.

    ``prepared=True`` declares ``corpus`` already in the config's
    representation: timed callers (autotune, ``bench_planner``) convert
    once per representation up front, so measurements compare the join the
    cost model prices — not a per-call ``to_dense``/``from_dense``.
    """
    data = corpus if prepared else _to_representation(corpus, cfg.sparse)
    with trace.span("execute", config=cfg.name):
        if _has_host_stage(cfg):
            return _dispatch(cfg, data, threshold, k, mesh)
        return _execute_traced(data, cfg, float(threshold), k, mesh)


@functools.partial(
    jax.jit, static_argnames=("cfg", "threshold", "k", "mesh")
)
def _execute_traced(data, cfg, threshold, k, mesh):
    return _dispatch(cfg, data, threshold, k, mesh)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Plan:
    """A ranked execution decision: chosen config + every priced alternative."""

    config: VariantConfig
    cost: CostEstimate
    estimates: list[CostEstimate]
    summary: CorpusSummary
    profile: CalibrationProfile
    threshold: float
    k: int
    mesh: object = None
    corpus: object = dataclasses.field(default=None, repr=False)
    autotuned: bool = False

    def run(self, corpus=None):
        """Execute the chosen configuration (on the planned corpus by default)."""
        data = corpus if corpus is not None else self.corpus
        if data is None:
            raise ValueError("Plan holds no corpus; pass one to run()")
        return execute(self.config, data, self.threshold, self.k, self.mesh)

    def describe(self, top: int = 8) -> str:
        s = self.summary
        mesh_s = dict(self.mesh.shape) if self.mesh is not None else None
        lines = [
            f"Plan: {self.config.name}"
            + (f" on mesh {mesh_s}" if mesh_s else " (single device)")
            + ("  [autotuned]" if self.autotuned else ""),
            f"corpus: n={s.n} m={s.m} density={s.density:.4f} cap={s.cap} "
            f"zipf={s.zipf_alpha:.2f} live_tiles={s.live_fraction:.3f} "
            f"t={s.threshold}",
            f"profile: {self.profile.device_kind} "
            f"matmul={self.profile.matmul_gflops:.1f}GF "
            f"gather={self.profile.gather_gflops:.1f}GF "
            f"wire={self.profile.collective_gbps:.1f}GB/s",
            f"{'rank':>4}  {'config':<42} {'predicted':>10} {'compute':>10} "
            f"{'comm':>10} {'wire':>10}",
        ]
        for i, e in enumerate(self.estimates[:top]):
            meas = (
                f"  measured={e.measured_s * 1e3:.1f}ms"
                if e.measured_s is not None
                else ""
            )
            lines.append(
                f"{i + 1:>4}  {e.config.name:<42} {e.total_s * 1e3:>8.2f}ms "
                f"{e.compute_s * 1e3:>8.2f}ms {e.comm_s * 1e3:>8.2f}ms "
                f"{e.wire_bytes / 1e6:>8.2f}MB{meas}"
            )
        return "\n".join(lines)

    def as_dict(self) -> dict:
        return {
            "chosen": self.config.name,
            "autotuned": self.autotuned,
            "summary": self.summary.as_dict(),
            "estimates": [e.as_dict() for e in self.estimates],
        }


def plan_apss(
    corpus,
    threshold: float,
    k: int = 32,
    mesh=None,
    *,
    profile: Optional[CalibrationProfile] = None,
    block_rows_choices: Sequence[int] = (128, 256, 512),
    include_kernel: Optional[bool] = None,
    autotune: bool = False,
    autotune_top: int = 3,
    sample_rows: int = 2048,
    seed: int = 0,
) -> Plan:
    """Rank every valid configuration by modeled cost; return a :class:`Plan`.

    ``corpus`` may be dense, sparse, or a prebuilt ``APSSIndex`` (planned
    from its exact corpus-side stats). ``profile=None`` loads the cached
    calibration for this device kind (deterministic defaults when none has
    been measured — run ``planner.calibrate.calibrate()`` once for real
    numbers). ``autotune=True`` additionally microbenchmarks the
    ``autotune_top`` best-predicted configurations and promotes the
    measured winner — the escape hatch for backend quirks (eager overhead,
    collective implementations) no closed-form model carries.
    """
    with trace.span("plan", autotune=autotune):
        p = _plan_apss_impl(
            corpus, threshold, k, mesh, profile=profile,
            block_rows_choices=block_rows_choices,
            include_kernel=include_kernel, autotune=autotune,
            autotune_top=autotune_top, sample_rows=sample_rows, seed=seed,
        )
        trace.annotate(chosen=p.config.name, candidates=len(p.estimates))
        return p


def _plan_apss_impl(
    corpus, threshold, k, mesh, *, profile, block_rows_choices,
    include_kernel, autotune, autotune_top, sample_rows, seed,
) -> Plan:
    from repro.serving.index import APSSIndex

    s = summarize_corpus(
        corpus, threshold, sample_rows=sample_rows, seed=seed
    )
    if profile is None:
        profile = _calibrate.get_profile()
    cfgs = candidate_configs(
        s, mesh, k, block_rows_choices=block_rows_choices,
        include_kernel=include_kernel,
    )
    if not cfgs:
        raise ValueError("no valid configuration for this corpus/mesh")
    mesh_sizes = dict(mesh.shape) if mesh is not None else None
    ests = sorted(
        (estimate_cost(c, s, mesh_sizes, profile, k) for c in cfgs),
        key=lambda e: e.total_s,
    )
    run_corpus = (
        _index_valid_corpus(corpus) if isinstance(corpus, APSSIndex) else corpus
    )
    autotuned = False
    if autotune and len(ests) > 1:
        # Measure the best-predicted config of the top `autotune_top`
        # DISTINCT variant families (block-size ties within a family are
        # modeled identically — measuring three of them would burn the
        # budget on noise), each on a pre-converted corpus so the timing
        # covers exactly the join the model priced.
        seen: set = set()
        picked: list[CostEstimate] = []
        for e in ests:
            fam = (e.config.kind, e.config.schedule,
                   e.config.accumulation, e.config.sparse)
            if fam in seen:
                continue
            seen.add(fam)
            picked.append(e)
            if len(picked) >= max(2, autotune_top):
                break
        rep_cache: dict = {}
        for e in picked:
            if e.config.sparse not in rep_cache:
                rep_cache[e.config.sparse] = _to_representation(
                    run_corpus, e.config.sparse
                )
            data = rep_cache[e.config.sparse]
            try:
                jax.block_until_ready(
                    execute(e.config, data, threshold, k, mesh, prepared=True)
                )  # compile + warm
                t0 = time.perf_counter()
                jax.block_until_ready(
                    execute(e.config, data, threshold, k, mesh, prepared=True)
                )
                e.measured_s = time.perf_counter() - t0
            except Exception:  # pragma: no cover - autotune is best-effort
                e.measured_s = float("inf")
        # Measured winner first; unmeasured keep their predicted order.
        ests.sort(
            key=lambda e: (
                (0, e.measured_s) if e.measured_s is not None
                else (1, e.total_s)
            )
        )
        autotuned = True
    return Plan(
        config=ests[0].config, cost=ests[0], estimates=ests, summary=s,
        profile=profile, threshold=float(threshold), k=k, mesh=mesh,
        corpus=run_corpus, autotuned=autotuned,
    )
