"""Model-vs-HLO audit: does the cost model price the program XLA built?

``planner.costmodel`` prices variants from closed-form FLOP / wire-byte
formulas; ``obs.drift`` checks those predictions against *measured wall
time* — which needs a run, a warm device, and a calibrated profile. This
module adds the third, zero-run leg: AOT-compile every plannable variant
family (``CompileMonitor.lower_and_compile``), run the loop-aware static
analyzer ``launch.hlo_analysis.analyze`` over the post-SPMD HLO, and
compare

- model FLOPs          vs HLO dot FLOPs (trip-count-weighted),
- model collective B   vs HLO link bytes (per-device, same wire convention
  as ``telemetry.CollectiveHop.total_bytes``),
- a streaming HBM lower bound vs HLO-billed HBM traffic,

as per-family ratios in an :class:`AuditReport`. A family whose HLO FLOPs
drift from the model (an XLA upgrade re-fusing a scan, a schedule change
doubling a mirror score) shows up here at *compile* time, before any
benchmark. Ratios also feed :func:`AuditReport.residuals` →
``obs.drift.drift_report`` as ``source="audit"`` rows (unit-free: the
Residual convention is ratios, so FLOPs work as well as seconds).

Coverage: every family ``candidate_configs`` can plan on the given meshes
— dense/sparse × blocked / horizontal allgather / ring / halfring /
vertical / hierarchical / 2-D checkerboard — plus the serving
``query_topk`` inners and the mutable delta join, captured from REAL call
sites via ``obs.compile.capture_calls`` (their worklist arguments are
built host-side, so the audit lowers the exact program the hot path
runs). Host-staged sparse families (dimensions dealt by frequency: the
vertical split sized on the host, the 2-D ``shard_dims`` pre-split) lower
through the post-split seams
``core.distributed._vertical_sparse_post_split`` / ``_2d_sparse_post_split``.

Known, documented gaps (reported as entry notes, not failures):

- the sparse XLA scan materializes a ``(block, S)`` gathered support slab
  per worklist tile — ≈ ``2·T·b·S·4`` bytes of HBM the streaming model
  does not charge (ROADMAP: in-kernel gather);
- HBM ratios are informational: the analyzer bills fusion call sites,
  which legitimately re-read operands the streaming bound counts once.

CLI: ``python -m repro.obs.audit [--n N] [--m M] [--json PATH]``.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Optional

import numpy as np

from repro.obs import compile as obs_compile
from repro.obs import drift, trace

# Families whose HLO-derived FLOPs must sit within this factor of the
# model (both directions). Only the dense families XLA compiles to plain
# dot chains are gated — sparse gather-dot FLOPs are partially hidden in
# scatter/gather ops the dot census cannot see.
FLOP_RATIO_BAND = 1.5
GATED_FAMILIES = ("blocked[dense]", "horizontal/ring[dense]")


@dataclasses.dataclass
class AuditEntry:
    """One variant family: model prediction vs HLO-derived measurement."""

    family: str
    config: str
    mesh: Optional[dict]
    predicted_flops: float
    hlo_flops: float
    predicted_link_bytes: float
    hlo_link_bytes: float
    predicted_hbm_bytes: float
    hlo_hbm_bytes: float
    record: obs_compile.CompileRecord
    notes: tuple = ()

    @staticmethod
    def _ratio(hlo: float, predicted: float) -> Optional[float]:
        if predicted <= 0:
            return None
        return hlo / predicted

    @property
    def flop_ratio(self) -> Optional[float]:
        return self._ratio(self.hlo_flops, self.predicted_flops)

    @property
    def link_ratio(self) -> Optional[float]:
        return self._ratio(self.hlo_link_bytes, self.predicted_link_bytes)

    @property
    def hbm_ratio(self) -> Optional[float]:
        return self._ratio(self.hlo_hbm_bytes, self.predicted_hbm_bytes)

    def as_dict(self) -> dict:
        return {
            "family": self.family,
            "config": self.config,
            "mesh": self.mesh,
            "predicted_flops": self.predicted_flops,
            "hlo_flops": self.hlo_flops,
            "flop_ratio": self.flop_ratio,
            "predicted_link_bytes": self.predicted_link_bytes,
            "hlo_link_bytes": self.hlo_link_bytes,
            "link_ratio": self.link_ratio,
            "predicted_hbm_bytes": self.predicted_hbm_bytes,
            "hlo_hbm_bytes": self.hlo_hbm_bytes,
            "hbm_ratio": self.hbm_ratio,
            "compile": self.record.as_dict(),
            "notes": list(self.notes),
        }


@dataclasses.dataclass
class AuditReport:
    """Every audited family + the corpus/mesh context they compiled for."""

    entries: list
    n: int
    m: int
    k: int
    threshold: float
    meshes: list

    def families(self) -> list:
        return [e.family for e in self.entries]

    def entry(self, family: str) -> AuditEntry:
        for e in self.entries:
            if e.family == family:
                return e
        raise KeyError(family)

    def gated_ok(self, band: float = FLOP_RATIO_BAND) -> bool:
        """Do the gated dense families' HLO FLOPs sit within ``band``?"""
        for fam in GATED_FAMILIES:
            try:
                r = self.entry(fam).flop_ratio
            except KeyError:
                return False
            if r is None or r > band or r < 1.0 / band:
                return False
        return True

    def residuals(self) -> list:
        """FLOP-ratio rows for ``obs.drift.drift_report`` (``source="audit"``,
        unit-free by the Residual ratio convention)."""
        out = []
        for e in self.entries:
            if e.predicted_flops > 0 and e.hlo_flops > 0:
                out.append(drift.Residual(
                    variant=e.family,
                    predicted_s=e.predicted_flops,
                    measured_s=e.hlo_flops,
                    source="audit",
                ))
        return out

    def as_dict(self) -> dict:
        return {
            "n": self.n, "m": self.m, "k": self.k,
            "threshold": self.threshold,
            "meshes": self.meshes,
            "flop_ratio_band": FLOP_RATIO_BAND,
            "gated_families": list(GATED_FAMILIES),
            "gated_ok": self.gated_ok(),
            "entries": [e.as_dict() for e in self.entries],
        }

    def describe(self) -> str:
        lines = [
            f"AuditReport: n={self.n} m={self.m} k={self.k} "
            f"t={self.threshold} meshes={self.meshes}",
            f"{'family':<36} {'flopsx':>7} {'linkx':>7} {'hbmx':>7} "
            f"{'peakMB':>8} {'compile':>8}",
        ]
        fmt = lambda r: "   -  " if r is None else f"{r:6.2f}"  # noqa: E731
        for e in self.entries:
            lines.append(
                f"{e.family:<36} {fmt(e.flop_ratio):>7} "
                f"{fmt(e.link_ratio):>7} {fmt(e.hbm_ratio):>7} "
                f"{e.record.total_bytes / 1e6:>7.1f}M "
                f"{e.record.t_compile_s * 1e3:>6.0f}ms"
            )
            for note in e.notes:
                lines.append(f"    note: {note}")
        gate = "PASS" if self.gated_ok() else "FAIL"
        lines.append(
            f"gate[{', '.join(GATED_FAMILIES)}] within "
            f"{FLOP_RATIO_BAND}x: {gate}"
        )
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Prediction helpers
# ---------------------------------------------------------------------------


def _family_name(cfg) -> str:
    base = cfg.kind
    if cfg.schedule:
        base += f"/{cfg.schedule}"
    if cfg.accumulation:
        base += f"/{cfg.accumulation}"
    return f"{base}[{'sparse' if cfg.sparse else 'dense'}]"


def _predicted_hbm(cfg, s, p: int, k: int) -> float:
    """Streaming lower bound: each device scores a ``rows × n`` strip by
    reading its resident row block plus every counterpart block once, and
    writes its matches. Deliberately optimistic — the HLO side bills
    fusion operand re-reads on top — so ``hbm_ratio ≥ 1`` is the healthy
    regime and the ratio is informational, not gated."""
    from repro.planner import telemetry

    depth = s.cap if cfg.sparse else s.m
    itemb = 8 if cfg.sparse else s.itemsize  # CSR slot = i32 idx + f32 val
    rows = s.n if cfg.kind == "vertical" else s.n // max(1, p)
    corpus_pass = (rows + s.n) * depth * itemb
    return float(corpus_pass + telemetry.matches_bytes(rows, k))


def _sparse_scan_note(cfg, s) -> str:
    """Quantify the sparse XLA scan's per-tile gathered support slab —
    the ``(T, block, S)`` HBM intermediate the streaming model does not
    charge (ROADMAP: in-kernel gather)."""
    b = cfg.block_rows
    total_tiles = max(1, (s.n // max(b, 1)) ** 2)
    live_tiles = max(1, int(round(s.live_fraction * total_tiles)))
    support = min(s.m, b * s.cap)
    slab = 2 * live_tiles * b * support * 4
    return (
        f"sparse scan gather intermediate ~(T={live_tiles}, b={b}, "
        f"S<={support}) x2 slabs = {slab / 1e6:.1f}MB HBM not in the "
        "streaming model (ROADMAP: in-kernel gather)"
    )


# ---------------------------------------------------------------------------
# Lowering seams per family
# ---------------------------------------------------------------------------


def _lower_planned(cfg, data, threshold: float, k: int, mesh):
    """AOT-compile one planner config through its lowerable seam."""
    import jax

    from repro.core import distributed as dist
    from repro.core.sparse import shard_dims
    from repro.planner import plan as planner_plan

    name = _family_name(cfg)
    if not planner_plan._has_host_stage(cfg):
        return obs_compile.lower_and_compile(
            planner_plan._execute_traced, data, cfg, float(threshold), k,
            mesh if cfg.kind != "blocked" else None, name=name,
        )
    names = tuple(mesh.axis_names)
    if cfg.kind == "vertical":
        idx_s, val_s, spill, _, m_loc = dist._vertical_sparse_split(
            data, cfg.block_rows, mesh, names[-1]
        )
        seam = functools.partial(
            dist._vertical_sparse_post_split,
            n_valid=data.n, m_loc=m_loc, threshold=float(threshold), k=k,
            mesh=mesh, axis_name=names[-1], accumulation=cfg.accumulation,
            block_rows=cfg.block_rows, candidate_capacity=None,
            return_stats=False,
        )
        return obs_compile.lower_and_compile(
            jax.jit(seam), idx_s, val_s, *spill, name=name,
        )
    if cfg.kind == "2d":
        r = mesh.shape[names[1]]
        idx_s, val_s, nnz_s, m_loc = shard_dims(data, r)
        seam = functools.partial(
            dist._2d_sparse_post_split,
            m_loc=m_loc, threshold=float(threshold), k=k, mesh=mesh,
            row_axis=names[0], col_axis=names[1],
            accumulation=cfg.accumulation, block_rows=cfg.block_rows,
            candidate_capacity=dist.default_candidate_capacity(k),
        )
        return obs_compile.lower_and_compile(
            jax.jit(seam), idx_s, val_s, nnz_s, name=name,
        )
    raise ValueError(f"no lowering seam for host-staged config {cfg.name}")


def _audit_planned(cfg, s, corpus, threshold: float, k: int, mesh,
                   mesh_sizes, analyze) -> AuditEntry:
    from repro.planner import costmodel
    from repro.planner.plan import _to_representation

    p = 1
    for v in (mesh_sizes or {}).values():
        p *= v
    if cfg.kind == "blocked":
        p = 1
    data = _to_representation(corpus, cfg.sparse)
    compiled, record = _lower_planned(cfg, data, threshold, k, mesh)
    analysis = analyze(compiled.as_text())
    hops = (
        costmodel.variant_hops(cfg, s, mesh_sizes, k)
        if mesh_sizes and p > 1 else ()
    )
    notes = []
    if cfg.sparse and cfg.kind in ("blocked", "horizontal"):
        notes.append(_sparse_scan_note(cfg, s))
    return AuditEntry(
        family=_family_name(cfg),
        config=cfg.name,
        mesh=dict(mesh_sizes) if mesh_sizes else None,
        predicted_flops=costmodel.variant_flops(cfg, s, p),
        hlo_flops=analysis["flops"],
        predicted_link_bytes=float(sum(h.total_bytes for h in hops)),
        hlo_link_bytes=analysis["link_bytes"],
        predicted_hbm_bytes=_predicted_hbm(cfg, s, p, k),
        hlo_hbm_bytes=analysis["hbm_bytes"],
        record=record,
        notes=tuple(notes),
    )


def _audit_serving(corpus, threshold: float, k: int, analyze) -> list:
    """query_topk inner + mutable forward delta join, from REAL call
    sites (``capture_calls``) so the audit lowers exactly what serving
    runs — worklist length ``T`` included."""
    from repro.core.sparse import from_dense
    from repro.serving import build_index, query_topk
    from repro.serving.mutable import MutableAPSSIndex

    D = np.asarray(corpus, np.float32)
    n, m = D.shape
    entries = []

    Q = D[: min(32, n)]
    calls: dict = {}
    for data in (D, from_dense(D)):
        index = build_index(data, block_rows=min(64, n))
        with obs_compile.capture_calls() as got:
            query_topk(index, Q, threshold, k)
        calls.update(got)
    for cap_name, fam in (
        ("serving.dense_inner", "serving.query_topk[dense]"),
        ("serving.sparse_inner", "serving.query_topk[sparse]"),
    ):
        call = calls.get(cap_name)
        if call is None:
            continue
        entries.append(_audit_captured(call, fam, m, analyze))

    mut = MutableAPSSIndex(
        D[: n // 2], threshold=threshold, k=k, kind="dense",
        block_rows=min(64, 1 << (n // 2 - 1).bit_length()),
    )
    with obs_compile.capture_calls() as calls:
        mut.append(D[n // 2:])  # append runs the forward delta join
    for cap_name, fam in (
        ("mutable.dense_inner", "mutable.delta_join[dense]"),
        ("mutable.sparse_inner", "mutable.delta_join[sparse]"),
    ):
        call = calls.get(cap_name)
        if call is None:
            continue
        entries.append(_audit_captured(call, fam, m, analyze))
    return entries


def _audit_captured(call, family: str, m: int, analyze) -> AuditEntry:
    """Worklist-path prediction: ``2·T·block_q·block_c·depth`` FLOPs over
    the captured tile list (``ij`` is ``(2, T)``), one gathered
    query-block + corpus-block read per tile for HBM."""
    kw = call.kwargs
    T = 0
    for a in call.args:
        shp = getattr(a, "shape", ())
        if len(shp) == 2 and shp[0] == 2:
            T = int(shp[1])
            break
    bq, bc = int(kw["block_q"]), int(kw["block_c"])
    predicted_flops = 2.0 * T * bq * bc * m
    predicted_hbm = float(T * (bq + bc) * m * 4 + T * bq * bc * 4)
    compiled, record = obs_compile.lower_and_compile(
        call.fn, *call.args, name=family, **call.kwargs,
    )
    analysis = analyze(compiled.as_text())
    return AuditEntry(
        family=family,
        config=f"{call.name}(T={T}, block_q={bq}, block_c={bc})",
        mesh=None,
        predicted_flops=predicted_flops,
        hlo_flops=analysis["flops"],
        predicted_link_bytes=0.0,
        hlo_link_bytes=analysis["link_bytes"],
        predicted_hbm_bytes=predicted_hbm,
        hlo_hbm_bytes=analysis["hbm_bytes"],
        record=record,
        notes=(),
    )


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def run_audit(
    corpus=None,
    *,
    n: int = 64,
    m: int = 64,
    k: int = 8,
    threshold: float = 0.3,
    density: float = 0.2,
    seed: int = 0,
    meshes=None,
    include_serving: bool = True,
) -> AuditReport:
    """Audit every plannable variant family (one config per family — block
    sizes within a family lower to the same program shape).

    ``meshes=None`` builds a 1-axis mesh over all devices plus (when the
    device count is an even composite) a 2-axis ``(q, 2)`` mesh, matching
    the families ``candidate_configs`` can plan. Pass ``corpus`` to audit
    real data; the default is the synthetic power-law corpus at a size
    every family's divisibility gates accept.
    """
    import jax
    from jax.sharding import Mesh

    from repro.data.synthetic import synthetic_corpus
    from repro.launch.hlo_analysis import analyze
    from repro.planner.plan import candidate_configs, summarize_corpus

    if corpus is None:
        corpus = synthetic_corpus(n, m, density * m, seed=seed)
    D = np.asarray(corpus, np.float32)
    n, m = D.shape

    if meshes is None:
        devs = jax.devices()
        meshes = [Mesh(np.array(devs), ("data",))]
        if len(devs) >= 4 and len(devs) % 2 == 0:
            meshes.append(
                Mesh(np.array(devs).reshape(len(devs) // 2, 2),
                     ("data", "model"))
            )

    s = summarize_corpus(D, threshold)
    entries: list = []
    seen: set = set()
    mesh_list = []
    with trace.span("obs/audit", n=n, m=m, k=k):
        for mesh in [None] + list(meshes):
            mesh_sizes = dict(mesh.shape) if mesh is not None else None
            if mesh_sizes:
                mesh_list.append(mesh_sizes)
            for cfg in candidate_configs(s, mesh, k, include_kernel=False):
                fam = (cfg.kind, cfg.schedule, cfg.accumulation, cfg.sparse)
                if fam in seen:
                    continue
                if cfg.kind == "blocked" and mesh is not None:
                    continue  # identical program regardless of mesh
                seen.add(fam)
                entries.append(_audit_planned(
                    cfg, s, D, threshold, k, mesh, mesh_sizes, analyze,
                ))
        if include_serving:
            entries.extend(_audit_serving(D, threshold, k, analyze))
    return AuditReport(
        entries=entries, n=n, m=m, k=k, threshold=float(threshold),
        meshes=mesh_list,
    )


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="model-vs-HLO audit over every plannable variant family"
    )
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--threshold", type=float, default=0.3)
    ap.add_argument("--density", type=float, default=0.2)
    ap.add_argument("--json", default=None, help="write AuditReport JSON here")
    args = ap.parse_args(argv)
    report = run_audit(
        n=args.n, m=args.m, k=args.k,
        threshold=args.threshold, density=args.density,
    )
    print(report.describe())
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report.as_dict(), f, indent=2)
            f.write("\n")
    rep = drift.drift_report(report.residuals(), band=4.0)
    print(rep.describe())
    return 0 if report.gated_ok() else 1


if __name__ == "__main__":
    raise SystemExit(main())
