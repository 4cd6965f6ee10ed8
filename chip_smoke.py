"""Bring-up smoke: the main path on a TPU at real size, checked phase by phase.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the 1-D and 2-D distributions

One process drives every phase, through the entry points a user calls, and
checks each phase against a plain oracle (``apss_reference``, or
``extract_matches`` over the brute-force scores for retrieval):

- A. dense self-join, 32,768 × 768 (the width of a BERT-base sentence
  embedding), t = 0.8, k = 32: ``apss_blocked(use_kernel=True)`` and
  ``apss_fused_compacted``;
- B. CSR self-join at the radikal shape of the paper's Table 1 (6,883 ×
  136,447, 1,072,472 nonzeros), t = 0.2, k = 64: ``apss_blocked`` on a
  ``SparseCorpus`` with ``use_kernel=True``;
- C. retrieval over 1,183,514 × 100 (the glove-100-angular shape of
  ANN-Benchmarks): ``ContinuousRetrievalServer`` with the Pallas tier
  answers 512 perturbed corpus rows, and one batch goes through
  ``query_topk(use_kernel=True, early_exit=True)``;
- with ``--chips 4``, only phase A's corpus through ``apss_horizontal``
  (ring schedule, kernel), ``apss_vertical`` (compressed accumulation) and
  ``apss_2d`` on a 2×2 mesh, with each device's peak memory.

The contract is the tests' one: identical ``match_set`` and counts (counts
saturated at k under early exit), identical retrieval indices, values to
f32 rounding. A phase also fails if any Pallas kernel was traced in
interpret mode, or if the server shed, retried, degraded or answered stale.
Each phase prints one JSON line of bring-up facts (cold and warm seconds,
matches, live-tile fraction, peak device bytes, the kernels traced). These
are not benchmark numbers. The last line is ``{"ok": true, "device": ...}``,
printed only when every phase passed; without a TPU the script exits 2
before any phase.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Phase sizes (see the module docstring for their sources).
DENSE_N, DENSE_M, DENSE_NNZ, DENSE_T, DENSE_K = 32768, 768, 16, 0.8, 32
RADIKAL_N, RADIKAL_M, RADIKAL_NNZ = 6883, 136447, 1072472
SPARSE_T, SPARSE_K = 0.2, 64
GLOVE_N, GLOVE_M, QUERIES, RETR_T, RETR_K, MAX_BATCH = (
    1183514, 100, 512, 0.5, 10, 64
)
QUERY_NOISE = 0.05  # cos(query, its source row) ≈ 0.89


@contextlib.contextmanager
def traced_kernels():
    """Record ``(kernel name, interpret)`` of every ``pallas_call`` traced
    inside the block: which kernel a path really took, and whether it was
    compiled by Mosaic or emulated."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    seen: list[tuple[str, bool]] = []

    def spy(kernel, *args, **kwargs):
        fn = getattr(kernel, "func", kernel)
        seen.append((fn.__name__, bool(kwargs.get("interpret", False))))
        return real(kernel, *args, **kwargs)

    pl.pallas_call = spy
    try:
        yield seen
    finally:
        pl.pallas_call = real


def timed(fn):
    """``(result, seconds)`` of ``fn()``, waiting for the device."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def peak_bytes(device=None) -> int | None:
    """The device's peak bytes in use since the process started (the
    runtime keeps no per-phase peak)."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def oracle(D, threshold, k):
    """``apss_reference`` under one jit: eagerly, each intermediate of its
    ``n × n`` extraction would be a separate buffer on the device."""
    from repro.core.apss import apss_reference

    return jax.jit(apss_reference, static_argnums=(1, 2))(D, threshold, k)


def compare(got, ref) -> dict:
    """Mismatches of a self-join result against the oracle: match sets and
    counts exactly, values to f32 rounding. Empty = agree."""
    from repro.core.graph import match_set

    bad = {}
    gs, rs = match_set(got), match_set(ref)
    if gs != rs:
        bad["pairs_only_in_result"] = len(gs - rs)
        bad["pairs_only_in_reference"] = len(rs - gs)
    nc = int(np.sum(np.asarray(got.counts) != np.asarray(ref.counts)))
    if nc:
        bad["rows_with_other_counts"] = nc
    if not np.allclose(
        np.asarray(got.values), np.asarray(ref.values), rtol=1e-6, atol=1e-6
    ):
        bad["values_beyond_f32_rounding"] = True
    return bad


def phase_dense() -> dict:
    """A: dense self-join through the fused and the compacted kernels."""
    from repro.core.apss import apss_blocked, pad_rows
    from repro.core.pruning import block_prune_mask
    from repro.data.synthetic import clustered_corpus
    from repro.kernels.apss_block.ops import apss_fused_compacted, compact_worklist

    D = jnp.asarray(clustered_corpus(DENSE_N, DENSE_M, DENSE_NNZ, seed=0))
    t, k = DENSE_T, DENSE_K
    out = {"n": DENSE_N, "m": DENSE_M, "threshold": t, "k": k}

    runs = {
        "apss_blocked(use_kernel=True)": lambda: apss_blocked(
            D, t, k, use_kernel=True
        ),
        "apss_fused_compacted": lambda: apss_fused_compacted(D, t, k),
    }
    results = {}
    for name, fn in runs.items():
        with traced_kernels() as seen:
            got, cold = timed(fn)
        _, warm = timed(fn)
        results[name] = got
        out[name] = {"cold_s": cold, "warm_s": warm, "kernels": seen}

    Dp, _ = pad_rows(D, 256)
    nb = Dp.shape[0] // 256
    live = np.asarray(block_prune_mask(Dp, Dp, t, 256, use_minsize=False))
    mask, ub = block_prune_mask(Dp, Dp, t, 256, return_ub=True)
    wl = compact_worklist(mask, ub)
    out["live_tile_fraction"] = float(live.mean())
    out["worklist_tiles"] = 0 if wl is None else int(wl.shape[1])
    out["upper_tiles"] = nb * (nb + 1) // 2
    out["peak_bytes_so_far"] = peak_bytes()

    ref = oracle(D, t, k)
    out["matches"] = int(np.asarray(ref.counts).sum())
    if out["matches"] == 0:
        out["mismatch"] = {"reference_has_no_matches": True}
        return out
    for name, got in results.items():
        bad = compare(got, ref)
        if bad:
            out.setdefault("mismatch", {})[name] = bad
    return out


def phase_sparse() -> dict:
    """B: CSR self-join at the radikal shape through the CSR tile kernel."""
    from repro.core.apss import apss_blocked
    from repro.core.sparse import to_dense
    from repro.data.sparse import sparse_zipfian_corpus
    from repro.planner import telemetry

    sp = sparse_zipfian_corpus(
        RADIKAL_N, RADIKAL_M, RADIKAL_NNZ / RADIKAL_N, seed=0
    )
    t, k = SPARSE_T, SPARSE_K
    out = {"n": sp.n, "m": sp.m, "cap": sp.cap, "threshold": t, "k": k}

    def run():
        return apss_blocked(sp, t, k, use_kernel=True)

    with traced_kernels() as seen, telemetry.CommLog() as log:
        got, cold = timed(run)
    _, warm = timed(run)
    rec = log.last
    out["apss_blocked(SparseCorpus, use_kernel=True)"] = {
        "cold_s": cold, "warm_s": warm, "kernels": seen,
    }
    out["live_tile_fraction"] = rec.live_tiles / rec.total_tiles
    out["peak_bytes_so_far"] = peak_bytes()

    ref = oracle(to_dense(sp), t, k)
    out["matches"] = int(np.asarray(ref.counts).sum())
    bad = compare(got, ref)
    if out["matches"] == 0:
        bad["reference_has_no_matches"] = True
    if bad:
        out["mismatch"] = bad
    return out


def phase_retrieval() -> dict:
    """C: the continuous server's Pallas tier and the early-exit kernel."""
    from repro.core.apss import normalize_rows
    from repro.core.matches import SCORE_PRECISION, extract_matches
    from repro.planner import telemetry
    from repro.serving.index import build_index
    from repro.serving.query import query_topk
    from repro.serving.server import ContinuousRetrievalServer

    kc, kr, kq = jax.random.split(jax.random.key(0), 3)
    C = jax.random.normal(kc, (GLOVE_N, GLOVE_M), jnp.float32)
    Cn = normalize_rows(C)
    rows = jax.random.choice(kr, GLOVE_N, (QUERIES,), replace=False)
    Q = np.asarray(
        Cn[rows] + QUERY_NOISE * jax.random.normal(kq, (QUERIES, GLOVE_M))
    )
    t, k = RETR_T, RETR_K
    out = {"n": GLOVE_N, "m": GLOVE_M, "queries": QUERIES, "threshold": t,
           "k": k, "max_batch": MAX_BATCH}

    index, out["build_index_s"] = timed(lambda: build_index(C, block_rows=256))
    srv = ContinuousRetrievalServer(
        index, threshold=t, k=k, max_batch=MAX_BATCH, use_kernel=True
    )
    with srv, traced_kernels() as seen, telemetry.CommLog() as log:
        t0 = time.perf_counter()
        first = srv.serve(list(Q[:MAX_BATCH]))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        rest = srv.serve(list(Q[MAX_BATCH:]))
        warm = time.perf_counter() - t0
    results = first + rest
    stats = srv.stats
    batches = [r for r in log.records if r.variant == "serving/query"]
    out["server"] = {
        "cold_s_first_batch": cold,
        "warm_s_remaining_queries": warm,
        "kernels": sorted(set(seen)),
        "stats": stats._asdict(),
        "live_tile_fraction": float(
            sum(r.live_tiles for r in batches)
            / sum(r.total_tiles for r in batches)
        ),
    }

    # Oracle: brute-force scores of the same normalized batches.
    ref_parts = []
    for lo in range(0, QUERIES, MAX_BATCH):
        Qn = normalize_rows(jnp.asarray(Q[lo:lo + MAX_BATCH]))
        S = jnp.einsum(
            "qm,cm->qc", Qn, Cn,
            precision=SCORE_PRECISION,
            preferred_element_type=jnp.float32,
        )
        ref_parts.append(
            jax.tree.map(np.asarray, extract_matches(S, t, k, exclude_self=False))
        )
    ref_idx = np.concatenate([p.indices for p in ref_parts])
    ref_val = np.concatenate([p.values for p in ref_parts])
    ref_cnt = np.concatenate([p.counts for p in ref_parts])
    out["matches"] = int(ref_cnt.sum())

    bad = {}
    failed = [
        name for name in ("shed", "degraded", "retries", "stale")
        if getattr(stats, name)
    ]
    if failed:
        bad["server_counters_above_zero"] = failed
    if any(r.status != "ok" for r in results):
        bad["results_not_ok"] = sum(r.status != "ok" for r in results)
    got_idx = np.stack([r.indices for r in results])
    got_val = np.stack([r.values for r in results])
    got_cnt = np.array([r.count for r in results])
    if not np.array_equal(got_idx, ref_idx):
        bad["server_rows_with_other_indices"] = int(
            np.sum((got_idx != ref_idx).any(axis=1))
        )
    if not np.array_equal(got_cnt, ref_cnt):
        bad["server_rows_with_other_counts"] = int(np.sum(got_cnt != ref_cnt))
    if not np.allclose(got_val, ref_val, rtol=1e-6, atol=1e-6):
        bad["server_values_beyond_f32_rounding"] = True

    Qn0 = normalize_rows(jnp.asarray(Q[:MAX_BATCH]))

    def early_exit():
        return query_topk(
            index, Qn0, t, k, block_q=MAX_BATCH, use_kernel=True,
            early_exit=True,
        )

    with traced_kernels() as seen:
        ee, cold = timed(early_exit)
    _, warm = timed(early_exit)
    out["query_topk(use_kernel=True, early_exit=True)"] = {
        "cold_s": cold, "warm_s": warm, "kernels": seen,
    }
    p0 = ref_parts[0]
    if not np.array_equal(np.asarray(ee.indices), p0.indices):
        bad["early_exit_rows_with_other_indices"] = int(
            np.sum((np.asarray(ee.indices) != p0.indices).any(axis=1))
        )
    if not np.array_equal(np.asarray(ee.counts), np.minimum(p0.counts, k)):
        bad["early_exit_counts_not_saturated_reference"] = True
    if not np.allclose(np.asarray(ee.values), p0.values, rtol=1e-6, atol=1e-6):
        bad["early_exit_values_beyond_f32_rounding"] = True
    out["peak_bytes_so_far"] = peak_bytes()
    if out["matches"] == 0:
        bad["reference_has_no_matches"] = True
    if bad:
        out["mismatch"] = bad
    return out


def phase_distributed() -> dict:
    """Four chips: phase A's corpus through the 1-D and 2-D distributions."""
    from repro.compat import make_mesh
    from repro.core.distributed import apss_2d, apss_horizontal, apss_vertical
    from repro.data.synthetic import clustered_corpus

    D = jnp.asarray(clustered_corpus(DENSE_N, DENSE_M, DENSE_NNZ, seed=0))
    t, k = DENSE_T, DENSE_K
    out = {"n": DENSE_N, "m": DENSE_M, "threshold": t, "k": k}
    rows = make_mesh((4,), ("data",))
    dims = make_mesh((4,), ("model",))
    grid = make_mesh((2, 2), ("data", "model"))
    runs = {
        "apss_horizontal(ring, use_kernel=True)": lambda: apss_horizontal(
            D, t, k, rows, schedule="ring", use_kernel=True
        ),
        "apss_vertical(compressed)": lambda: apss_vertical(
            D, t, k, dims, accumulation="compressed"
        ),
        "apss_2d(2x2)": lambda: apss_2d(D, t, k, grid),
    }
    results = {}
    for name, fn in runs.items():
        with traced_kernels() as seen:
            got, cold = timed(fn)
        _, warm = timed(fn)
        results[name] = jax.tree.map(np.asarray, got)
        out[name] = {"cold_s": cold, "warm_s": warm, "kernels": seen}
    # Before the oracle, which runs on device 0 alone.
    out["peak_bytes_per_device"] = [peak_bytes(d) for d in jax.devices()]

    ref = oracle(D, t, k)
    out["matches"] = int(np.asarray(ref.counts).sum())
    for name, got in results.items():
        bad = compare(got, ref)
        if bad:
            out.setdefault("mismatch", {})[name] = bad
    if not all(out["peak_bytes_per_device"]):
        out.setdefault("mismatch", {})["device_without_peak_bytes"] = True
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.cache import enable_compile_cache
    from repro.kernels.apss_block.ops import _on_tpu

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    if not _on_tpu():
        print("chip_smoke: kernels would run in interpret mode", file=sys.stderr)
        return 1

    phases = (
        {"distributed": phase_distributed} if args.chips == 4
        else {"A_dense": phase_dense, "B_sparse": phase_sparse,
              "C_retrieval": phase_retrieval}
    )
    ok = True
    for name, fn in phases.items():
        t0 = time.perf_counter()
        try:
            res = fn()
        except Exception:
            traceback.print_exc()
            res = {"mismatch": {"exception": traceback.format_exc(limit=1)}}
        res = {"phase": name, "wall_s": time.perf_counter() - t0, **res}
        interpreted = [
            kname for rec in res.values() if isinstance(rec, dict)
            for kname, interp in rec.get("kernels", ()) if interp
        ]
        if interpreted:
            res.setdefault("mismatch", {})["interpreted_kernels"] = interpreted
        res["ok"] = "mismatch" not in res
        ok &= res["ok"]
        print(json.dumps(res, default=str), flush=True)
        gc.collect()
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
