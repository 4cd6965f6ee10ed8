"""The paper's own workload as a selectable arch: distributed APSS cells.

These cells are the paper-representative entries for §Roofline/§Perf: each
lowers one distributed APSS variant at the scale of a paper Table-4 dataset
(padded to mesh-divisible shapes). Thresholds follow Table 4.

Variants (paper §5-§6 + TPU extensions):
  h_allgather   1-D horizontal, paper-faithful Alg. 6 (corpus all-gather)
  h_ring        1-D horizontal, hierarchical nested ring (beyond-paper)
  v_compressed  1-D vertical w/ local pruning (Lemma 1) + top-C compaction
  grid_2d       2-D checkerboard (Alg. 7), compressed accumulation

Scales (chosen so every per-device shard fits a 16 GB v5e):
  wikipedia-like  n=71680  m=1351680  t=0.9   (horizontal / 2-D)
  20news-like     n=20480  m=315392   t=0.4   (vertical — the paper also
                  found the vertical distribution viable only at smaller n)
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (
    ArchDef, CellBuild, ShapeCell, register, sds, shardings_for,
)
from repro.core.distributed import (
    apss_2d,
    apss_horizontal,
    apss_horizontal_hierarchical,
    apss_vertical,
)

K_MATCHES = 64


def config() -> dict:
    return {
        "wikipedia": dict(n=71680, m=1351680, t=0.9),
        "20news": dict(n=20480, m=315392, t=0.4),
        "dtype": "f32",       # §Perf knob: "bf16" halves block traffic
        "block_rows": 512,    # §Perf knob: n_loc reads each ring block once
    }


def _row_axes(mesh):
    return tuple(a for a in ("pod", "data", "model") if a in mesh.shape)


def _dtype(cfg):
    return jnp.bfloat16 if cfg.get("dtype") == "bf16" else jnp.float32


def _h_allgather_cell(cfg, mesh) -> CellBuild:
    spec = cfg["wikipedia"]
    axes = _row_axes(mesh)
    D = sds((spec["n"], spec["m"]), _dtype(cfg))
    fn = functools.partial(
        _run_h_allgather, mesh=mesh, axes=axes, t=spec["t"], k=K_MATCHES
    )
    return CellBuild(
        fn=fn, args=(D,),
        in_shardings=(shardings_for(mesh, P(axes, None)),),
        out_shardings=None,
        static_info={
            "kind": "apss", "model_flops": 2 * spec["n"] ** 2 * spec["m"],
            "n": spec["n"], "m": spec["m"],
        },
    )


def _run_h_allgather(D, *, mesh, axes, t, k):
    return apss_horizontal(
        D, t, k, mesh, axis_name=axes, schedule="allgather", block_rows=512
    )


def _h_ring_cell(cfg, mesh) -> CellBuild:
    spec = cfg["wikipedia"]
    axes = _row_axes(mesh)
    D = sds((spec["n"], spec["m"]), _dtype(cfg))
    fn = functools.partial(
        _run_h_ring, mesh=mesh, axes=axes, t=spec["t"], k=K_MATCHES
    )
    return CellBuild(
        fn=fn, args=(D,),
        in_shardings=(shardings_for(mesh, P(axes, None)),),
        out_shardings=None,
        static_info={
            "kind": "apss", "model_flops": 2 * spec["n"] ** 2 * spec["m"],
            "n": spec["n"], "m": spec["m"],
        },
    )


def _run_h_ring(D, *, mesh, axes, t, k):
    return apss_horizontal_hierarchical(
        D, t, k, mesh, axes, block_rows=512
    )


def _v_compressed_cell(cfg, mesh) -> CellBuild:
    spec = cfg["20news"]
    D = sds((spec["n"], spec["m"]), _dtype(cfg))
    fn = functools.partial(
        _run_v_compressed, mesh=mesh, t=spec["t"], k=K_MATCHES
    )
    return CellBuild(
        fn=fn, args=(D,),
        in_shardings=(shardings_for(mesh, P(None, "model")),),
        out_shardings=None,
        static_info={
            "kind": "apss", "model_flops": 2 * spec["n"] ** 2 * spec["m"],
            "n": spec["n"], "m": spec["m"],
        },
    )


def _run_v_compressed(D, *, mesh, t, k):
    return apss_vertical(
        D, t, k, mesh, axis_name="model", accumulation="compressed",
        block_rows=512, candidate_capacity=256,
    )


def _2d_cell(cfg, mesh) -> CellBuild:
    spec = cfg["wikipedia"]
    D = sds((spec["n"], spec["m"]), _dtype(cfg))
    fn = functools.partial(
        _run_2d, mesh=mesh, t=spec["t"], k=K_MATCHES,
        block_rows=int(cfg.get("block_rows", 512)),
    )
    return CellBuild(
        fn=fn, args=(D,),
        in_shardings=(shardings_for(mesh, P("data", "model")),),
        out_shardings=None,
        static_info={
            "kind": "apss", "model_flops": 2 * spec["n"] ** 2 * spec["m"],
            "n": spec["n"], "m": spec["m"],
        },
    )


def _run_2d(D, *, mesh, t, k, block_rows=512):
    return apss_2d(
        D, t, k, mesh, row_axis="data", col_axis="model",
        accumulation="compressed", block_rows=block_rows,
        candidate_capacity=256,
    )


ARCH = register(ArchDef(
    name="apss",
    source="this paper (Özkural & Aykanat 2014)",
    make_config=config,
    shapes={
        "h_allgather": ShapeCell(
            kind="apss", desc="1-D horizontal Alg.6 (paper-faithful), "
            "wikipedia-scale n=71680 m=1351680 t=0.9",
            build=_h_allgather_cell,
        ),
        "h_ring": ShapeCell(
            kind="apss", desc="1-D horizontal hierarchical ring "
            "(beyond-paper), wikipedia-scale",
            build=_h_ring_cell,
        ),
        "v_compressed": ShapeCell(
            kind="apss", desc="1-D vertical + local pruning (Lemma 1), "
            "20news-scale n=20480 m=315392 t=0.4",
            build=_v_compressed_cell,
        ),
        "grid_2d": ShapeCell(
            kind="apss", desc="2-D checkerboard Alg.7, wikipedia-scale",
            build=_2d_cell,
        ),
    },
))
