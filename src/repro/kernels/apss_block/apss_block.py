"""Pallas TPU kernel: fused blocked ``X·Yᵀ`` + threshold + tile skipping.

This is the paper's compute hot-spot (the score-accumulation inner loop of
all-pairs-0-array) as a TPU kernel. The three fused pieces:

1. **Tile matmul** over the feature axis with an f32 VMEM accumulator
   (``bm×bn`` scratch), grid ``(i, j, kf)`` with the feature axis innermost —
   the MXU-native realization of the paper's dense score array.
2. **Threshold filter** applied in-register before the single HBM write:
   sub-threshold scores are never materialized at full precision in HBM
   (the paper's "filter during accumulation" carried to the memory
   hierarchy: HBM sees only the thresholded result).
3. **Block pruning**: a ``(grid_m, grid_n)`` live mask — from
   ``core.pruning.block_prune_mask`` (maxweight / minsize bounds at tile
   granularity) — gates the matmul with ``@pl.when``, so dead tiles issue no
   MXU work and no X/Y VMEM reads beyond the pipelined fetch.

TPU sizing (v5e): default tiles 256×256×512 → VMEM footprint
2·(256·512·2B) + 256·256·4B ≈ 0.8 MB « 16 MB VMEM, MXU-aligned (multiples
of 128 on every contraction/output dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.apss_block.fused import _tile_dot


def _apss_block_kernel(
    mask_ref,  # SMEM (1, 1, grid_n) i32 — live flags of this row block's tiles
    x_ref,     # (bm, bk)
    y_ref,     # (bn, bk)
    o_ref,     # (bm, bn)
    acc_ref,   # VMEM scratch (bm, bn) f32
    *,
    threshold: float,
    out_dtype,
):
    kf = pl.program_id(2)
    nkf = pl.num_programs(2)
    live = mask_ref[0, 0, pl.program_id(1)] != 0

    @pl.when(kf == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += _tile_dot(x_ref[...], y_ref[...])

    @pl.when(kf == nkf - 1)
    def _emit():
        acc = acc_ref[...]
        keep = (acc >= jnp.float32(threshold)) & live
        o_ref[...] = jnp.where(keep, acc, 0.0).astype(out_dtype)


def apss_block_pallas(
    x: jax.Array,
    y: jax.Array,
    block_mask: jax.Array,
    threshold: float,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """Raw pallas_call; shapes must be tile-divisible (see ops.py wrapper).

    Args:
      x: ``(n_rows, m)`` query rows.
      y: ``(n_cols, m)`` corpus rows.
      block_mask: ``(n_rows/bm, n_cols/bn)`` int32; 0 ⇒ tile provably dead.
      threshold: similarity threshold ``t`` (static).
    """
    n_rows, m = x.shape
    n_cols, m2 = y.shape
    assert m == m2, (m, m2)
    assert n_rows % block_m == 0, (n_rows, block_m)
    assert n_cols % block_n == 0, (n_cols, block_n)
    assert m % block_k == 0, (m, block_k)
    grid = (n_rows // block_m, n_cols // block_n, m // block_k)
    assert block_mask.shape == grid[:2], (block_mask.shape, grid)

    kernel = functools.partial(
        _apss_block_kernel, threshold=threshold, out_dtype=out_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, grid[1]), lambda i, j, kf: (i, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec((block_m, block_k), lambda i, j, kf: (i, kf)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kf: (j, kf)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kf: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_rows, n_cols), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(block_mask.astype(jnp.int32).reshape(grid[0], 1, grid[1]), x, y)
