"""Compile rehearsals: every main-path Pallas kernel, compiled by Mosaic for a
described TPU v5e at the sizes ``chip_smoke.py`` runs, without a chip.

Interpret mode checks neither Mosaic's block-shape rules nor its VMEM
limits; this file does, for each PR, at no chip time. The topology is
described inside a fixture (never at import) and the tests skip where it
cannot be described. Shapes carry a sharding on the described chip; no
array is ever placed.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.apss_block import fused, sparse

# chip_smoke.py sizes: dense self-join 32,768 × 768 (feature axis padded to
# the 512 tile), block 256; radikal CSR self-join, 27 blocks of 256 with a
# 13,824-wide support, 378 upper tiles; retrieval over 1,183,514 × 100
# (128 lanes), query block 64, 8,192 worklist entries.
N, MP, NB = 32768, 1024, 128
SP_NB, SP_S, SP_T = 27, 13824, 378
NC, Q, QT = 1183744, 64, 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a compile for a described chip can be written to it but not read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fused(s):
    return (
        lambda x, mask, meta: fused.apss_fused_pallas(
            x, x, mask, meta, 0.8, 32, block_m=256, block_n=256, block_k=512,
            n_valid_cols=N,
        ),
        s((N, MP)), s((NB, NB), jnp.int32), s((1, 2), jnp.int32),
    )


def _tile_candidates(s):
    return (
        lambda x, ij: fused.apss_tile_candidates_pallas(
            x, ij, 0.8, 32, block_m=256, block_n=256, block_k=512, n_valid=N,
        ),
        s((N, MP)), s((2, NB * (NB + 1) // 2), jnp.int32),
    )


def _sparse_tile_candidates(s):
    return (
        lambda bx, yg, ij: sparse.sparse_tile_candidates_pallas(
            bx, yg, ij, 0.2, 64, block_m=256, n_valid=6883,
        ),
        s((SP_NB, 256, SP_S)), s((SP_T, 256, SP_S)), s((2, SP_T), jnp.int32),
    )


def _rect_candidates(s):
    return (
        lambda q, c, ij: fused.rect_tile_candidates_pallas(
            q, c, ij, 0.5, 10, block_q=Q, block_c=256, block_k=128,
            nc_valid=NC - 230,
        ),
        s((Q, 128)), s((NC, 128)), s((2, QT), jnp.int32),
    )


def _rect_early_exit(s):
    return (
        lambda q, c, ij, ub: fused.rect_tile_candidates_early_exit_pallas(
            q, c, ij, ub, 0.5, 10, block_q=Q, block_c=256, block_k=128,
            nc_valid=NC - 230, nq_valid=Q,
        ),
        s((Q, 128)), s((NC, 128)), s((2, QT), jnp.int32), s((QT,)),
    )


def _rect_sparse_candidates(s):
    return (
        lambda qg, bx, ij: sparse.rect_sparse_tile_candidates_pallas(
            qg, bx, ij, 0.2, 64, block_q=128, block_c=256, nc_valid=6883,
        ),
        s((64, 128, SP_S)), s((SP_NB, 256, SP_S)), s((2, 64), jnp.int32),
    )


@pytest.mark.parametrize(
    "build",
    [
        _fused, _tile_candidates, _sparse_tile_candidates,
        _rect_candidates, _rect_early_exit, _rect_sparse_candidates,
    ],
    ids=lambda b: b.__name__.lstrip("_"),
)
def test_kernel_compiles_for_v5e(one_chip, build):
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    fn, *args = build(shape)
    compiled = jax.jit(fn).lower(*args).compile()
    # A Mosaic kernel, not the interpreter's emulation of one.
    assert "tpu_custom_call" in compiled.as_text()


def test_device_support_build_compiles_for_v5e(one_chip):
    # the radikal CSR corpus: 6,912 padded rows of width 204, m = 136,447
    n, cap, m = SP_NB * 256, 204, 136447

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    presence = sparse._support_presence.lower(
        shape((n, cap), jnp.int32), shape((n,), jnp.int32), block_m=256, m=m
    ).compile()
    marks, widest = presence.out_info
    assert marks.shape == (SP_NB, m) and widest.shape == ()
    build = sparse._support_build.lower(
        shape((SP_NB, m), jnp.bool_), shape((n, cap), jnp.int32),
        shape((n, cap), jnp.float32), shape((n,), jnp.int32),
        block_m=256, S=SP_S,
    ).compile()
    bdims, bx = build.out_info
    assert bdims.shape == (SP_NB, SP_S) and bx.shape == (SP_NB, 256, SP_S)
