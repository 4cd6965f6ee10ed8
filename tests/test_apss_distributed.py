"""Integration tests: every distributed APSS variant is exact vs the oracle
on an 8-device mesh (the paper's Algs. 3-7 + TPU extensions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import make_mesh
from repro.core.apss import apss_reference
from repro.core.distributed import (
    apss,
    apss_2d,
    apss_horizontal,
    apss_horizontal_hierarchical,
    apss_vertical,
)
from repro.core.graph import match_set

T, K = 0.35, 16


@pytest.fixture(scope="module")
def ref(corpus):
    return jax.jit(lambda d: apss_reference(d, T, K))(jnp.asarray(corpus))


def _check(got, ref):
    assert match_set(got) == match_set(ref)
    np.testing.assert_array_equal(np.asarray(got.counts), np.asarray(ref.counts))


@pytest.mark.parametrize("schedule", ["allgather", "ring", "halfring"])
def test_horizontal(corpus, mesh8, ref, schedule):
    got = jax.jit(
        lambda d: apss_horizontal(
            d, T, K, mesh8, schedule=schedule, block_rows=16
        )
    )(jnp.asarray(corpus))
    _check(got, ref)


@pytest.mark.parametrize(
    "accumulation", ["allreduce", "scatter", "compressed", "recursive"]
)
def test_vertical(corpus, mesh8_model, ref, accumulation):
    got = jax.jit(
        lambda d: apss_vertical(
            d, T, K, mesh8_model, accumulation=accumulation,
            block_rows=32, candidate_capacity=128,
        )
    )(jnp.asarray(corpus))
    _check(got, ref)


def test_vertical_overflow_reported(corpus, mesh8_model):
    # Tiny capacity must trip the overflow counter (visible truncation).
    _, stats = jax.jit(
        lambda d: apss_vertical(
            d, 0.05, K, mesh8_model, accumulation="compressed",
            block_rows=32, candidate_capacity=4, return_stats=True,
        )
    )(jnp.asarray(corpus))
    assert int(stats.overflow_rows) > 0


@pytest.mark.parametrize("accumulation", ["allreduce", "compressed"])
def test_2d(corpus, mesh4x2, ref, accumulation):
    got = jax.jit(
        lambda d: apss_2d(
            d, T, K, mesh4x2, accumulation=accumulation,
            block_rows=16, candidate_capacity=128,
        )
    )(jnp.asarray(corpus))
    _check(got, ref)


def test_2d_overflowing_blocks_take_the_exact_route(corpus, mesh4x2, ref):
    """A capacity of 2 overflows on some tiles: the dense 2-D
    sweep scores those by the all-reduce of the whole tile, so the join
    stays the oracle's, and the route counts cover every (query block, ring
    step) tile of every row shard."""
    got, stats = jax.jit(
        lambda d: apss_2d(
            d, T, K, mesh4x2, accumulation="compressed", block_rows=16,
            candidate_capacity=2, return_stats=True,
        )
    )(jnp.asarray(corpus))
    _check(got, ref)
    assert int(stats.overflow_rows) > 0 and int(stats.blocks_exact) > 0
    assert int(stats.blocks_pruned) + int(stats.blocks_exact) == 4 * 4 * 2


def test_hierarchical_2level(corpus, ref):
    mesh = make_mesh((2, 4), ("pod", "data"))
    got = jax.jit(
        lambda d: apss_horizontal_hierarchical(
            d, T, K, mesh, ("pod", "data"), block_rows=16
        )
    )(jnp.asarray(corpus))
    _check(got, ref)


def test_hierarchical_3level(corpus, ref):
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    got = jax.jit(
        lambda d: apss_horizontal_hierarchical(
            d, T, K, mesh, ("pod", "data", "model"), block_rows=16
        )
    )(jnp.asarray(corpus))
    _check(got, ref)


def test_dispatcher(corpus, mesh4x2, ref):
    got = jax.jit(
        lambda d: apss(
            d, T, K, mesh4x2, distribution="2d", block_rows=16,
            candidate_capacity=128,
        )
    )(jnp.asarray(corpus))
    _check(got, ref)


def test_odd_device_count_ring_and_halfring(corpus, ref):
    """Non-power-of-two processor counts (paper runs 3-, 5-way too)."""
    devs = jax.devices()[:5]
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    # 128 rows / 5 devices doesn't divide — pad rows to 130.
    D = np.concatenate([corpus, np.zeros((2, corpus.shape[1]), np.float32)])
    for schedule in ["ring", "halfring"]:
        got = jax.jit(
            lambda d: apss_horizontal(
                d, T, K, mesh, schedule=schedule, block_rows=13
            )
        )(jnp.asarray(D))
        got = jax.tree.map(lambda x: x[: corpus.shape[0]], got)
        _check(got, ref)
