"""launch.hlo_analysis loop awareness on REAL lowered HLO (ISSUE 9,
satellite 4): scan trip counts, nested-loop multipliers, collective link
factors — the analyzer the compile audit (repro.obs.audit) stands on."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.launch.hlo_analysis import analyze


def _hlo(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_of_matmuls_counts_trip_count():
    """A scanned matmul body must be billed T times, not once — the whole
    reason ``compiled.cost_analysis()`` is not enough."""
    T, n = 9, 64

    def loop(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        return jax.lax.scan(body, x, None, length=T)[0]

    a = analyze(_hlo(loop, (n, n), (n, n)))
    assert a["flops"] == pytest.approx(T * 2 * n**3, rel=0.02)
    assert a["max_multiplier"] >= T


def test_scan_over_stacked_operands_counts_leading_dim():
    """Trip count from scanning an actual array (the sparse tile-worklist
    shape: scan over (T, ...) stacked operands)."""
    T, n = 6, 32

    def loop(xs, w):
        def body(c, x):
            return c + x @ w, None

        return jax.lax.scan(body, jnp.zeros((n, n), jnp.float32), xs)[0]

    a = analyze(_hlo(loop, (T, n, n), (n, n)))
    assert a["flops"] == pytest.approx(T * 2 * n**3, rel=0.02)
    assert a["max_multiplier"] >= T


def test_nested_scans_multiply_trip_counts():
    outer, inner, n = 3, 5, 32

    def loop(x, w):
        def outer_body(c, _):
            def inner_body(ci, _):
                return jnp.tanh(ci @ w), None

            return jax.lax.scan(inner_body, c, None, length=inner)[0], None

        return jax.lax.scan(outer_body, x, None, length=outer)[0]

    a = analyze(_hlo(loop, (n, n), (n, n)))
    assert a["flops"] == pytest.approx(outer * inner * 2 * n**3, rel=0.02)
    assert a["max_multiplier"] >= outer * inner


def test_loop_multiplier_scales_hbm_too():
    """A matmul inside a scan re-reads its operands every iteration; the
    HBM census must scale with the trip count as the FLOPs do."""
    n = 64

    def once(x, w):
        return jnp.tanh(x @ w)

    def looped(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        return jax.lax.scan(body, x, None, length=8)[0]

    h1 = analyze(_hlo(once, (n, n), (n, n)))["hbm_bytes"]
    h8 = analyze(_hlo(looped, (n, n), (n, n)))["hbm_bytes"]
    assert h8 > 4 * h1  # 8 iterations must bill several times one pass


@pytest.fixture(scope="module")
def mesh8d():
    return make_mesh((8,), ("data",))


def _spmd_hlo(fn, mesh, in_specs, out_specs, *shapes):
    f = shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    return jax.jit(f).lower(*args).compile().as_text()


def test_all_gather_link_factor(mesh8d):
    """all-gather of a (g·r, c) array: per-device link = (g-1)/g × the
    gathered payload."""
    g, r, c = 8, 16, 32

    def f(x):
        return jax.lax.all_gather(x, "data", axis=0, tiled=True)

    a = analyze(_spmd_hlo(f, mesh8d, P("data", None), P(None, None),
                          (g * r, c)))
    payload = g * r * c * 4
    assert a["collectives"]["all-gather"]["count"] >= 1
    assert a["link_bytes"] == pytest.approx(payload * (g - 1) / g, rel=0.01)


def test_all_reduce_link_factor(mesh8d):
    """psum: ring all-reduce moves 2(g-1)/g × the payload per device."""
    g, r, c = 8, 16, 32

    def f(x):
        return jax.lax.psum(x, "data")

    a = analyze(_spmd_hlo(f, mesh8d, P("data", None), P(None, None),
                          (g * r, c)))
    payload = r * c * 4  # per-device operand after the shard split
    assert a["collectives"]["all-reduce"]["count"] >= 1
    assert a["link_bytes"] == pytest.approx(
        2 * payload * (g - 1) / g, rel=0.01
    )


def test_collective_permute_link_is_payload(mesh8d):
    """A ring step sends exactly its payload once per device."""
    g, r, c = 8, 16, 32

    def f(x):
        perm = [(i, (i + 1) % g) for i in range(g)]
        return jax.lax.ppermute(x, "data", perm)

    a = analyze(_spmd_hlo(f, mesh8d, P("data", None), P("data", None),
                          (g * r, c)))
    payload = r * c * 4
    assert a["collectives"]["collective-permute"]["count"] >= 1
    assert a["link_bytes"] == pytest.approx(payload, rel=0.01)


def test_collective_inside_loop_is_multiplied(mesh8d):
    """The ring schedule shape: a ppermute inside a scan must be billed
    once per step — link bytes scale with the trip count."""
    g, r, c, steps = 8, 16, 32, 7

    def f(x):
        perm = [(i, (i + 1) % g) for i in range(g)]

        def body(carry, _):
            return jax.lax.ppermute(carry, "data", perm), None

        return jax.lax.scan(body, x, None, length=steps)[0]

    a = analyze(_spmd_hlo(f, mesh8d, P("data", None), P("data", None),
                          (g * r, c)))
    payload = r * c * 4
    assert a["link_bytes"] == pytest.approx(steps * payload, rel=0.05)
