"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.apss import normalize_rows
from repro.kernels.apss_block.ops import apss_block_matmul
from repro.kernels.apss_block.ref import apss_block_reference

RNG = np.random.default_rng(42)


def _corp(n, m, dtype):
    D = np.abs(RNG.standard_normal((n, m))).astype(np.float32)
    D *= RNG.random((n, m)) < 0.25
    D = np.asarray(normalize_rows(jnp.asarray(D)))
    return jnp.asarray(D, dtype)


# -- apss_block ---------------------------------------------------------------


@pytest.mark.parametrize(
    "n1,n2,m,bm,bn,bk",
    [
        (256, 256, 512, 128, 128, 256),
        (256, 128, 512, 128, 128, 512),
        (300, 200, 700, 128, 128, 256),   # ragged → padding
        (512, 512, 1024, 256, 256, 512),  # production tile
        (128, 128, 128, 128, 128, 128),
    ],
)
@pytest.mark.parametrize("t", [0.2, 0.5])
def test_apss_block_shapes(n1, n2, m, bm, bn, bk, t):
    X, Y = _corp(n1, m, jnp.float32), _corp(n2, m, jnp.float32)
    got = apss_block_matmul(X, Y, t, block_m=bm, block_n=bn, block_k=bk)
    want = apss_block_reference(X, Y, t)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_apss_block_dtypes(dtype):
    X = _corp(256, 512, dtype)
    got = apss_block_matmul(X, X, 0.3, block_m=128, block_n=128, block_k=256)
    want = apss_block_reference(X.astype(jnp.float32), X.astype(jnp.float32), 0.3)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    # bf16 inputs can flip borderline threshold decisions; compare the
    # confidently-matched region only.
    gotf = np.asarray(got, np.float32)
    wantf = np.asarray(want)
    confident = np.abs(wantf - 0.3) > 0.02
    np.testing.assert_allclose(gotf[confident], wantf[confident], atol=atol)


def test_apss_block_mask_skips_blocks():
    """An explicitly dead mask zeroes the tile even if scores pass t."""
    X = _corp(256, 256, jnp.float32)
    mask = jnp.zeros((2, 2), jnp.int32).at[0, 0].set(1)
    got = apss_block_matmul(
        X, X, 0.0, block_mask=mask, block_m=128, block_n=128, block_k=256
    )
    g = np.asarray(got)
    assert (g[128:, :] == 0).all() and (g[:, 128:] == 0).all()
    want = apss_block_reference(X, X, 0.0, block_mask=mask, block_m=128, block_n=128)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_apss_block_auto_mask_exact(corpus):
    """Auto bound mask must not change results (bounds are sound)."""
    X = jnp.asarray(np.repeat(corpus, 2, axis=0)[:256, :96])
    Xp = jnp.pad(X, ((0, 0), (0, 160)))
    blocks = dict(block_m=128, block_n=128, block_k=128)
    a = apss_block_matmul(Xp, Xp, 0.4, auto_mask=True, **blocks)
    b = apss_block_matmul(Xp, Xp, 0.4, auto_mask=False, **blocks)
    np.testing.assert_allclose(a, b, atol=1e-5)
