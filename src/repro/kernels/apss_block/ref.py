"""Pure-jnp oracle for the apss_block kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.matches import SCORE_PRECISION


def apss_block_reference(
    x: jax.Array,
    y: jax.Array,
    threshold: float,
    *,
    block_mask: jax.Array | None = None,
    block_m: int = 128,
    block_n: int = 128,
) -> jax.Array:
    """Thresholded similarity scores: ``where(S ≥ t, S, 0)`` with optional
    block masking.

    ``block_mask[i, j] == 0`` declares tile ``(i, j)`` dead (the kernel skips
    its matmul); the oracle zeroes the same region so kernel and oracle agree
    for any mask. When the mask comes from ``core.pruning.block_prune_mask``
    the masked tiles provably contain no score ≥ t, so the masked and unmasked
    oracles coincide (asserted by the property tests).
    """
    s = jnp.einsum(
        "im,jm->ij", x, y,
        precision=SCORE_PRECISION,
        preferred_element_type=jnp.float32,
    )
    out = jnp.where(s >= jnp.float32(threshold), s, 0.0)
    if block_mask is not None:
        live = jnp.repeat(
            jnp.repeat(block_mask.astype(bool), block_m, axis=0),
            block_n,
            axis=1,
        )
        out = jnp.where(live, out, 0.0)
    return out
