"""Pallas TPU kernel: causal GQA flash attention (forward / prefill path).

IO-aware attention (FlashAttention, arXiv:2205.14135) adapted to the TPU
memory hierarchy: Q/K/V stream HBM→VMEM in MXU-aligned tiles, the softmax
running statistics (row max ``m``, row sum ``l``) and the output accumulator
live in VMEM scratch across the KV grid axis, and causally-dead KV tiles are
skipped with ``@pl.when`` (the same tile-predication idea as the APSS block
kernel — the APSS block bound mask and the causal mask are both
tile-granular pruning).

Grid: ``(batch, q_heads, q_blocks, kv_blocks)``, KV innermost.
GQA is handled in the K/V index maps (``kv_head = q_head // group``), so no
repeated K/V materialization in HBM.

VMEM per step (defaults bq=bk=512, D=128, bf16 in / f32 acc):
q,k,v tiles 3·512·128·2B ≈ 0.4 MB + acc 512·128·4B ≈ 0.26 MB « 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_LARGE = -0.5e30  # finite stand-in for -inf (keeps exp() NaN-free)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_LARGE)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal tile skip: KV tile strictly above the diagonal band is dead.
    live = (j * block_k <= (i + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale   # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # (bq, bk)
        if causal:
            qi = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            ki = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qi >= ki, s, NEG_LARGE)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(j == nj - 1)
    def _emit():
        l = l_ref[...]
        o = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Hq, S, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    scale: float | None = None,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    grid = (b, hq, s // block_q, s // block_k)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, block_q=block_q, block_k=block_k, causal=causal,
    )
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, i, j, group=group: (b_, h // group, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, i, j, group=group: (b_, h // group, j, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h, i, j: (b_, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
