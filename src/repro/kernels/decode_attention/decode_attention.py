"""Pallas TPU kernel: flash-decode partial attention over a KV-cache shard.

One new token attends to a (possibly sequence-sharded) KV cache. The kernel
streams KV tiles HBM→VMEM with a running (max, sum-exp, accumulator) state
and emits the *unnormalized* partial ``(acc, m, l)`` so that shards combine
exactly with the logsumexp monoid (``ops.combine_partials`` /
``lax`` collectives in the model decode path). This is the long_500k serving
path: each `data`-axis device holds L/p cache positions; partials are the
softmax analogue of the paper's vertical partial-score accumulation.

Grid: ``(batch, q_heads, kv_blocks)``, KV innermost. VMEM per step at
defaults (bk=1024, D=128): k,v tiles 2·1024·128·2B = 0.5 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_LARGE = -0.5e30


def _decode_kernel(
    len_ref,   # (1, 1) i32 valid cache length for this batch row
    q_ref,     # (1, 1, d)
    k_ref,     # (1, 1, bk, d)
    v_ref,     # (1, 1, bk, d)
    acc_o_ref,  # (1, 1, d) f32 out
    m_o_ref,    # (1, 1) f32 out
    l_o_ref,    # (1, 1) f32 out
    acc_ref, m_ref, l_ref,  # scratch
    *,
    scale: float,
    block_k: int,
):
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_LARGE)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0, 0]
    live = j * block_k < length

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (d,)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        s = jnp.sum(k * q[None, :], axis=-1)[None, :]      # (1, bk)
        pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_LARGE)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # (1, bk)
        p = jnp.where(pos < length, p, 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        vv = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, vv, preferred_element_type=jnp.float32
        )

    @pl.when(j == nj - 1)
    def _emit():
        acc_o_ref[0, 0] = acc_ref[0, :]
        m_o_ref[0, 0] = m_ref[0, 0]
        l_o_ref[0, 0] = l_ref[0, 0]


def decode_attention_pallas(
    q: jax.Array,        # (B, Hq, D)
    k: jax.Array,        # (B, Hkv, L, D)
    v: jax.Array,        # (B, Hkv, L, D)
    lengths: jax.Array,  # (B,) i32
    *,
    scale: float | None = None,
    block_k: int = 1024,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    b, hq, d = q.shape
    hkv, L = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv
    assert L % block_k == 0, (L, block_k)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    grid = (b, hq, L // block_k)

    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k)
    lengths2d = lengths.reshape(b, 1).astype(jnp.int32)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b_, h, j: (b_, 0)),            # lengths
            pl.BlockSpec((1, 1, d), lambda b_, h, j: (b_, h, 0)),      # q
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, j, group=group: (b_, h // group, j, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, j, group=group: (b_, h // group, j, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d), lambda b_, h, j: (b_, h, 0)),
            pl.BlockSpec((1, 1), lambda b_, h, j: (b_, h)),
            pl.BlockSpec((1, 1), lambda b_, h, j: (b_, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq), jnp.float32),
            jax.ShapeDtypeStruct((b, hq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths2d, q, k, v)
    return acc, m, l
