"""GQA flash-decode attention Pallas kernel (one query token vs long KV).

The serving hot-spot for decode_32k/long_500k: online-softmax accumulation
over KV blocks so the [S] score row never materializes in HBM. Running
(max, sum, acc) live in VMEM scratch and persist across the sequential KV
grid dimension; the KV-length mask comes from a scalar-prefetched per-batch
length. GQA is expressed directly: the q block holds the G query heads of one
KV head, so the score block is a [G, Sb] matmul on the MXU.

Grid: (B, K, S // Sb) — last dim innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_s: int, hd: int):
    s = pl.program_id(2)
    n_s = pl.num_programs(2)
    kv_len = len_ref[pl.program_id(0)]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                   # [G, hd]
    k = k_ref[0, :, 0].astype(jnp.float32)                # [Sb, hd]
    v = v_ref[0, :, 0].astype(jnp.float32)                # [Sb, hd]
    scale = 1.0 / (hd ** 0.5)
    scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, Sb]

    kv_pos = s * block_s + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(kv_pos < kv_len, scores, NEG_INF)

    m_prev = m_ref[...]                                   # [G, 1]
    m_cur = jnp.max(scores, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)                           # [G, Sb]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)         # [G, hd]
    m_ref[...] = m_new

    @pl.when(s == n_s - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kv_len: jax.Array, *, block_s: int = 512,
                 interpret: bool) -> jax.Array:
    """q: [B, H, hd]; k/v: [B, S, K, hd]; kv_len: [B] int32 (valid prefix).
    Returns attention output [B, H, hd] (f32).
    """
    B, H, hd = q.shape
    _, S, K, _ = k.shape
    G = H // K
    assert S % block_s == 0, (S, block_s)
    qg = q.reshape(B, K, G, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, S // block_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, s, L: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, hd), lambda b, h, s, L: (b, s, h, 0)),
            pl.BlockSpec((1, block_s, 1, hd), lambda b, h, s, L: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, s, L: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),   # running max
            pltpu.VMEM((G, 1), jnp.float32),   # running denominator
            pltpu.VMEM((G, hd), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, hd=hd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), jnp.float32),
        interpret=interpret,
    )(kv_len, qg, k, v)
    return out.reshape(B, H, hd)
