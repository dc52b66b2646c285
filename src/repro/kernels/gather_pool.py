"""Fused gather + row-wise dequant + pool Pallas kernel (SparseLengthsSum).

The paper's embedding hot path (§4.4: lookup -> dequantize -> pool, FBGEMM's
kernel on CPU) adapted to TPU. The quantized store stays in HBM
(``memory_space=ANY``) and the kernel moves rows itself with async DMAs, the
TPU analogue of the paper's small-granularity NVMe reads.

One grid step pools ``BAGS_PER_STEP`` bags. Their indices and per-row
dequant weights arrive as SMEM blocks; the kernel starts the DMAs of the
next bag's rows before it pools the current bag (double buffering). HBM
tiles 8-bit rows eight to a DMA-able slice, so each lookup moves the
aligned 8-row group holding its row and the row is picked out in VMEM with
a sublane mask: 8x read amplification, no alignment copy of the store.
Dequant (``q * scale + bias``) and pooling run on the VPU.

Row counts must be a multiple of ``ROW_GROUP`` (``ops.aligned_rows``) and,
compiled, the row width a multiple of 128 lanes (``ops.py`` pads it).
Per-row scale and bias are gathered by XLA before the call: two ``[N, P]``
f32 arrays, small next to the rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_GROUP = 8        # 8-bit rows per HBM slice a DMA can address
BAGS_PER_STEP = 32   # bags pooled per grid step (a multiple of 8 sublanes)


def _kernel(idx_ref, w_ref, b_ref, payload_hbm, out_ref, buf, sem):
    bags, pool = idx_ref.shape
    width = out_ref.shape[1]

    def row_copy(bag, p, slot):
        base = pl.multiple_of(idx_ref[bag, p] // ROW_GROUP * ROW_GROUP,
                              ROW_GROUP)
        return pltpu.make_async_copy(payload_hbm.at[pl.ds(base, ROW_GROUP)],
                                     buf.at[slot, p], sem.at[slot])

    def fetch(bag, slot):
        def start(p, carry):
            row_copy(bag, p, slot).start()
            return carry
        jax.lax.fori_loop(0, pool, start, 0)

    fetch(0, 0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (ROW_GROUP, width), 0)

    def pool_bag(bag, carry):
        slot = bag % 2

        @pl.when(bag + 1 < bags)
        def _prefetch():
            fetch(bag + 1, 1 - slot)

        def add_row(p, acc):
            row_copy(bag, p, slot).wait()
            group = buf[slot, p].astype(jnp.int32).astype(jnp.float32)
            val = group * w_ref[bag, p] + b_ref[bag, p]
            return acc + jnp.where(sub == idx_ref[bag, p] % ROW_GROUP, val, 0.0)

        acc = jax.lax.fori_loop(0, pool, add_row,
                                jnp.zeros((ROW_GROUP, width), jnp.float32))
        out_ref[pl.ds(bag, 1), :] = jnp.sum(acc, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, bags, pool_bag, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pool(payload: jax.Array, scale: jax.Array, bias: jax.Array,
                indices: jax.Array, *, interpret: bool) -> jax.Array:
    """payload: [R, D] int8/uint8 quantized rows, R a multiple of
    ``ROW_GROUP``; scale/bias: [R] f32; indices: [N, P] int32.
    Returns pooled bags [N, D] f32."""
    N, P = indices.shape
    R, D = payload.shape
    if R % ROW_GROUP:
        raise ValueError(f"payload rows ({R}) must be a multiple of "
                         f"{ROW_GROUP}")
    idx = jnp.pad(indices, ((0, -N % BAGS_PER_STEP), (0, 0)))
    n_pad = idx.shape[0]
    smem = pl.BlockSpec((BAGS_PER_STEP, P), lambda i: (i, 0),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // BAGS_PER_STEP,),
        in_specs=[smem, smem, smem, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((BAGS_PER_STEP, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, P, ROW_GROUP, D), payload.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        name="gather_pool",
    )(idx, scale[idx], bias[idx], payload)
    return out[:N]
