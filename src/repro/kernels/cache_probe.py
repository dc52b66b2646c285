"""Set-associative cache probe Pallas kernel (the FM row-cache hot path, §4.3).

One grid step probes ``QUERIES_PER_STEP`` queries. Each query's set id, key
and the set's ``W`` tags arrive as SMEM blocks; the way match is a
scalar compare over the ``W`` ways, and a hit starts one DMA of the hit row
(``[1, D]`` f32) from the HBM cache data straight into the output block.
Misses write zeros. The step waits for its row DMAs before it ends.

The tag line of each query's set is gathered by XLA before the call, as a
``[W, N]`` block with queries in the lanes: a ``[Sets, W]`` int32 plane
keeps ``W`` in the 128-lane dim, so one of its rows is not a slice a DMA
can address, and SMEM pads a 2-D block's minor dim to 128 words. 1-D SMEM
blocks must be multiples of 1024 words to match XLA's layout of 1-D
arrays.

Outputs: values [N, D] (zeros on miss), hit [N] int32. The lowest matching
way wins, as in ``JaxRowCache.lookup``; a valid cache holds a key at most
once per set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QUERIES_PER_STEP = 1024   # 1-D SMEM blocks: a multiple of XLA's T(1024)


def _kernel(sets_ref, qt_ref, qr_ref, tt_ref, tr_ref, data_hbm,
            out_ref, hit_ref, sem):
    ways, queries = tt_ref.shape
    width = out_ref.shape[1]

    def row_copy(j, way):
        return pltpu.make_async_copy(
            data_hbm.at[sets_ref[j], pl.ds(way, 1)],
            out_ref.at[pl.ds(j, 1)], sem.at[0])

    def probe(j, n_hit):
        qt, qr = qt_ref[j], qr_ref[j]
        way = jnp.int32(-1)
        for w in reversed(range(ways)):          # lowest matching way wins
            match = (tt_ref[w, j] == qt) & (tr_ref[w, j] == qr)
            way = jnp.where(match, jnp.int32(w), way)
        hit = way >= 0
        hit_ref[j] = hit.astype(jnp.int32)

        @pl.when(hit)
        def _fetch():
            row_copy(j, way).start()

        @pl.when(~hit)
        def _zero():
            out_ref[pl.ds(j, 1), :] = jnp.zeros((1, width), jnp.float32)

        return n_hit + hit.astype(jnp.int32)

    n_hit = jax.lax.fori_loop(0, queries, probe, jnp.int32(0))

    def drain(i, carry):
        row_copy(0, 0).wait()
        return carry

    jax.lax.fori_loop(0, n_hit, drain, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_probe(tag_table: jax.Array, tag_row: jax.Array, data: jax.Array,
                q_table: jax.Array, q_row: jax.Array, sets: jax.Array,
                *, interpret: bool):
    """tag_table/tag_row: [Sets, W] int32; data: [Sets, W, D] f32;
    q_table/q_row: [N] int32; sets: [N] int32 (precomputed set ids).
    Returns (values [N, D] f32, hit [N] int32)."""
    N = q_table.shape[0]
    S, W, D = data.shape
    pad = (0, -N % QUERIES_PER_STEP)
    sets_p = jnp.pad(sets, pad)
    n_pad = sets_p.shape[0]
    tt = tag_table.T[:, sets_p]                 # [W, n_pad] tag lines
    tr = tag_row.T[:, sets_p]
    q = pl.BlockSpec((QUERIES_PER_STEP,), lambda i: (i,),
                     memory_space=pltpu.SMEM)
    lines = pl.BlockSpec((W, QUERIES_PER_STEP), lambda i: (0, i),
                         memory_space=pltpu.SMEM)
    values, hit = pl.pallas_call(
        _kernel,
        grid=(n_pad // QUERIES_PER_STEP,),
        in_specs=[q, q, q, lines, lines, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((QUERIES_PER_STEP, D), lambda i: (i, 0)), q],
        out_shape=[jax.ShapeDtypeStruct((n_pad, D), jnp.float32),
                   jax.ShapeDtypeStruct((n_pad,), jnp.int32)],
        scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        interpret=interpret,
        name="cache_probe",
    )(sets_p, jnp.pad(q_table, pad), jnp.pad(q_row, pad), tt, tr, data)
    return values[:N], hit[:N]
